"""Sharding rules + a subprocess mini dry-run (8 placeholder devices).

The full 512-device dry-run lives in launch/dryrun.py and runs as its own
process (results in experiments/dryrun.jsonl); here we verify the rule
machinery on every arch and actually lower train+decode on a small mesh.
"""

import functools
import json
import math
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import ARCHS, get_config
from repro.models import transformer as T
from repro.sharding import rules

KEY = jax.ShapeDtypeStruct((2,), jnp.uint32)


def fake_mesh(shape, axes):
    """AbstractMesh stands in for a device mesh in pure spec computations."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_divisible_full_configs(arch):
    """Every assigned FULL config gets valid (divisible) specs on 16x16."""
    cfg = get_config(arch)
    mesh = fake_mesh((16, 16), ("data", "model"))
    shapes = jax.eval_shape(functools.partial(T.init_params, cfg), KEY)
    specs = rules.param_specs(shapes, mesh)

    def check(path, leaf, spec):
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            axes = axis if isinstance(axis, tuple) else (axis,)
            n = math.prod(mesh.shape[a] for a in axes)
            assert leaf.shape[dim] % n == 0, (path, leaf.shape, spec)

    flat_shapes = jax.tree_util.tree_flatten_with_path(shapes)[0]
    flat_specs = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_shapes) == len(flat_specs)
    for (path, leaf), spec in zip(flat_shapes, flat_specs):
        check(path, leaf, spec)


@pytest.mark.parametrize("arch", ["qwen3-4b", "deepseek-v3-671b",
                                  "falcon-mamba-7b", "gemma3-12b"])
def test_cache_specs_shard_sequence(arch):
    from repro.configs import RECALKV_APPLICABLE
    kw = {"recalkv_ratio": 0.5} if RECALKV_APPLICABLE[arch] else {}
    cfg = get_config(arch, **kw)
    mesh = fake_mesh((16, 16), ("data", "model"))
    caches = jax.eval_shape(
        functools.partial(T.init_decode_cache, cfg, 128, 32768))
    specs = rules.cache_specs(caches, mesh)
    found_seq_shard = False
    for spec in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)):
        if "model" in tuple(spec):
            found_seq_shard = True
    assert found_seq_shard, f"{arch}: no cache leaf sequence-sharded"


def test_moe_experts_sharded():
    cfg = get_config("qwen3-moe-235b-a22b")
    mesh = fake_mesh((16, 16), ("data", "model"))
    shapes = jax.eval_shape(functools.partial(T.init_params, cfg), KEY)
    specs = rules.param_specs(shapes, mesh)
    wi_spec = specs["blocks"][0]["mlp"]["wi"]
    # leading dim is the scan stack; then (E, d, f): E->model, d->data (fsdp)
    assert tuple(wi_spec) [1] == "model"
    assert tuple(wi_spec)[2] == "data"


def test_zero3_spans_pods_for_giant_leaves():
    cfg = get_config("deepseek-v3-671b")
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    shapes = jax.eval_shape(functools.partial(T.init_params, cfg), KEY)
    specs = rules.param_specs(shapes, mesh)
    wi_spec = tuple(specs["blocks"][0]["mlp"]["wi"])
    assert wi_spec[1] == "model"
    assert wi_spec[2] == ("data", "pod")  # ZeRO-3 across pods


def test_batch_specs_use_pod_and_data():
    mesh = fake_mesh((2, 16, 16), ("pod", "data", "model"))
    b = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32)}
    spec = rules.batch_specs(b, mesh)["tokens"]
    assert tuple(spec)[0] == ("pod", "data")


def test_make_test_mesh_guards_device_count():
    """The shared mesh helper must not silently hand out an unbuildable
    mesh: raise by default with the XLA_FLAGS hint, shrink toward (1, 1)
    with degrade=True.  (On hosts with >= the requested devices the
    request is honored as-is — both branches still hold.)"""
    from repro.launch.mesh import make_test_mesh
    have = len(jax.devices())
    big = 2 * have
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_test_mesh(big, big)
    mesh = make_test_mesh(big, big, degrade=True)
    assert tuple(mesh.axis_names) == ("data", "model")
    assert math.prod(mesh.shape.values()) <= have


def test_carry_specs_shard_slot_axis():
    """The serving engine's device carry shards dim 0 (the slot axis)
    over the batch axes when divisible, else replicates."""
    mesh = fake_mesh((2, 4), ("data", "model"))
    st = {
        "tok": jax.ShapeDtypeStruct((8,), jnp.int32),
        "keys": jax.ShapeDtypeStruct((8, 2), jnp.uint32),
        "buf": jax.ShapeDtypeStruct((8, 16), jnp.int32),
    }
    specs = rules.carry_specs(st, mesh)
    assert tuple(specs["tok"]) == ("data",)
    assert tuple(specs["keys"])[0] == "data"
    assert tuple(specs["buf"])[0] == "data"
    odd = rules.carry_specs({"tok": jax.ShapeDtypeStruct((7,), jnp.int32)},
                            mesh)
    assert all(a is None for a in tuple(odd["tok"]))


def test_slot_stacked_spec():
    mesh = fake_mesh((2, 4), ("data", "model"))
    assert tuple(rules.slot_stacked_spec(8, mesh)) == (None, "data")
    assert tuple(rules.slot_stacked_spec(7, mesh)) == ()


def test_param_specs_head_grain():
    """With grains given, attention projections never shard inside a
    head: Hkv*dh = 16 over model=4 would tile 4-wide across dh=8."""
    mesh = fake_mesh((2, 4), ("data", "model"))
    grains = {"wk": 8, "wq": 8}
    wk = {"wk": jax.ShapeDtypeStruct((64, 16), jnp.float32)}
    free = rules.param_specs(wk, mesh)["wk"]
    assert tuple(free)[-1] == "model"            # shape-only rule shards it
    grained = rules.param_specs(wk, mesh, grains=grains)["wk"]
    assert "model" not in tuple(grained)          # head grain forbids it
    wq = {"wq": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    assert tuple(rules.param_specs(wq, mesh, grains=grains)["wq"])[-1] == "model"


def test_head_grains_cover_mla_projections():
    """MLA's per-head widths differ from d_head, and wkv_a's whole
    latent ‖ rope output is one grain (rmsnorm + rope operate on it as a
    unit) — TP must never split any of them."""
    cfg = get_config("deepseek-v3-671b", smoke=True)
    grains = rules.head_grains(cfg)
    a = cfg.mla
    assert grains == {"wq_b": a.qk_nope_dim + a.qk_rope_dim,
                      "wkv_a": a.kv_lora_rank + a.qk_rope_dim,
                      "wkv_b": a.qk_nope_dim + a.v_head_dim}
    dense = get_config("qwen3-4b", smoke=True)
    assert rules.head_grains(dense) == {
        "wq": dense.d_head, "wk": dense.d_head, "wv": dense.d_head}


MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools, json
    import jax, jax.numpy as jnp
    from repro.configs import get_config, RECALKV_APPLICABLE
    from repro.models import transformer as T
    from repro.sharding import rules
    from repro.optim import AdamWConfig, init_state
    from repro.runtime import TrainConfig, make_train_step
    from repro.launch import hlo_analysis as H
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh(2, 4)
    named = lambda t: rules.to_named(t, mesh)
    KEY = jax.ShapeDtypeStruct((2,), jnp.uint32)
    out = {}
    for arch in ("qwen3-4b", "deepseek-v3-671b", "recurrentgemma-9b"):
        cfg = get_config(arch, smoke=True)
        p = jax.eval_shape(functools.partial(T.init_params, cfg), KEY)
        opt_cfg = AdamWConfig()
        o = jax.eval_shape(functools.partial(init_state, cfg=opt_cfg), p)
        b = {"tokens": jax.ShapeDtypeStruct((16, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((16, 32), jnp.int32)}
        fn = make_train_step(cfg, opt_cfg, TrainConfig(microbatches=2))
        with mesh:
            comp = jax.jit(fn, in_shardings=(
                named(rules.param_specs(p, mesh)),
                named(rules.opt_specs(o, None, mesh)),
                named(rules.batch_specs(b, mesh))),
                donate_argnums=(0, 1)).lower(p, o, b).compile()
        st = H.collective_stats(comp.as_text())
        out[arch] = {"train_collective_bytes": st.total_bytes,
                     "flops": H.cost_report(comp)["flops"]}
    print("RESULT " + json.dumps(out))
""")


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """End-to-end pjit lowering on 8 placeholder devices (own process so
    the forced device count cannot leak into other tests)."""
    res = subprocess.run(
        [sys.executable, "-c", MINI_DRYRUN], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root"}, cwd="/root/repo", timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][0]
    data = json.loads(line[len("RESULT "):])
    assert set(data) == {"qwen3-4b", "deepseek-v3-671b", "recurrentgemma-9b"}
    for arch, rec in data.items():
        assert rec["flops"] > 0, arch
