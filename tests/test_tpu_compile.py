"""Compile every Pallas kernel for a described TPU v5e at qwen3-4b widths.

Interpret mode (every other kernel test) cannot see Mosaic's tiling
rules: a block whose last two dims are not (8, 128)-aligned or whole is
refused only when the kernel lowers for a real chip.  These tests lower
and compile for a v5e:2x2 topology that is described, not attached — the
TPU compiler runs on the host and nothing executes.

Widths are qwen3-4b's (32 query heads, 8 kv heads, head_dim 128); the
latent cache is ReCalKV at keep ratio 0.5 with group size 4 (2 groups,
rank 256), the engine's 8 slots and a 2048-position ring plus the
deferred self column.  Qwen3-235B-A22B's share adds the latent decode
kernel with one group of its 4 kv heads (64 query rows per group) at 32
slots and a 4096-position ring, the held-expert grouped matmul at its
widths (d 4096, expert width 1536, 16 experts held), over one layer's
experts and over a layer stack, and its whole decode step at 2 layers.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.kernels.expert_gmm import expert_gmm
from repro.kernels.flash_prefill import flash_prefill_attention
from repro.kernels.latent_decode import (latent_decode_attention,
                                         latent_decode_attention_mq,
                                         latent_decode_attention_mq_paged,
                                         latent_decode_attention_paged)
from repro.kernels.latent_decode_q import (latent_decode_attention_mq_quant,
                                           latent_decode_attention_quant)
from repro.models import transformer as T
from repro.models.config import ModelConfig, MoEConfig, ReCalKVRuntime

B, S, DH, NQ = 8, 2048 + 1, 128, 3
HALF = DH // 2
PAGE, N_SP = 16, 128
N_PAGES = B * N_SP + 1
BF, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
# (groups, rank, query rows per group, group-slots): dense caches ride the
# latent kernels as one kv head per group with identity reconstruction.
DENSE = (8, DH, 4, 1)
LATENT = (2, 256, 16, 4)
# Qwen3-235B-A22B: 64 query heads over 4 kv heads, one group of 4
LATENT_G1 = (1, 256, 64, 4)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one; keep it out entirely."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _ring(geom, quant=False, nq=None, B=B, S=S):
    G, r, Hg, s = geom
    cols = S if nq is None else S + nq
    rows = Hg if nq is None else nq * Hg
    bias = (B, cols) if nq is None else (B, nq, cols)
    cache = ([((B, cols, G, r), I8), ((B, cols, G), F32),
              ((B, cols, G, r), I8), ((B, cols, G), F32)] if quant
             else [((B, cols, G, r), BF), ((B, cols, G, r), BF)])
    return ([((B, G, rows, DH), BF)] + cache
            + [((G, r, s * DH), BF), ((B, cols, HALF), F32),
               ((B, cols, HALF), F32), (bias, F32)])


def _paged(geom, nq=None):
    G, r, Hg, s = geom
    cols = (N_SP + 1) * PAGE
    rows = Hg if nq is None else nq * Hg
    bias = (B, cols) if nq is None else (B, nq, cols)
    return [((B, N_SP), I32), ((B, G, rows, DH), BF),
            ((N_PAGES, PAGE, G, r), BF), ((N_PAGES, PAGE, G, r), BF),
            ((G, r, s * DH), BF), ((B, PAGE, G, r), BF),
            ((B, PAGE, G, r), BF), ((B, cols, HALF), F32),
            ((B, cols, HALF), F32), (bias, F32)]


def _prefill(hv, dv, T=1024):
    return [((B, T, 32, DH), BF), ((B, T, 8, DH), BF), ((B, T, hv, dv), BF)]


KN = ((DH,), F32)
CASES = {
    "dense_decode": (latent_decode_attention, _ring(DENSE), False),
    "latent_decode": (latent_decode_attention, _ring(LATENT), True),
    "latent_decode_one_group": (latent_decode_attention,
                                _ring(LATENT_G1, B=32, S=4096 + 1), True),
    "int8_decode": (latent_decode_attention_quant, _ring(LATENT, True), True),
    "dense_verify": (latent_decode_attention_mq, _ring(DENSE, nq=NQ), False),
    "latent_verify": (latent_decode_attention_mq, _ring(LATENT, nq=NQ), True),
    "int8_verify": (latent_decode_attention_mq_quant,
                    _ring(LATENT, True, nq=NQ), True),
    "dense_decode_paged": (latent_decode_attention_paged, _paged(DENSE),
                           False),
    "latent_decode_paged": (latent_decode_attention_paged, _paged(LATENT),
                            True),
    "latent_verify_paged": (latent_decode_attention_mq_paged,
                            _paged(LATENT, nq=NQ), True),
    "prefill_dense": (flash_prefill_attention, _prefill(8, DH), None),
    "prefill_latent_values": (flash_prefill_attention, _prefill(2, 256),
                              None),
    "prefill_ragged_tail": (flash_prefill_attention, _prefill(8, DH, 300),
                            None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    kernel, shapes, knorm = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    if knorm is None:
        def fn(*a):
            return kernel(*a, interpret=False)
    else:
        if knorm:
            args.append(jax.ShapeDtypeStruct(*KN, sharding=one_chip))

        def fn(*a):
            kn = a[-1] if knorm else None
            a = a[:-1] if knorm else a
            return kernel(*a, scale=DH ** -0.5, interpret=False, k_norm=kn)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("hv,dv", [(8, DH), (2, 256)],
                         ids=["dense", "latent_values"])
def test_prefill_compiles_on_a_sharded_mesh(hv, dv, topo,
                                            no_persistent_cache):
    """Prefill over weights sharded on a (1, 4) mesh: pjit cannot
    partition a Mosaic kernel, so ``ops.flash_prefill(mesh=)`` must put it
    under shard_map."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    args = [jax.ShapeDtypeStruct(s, BF, sharding=NamedSharding(
                mesh, P(None, None, "model" if s[2] % 4 == 0 else None)))
            for s, _ in _prefill(hv, dv)]

    def fn(q, k, v):
        return ops.flash_prefill(q, k, v, interpret=False, mesh=mesh)

    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (rows, K, N[, layers]): the 32 x 8 decode rows and the 4096 x 8 prefill
# bucket through the gate/up (d -> f) and down (f -> d) products; with
# layers, the weights are an 8-layer stack read at a traced layer
EXPERT_CASES = {
    "decode_gate_up": (32 * 8, 4096, 1536),
    "decode_down": (32 * 8, 1536, 4096),
    "prefill_gate_up": (4096 * 8, 4096, 1536),
    "prefill_down": (4096 * 8, 1536, 4096),
    "decode_gate_up_stacked": (32 * 8, 4096, 1536, 8),
    "decode_down_stacked": (32 * 8, 1536, 4096, 8),
    "prefill_down_stacked": (4096 * 8, 1536, 4096, 8),
}


@pytest.mark.parametrize("name", sorted(EXPERT_CASES))
def test_expert_gmm_compiles_for_v5e(name, one_chip, no_persistent_cache):
    m, k, n, *stack = EXPERT_CASES[name]
    args = [jax.ShapeDtypeStruct((m, k), BF, sharding=one_chip),
            jax.ShapeDtypeStruct((*stack, 16, k, n), BF, sharding=one_chip),
            jax.ShapeDtypeStruct((16,), I32, sharding=one_chip)]
    if stack:
        args.append(jax.ShapeDtypeStruct((), I32, sharding=one_chip))

    def fn(x, w, g, layer=None):
        return expert_gmm(x, w, g, layer=layer, interpret=False)

    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "expert_gmm" in text


def test_moe_decode_step_reads_held_experts_in_place(one_chip,
                                                     no_persistent_cache,
                                                     monkeypatch):
    """The decode step of Qwen3-235B-A22B's share (2 scanned layers, 32
    slots, ring 4096, caches donated) hands ``expert_gmm`` the layer
    stack itself: no instruction has one layer's held-expert shape, which
    is what a slice copied out of the scanned stack would have."""
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    cfg = ModelConfig(
        name="qwen3-235b-a22b-share", family="moe", num_layers=2,
        d_model=4096, num_heads=64, num_kv_heads=4, d_head=DH, d_ff=1536,
        vocab_size=151936, qk_norm=True,
        moe=MoEConfig(num_experts=128, top_k=8, d_expert=1536, num_held=16),
        recalkv=ReCalKVRuntime(rank_k=256, rank_v=256, group_size=4),
        attn_backend="pallas", dtype=BF)
    slots, ring = 32, 4096

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(functools.partial(T.init_params, cfg),
                                   jax.random.PRNGKey(0)))
    caches = placed(jax.eval_shape(
        functools.partial(T.init_decode_cache, cfg, slots, ring)))
    assert params["blocks"][0]["mlp"]["wg"].shape == (2, 16, 4096, 1536)
    step = jax.jit(functools.partial(T.decode_step, cfg),
                   donate_argnums=(1,))
    text = step.lower(params, caches,
                      jax.ShapeDtypeStruct((slots,), I32, sharding=one_chip),
                      jax.ShapeDtypeStruct((slots,), I32, sharding=one_chip),
                      jax.ShapeDtypeStruct((slots,), jnp.bool_,
                                           sharding=one_chip),
                      ).compile().as_text()
    assert "expert_gmm" in text and "tpu_custom_call" in text
    for shape in ("[16,4096,1536]", "[16,1536,4096]"):
        assert f"bf16{shape}" not in text, shape
