"""The Qwen3-MoE family at a CPU size: its plain reference against the
program's prefill, the fp8 control, its counts, and the two metric
readers of its cell."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import peaks
from bench.lib import cell, registry
from bench.models.qwen3_moe import counts, program, reference, weights
from bench.tests.tiny_moe import tiny_moe_config

CELL = "qwen3-235b-a22b-recalkv.longout"


def real_cfg():
    return json.loads((registry.ROOT / "bench/configs/"
                       "qwen3-235b-a22b-recalkv.json").read_text())


@pytest.mark.parametrize("kind", ["recalkv", "dense"])
@pytest.mark.parametrize("backend", ["einsum", "pallas"])
def test_reference_matches_program_prefill(kind, backend):
    """The program in float32 on the same weights: sound runs read under
    1e-6 of the largest logit (summation order only); scaling the MoE part
    by 0.9 reads 2%, a routing flip far more."""
    from repro.models import transformer as T
    cfg = tiny_moe_config(kind)
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     weights.init(cfg, 2 ** 31 + 29))
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    mc = dataclasses.replace(program.model_config(cfg), attn_backend=backend,
                             dtype=jnp.float32)
    got, _ = T.prefill(mc, program.params(cfg, w),
                       jnp.asarray(toks[None], jnp.int32),
                       jnp.asarray([40]), max_len=64)
    ref = reference.logits(cfg, w, toks, [39])
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(got[0] - ref[0]))) < 1e-4 * scale


def test_fp8_control_fails():
    cfg = tiny_moe_config()
    w = weights.init(cfg, 2 ** 31 + 29)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    ref = reference.logits(cfg, w, toks, [39])
    fp8 = reference.logits(cfg, w, toks, [39], precision="fp8")
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(fp8[0] - ref[0]))) > 0.03 * scale


def test_reference_leaves_out_the_experts_not_held():
    """The reference honours the held range: holding experts 0..7 of the
    16 that one router spans changes the logits."""
    cfg = tiny_moe_config(first_held=0, held=16)
    w = weights.init(cfg, 7)
    toks = np.arange(24) % cfg["vocab_size"]
    whole = reference.logits(cfg, w, toks, [23])
    part = tiny_moe_config(first_held=0, held=8)
    pw = dict(w, layers={k: (v[:, :8] if k.startswith("w_") else v)
                         for k, v in w["layers"].items()})
    half = reference.logits(part, pw, toks, [23])
    assert float(jnp.max(jnp.abs(whole - half))) > 1e-3


def test_cache_and_weight_bytes_hand_reckoned():
    c = real_cfg()
    # 8 layers x 1 group x (256 + 256) x 2 B
    assert counts.cache_bytes_per_position(c) == 8192
    d, f, V, L = 4096, 1536, 151936, 8
    attn = d * 64 * 128 + 2 * d * 256 + 256 * 512 + 64 * 256 * d
    experts = 16 * 3 * d * f
    per_layer = 2 * (attn + experts) + 4 * (d * 128 + 2 * d + 2 * 128)
    want = L * per_layer + 2 * 2 * V * d + 4 * d
    assert counts.weight_bytes(c) == want
    assert 8.9e9 < want < 9.1e9


def test_weight_bytes_match_the_generated_shapes():
    c = real_cfg()
    s = weights.shapes(c)
    total = 0
    for shape, dtype, _ in [s["embed"], s["lm_head"], s["final_norm"],
                            *s["layers"].values()]:
        total += int(np.prod(shape)) * (2 if dtype.__name__ == "bfloat16"
                                        else 4)
    assert total == counts.weight_bytes(c)


def test_active_expert_flops():
    c = real_cfg()
    d, f = 4096, 1536
    assert counts.expert_row_flops(c) == 2 * 3 * d * f
    assert counts.expert_weight_bytes(c) == 3 * d * f * 2
    # 8 of 128 experts per token, 16 held here: one expert's rows expected
    lin = counts._linear_flops_per_token(c)
    attn = d * 64 * 128 + 2 * d * 256 + 64 * 256 * d
    assert lin == 8 * (2 * (attn + d * 128) + 2 * 3 * d * f)
    ctx = 1000
    assert counts.decode_token_flops(c, ctx) == (
        lin + counts.decode_attn_flops(c, ctx) + 2 * d * 151936)


M0 = {"moe_decode_rows": 100, "moe_decode_groups": 40}
M1 = {"moe_decode_rows": 4100, "moe_decode_groups": 2040}


def _record(trace, m0=M0, m1=M1):
    return cell.Record(cell=registry.resolve(CELL), counts=counts,
                       peaks=peaks.peaks_for("TPU v5 lite"), t0=0.0,
                       t1=10.0, setup_s=1.0, live=[], eng_start=m0,
                       eng_end=m1, memory_peak_bytes=0, trace=trace)


def read(name, rec):
    return registry.metric_reader(name)(rec)


def test_rows_per_read_differences_the_counters():
    assert read("expert_rows_per_read", _record(None)) == pytest.approx(2.0)
    assert read("expert_rows_per_read", _record(None, {}, {})) is None


def test_expert_roofline_reads_100_at_the_bound():
    c = real_cfg()
    rows, groups = 4000, 2000
    nbytes = (groups * counts.expert_weight_bytes(c)
              + rows * counts.expert_row_bytes(c))
    t = max(nbytes / 819e9, rows * counts.expert_row_flops(c) / 197e12)
    trace = {"device_ops": [["jit_counted_fn/fusion", 9.0],
                            ["jit_counted_fn/expert_gmm", t]]}
    assert read("expert_decode_roofline", _record(trace)) == \
        pytest.approx(100.0)
    trace["device_ops"][1][1] = 4 * t
    assert read("expert_decode_roofline", _record(trace)) == \
        pytest.approx(25.0)


def test_expert_roofline_is_silent_without_its_op():
    assert read("expert_decode_roofline", _record(None)) is None
    trace = {"device_ops": [["jit_counted_fn/fusion", 9.0]]}
    assert read("expert_decode_roofline", _record(trace)) is None
    # a program without the MoE counters (the parent's) reads nothing
    trace = {"device_ops": [["jit_counted_fn/expert_gmm", 1.0]]}
    assert read("expert_decode_roofline", _record(trace, {}, {})) is None
