"""Operation and byte counts pinned to hand-reckoned values."""

import json

import pytest

from bench import peaks
from bench.lib import registry
from bench.models.qwen3 import counts, weights


def cfg(name):
    return json.loads((registry.ROOT / f"bench/configs/{name}.json")
                      .read_text())


def test_cache_bytes_per_position():
    # 36 layers x 2 groups x (256 + 256) x 2 B
    assert counts.cache_bytes_per_position(cfg("qwen3-4b-recalkv")) == 73728
    # 36 layers x 8 heads x 128 x (K and V) x 2 B
    assert counts.cache_bytes_per_position(cfg("qwen3-4b-dense")) == 147456


def test_weight_bytes_hand_reckoned():
    d, f, V, L = 2560, 9728, 151936, 36
    mlp = 3 * d * f
    norms = 4 * (2 * d + 2 * 128)
    dense_attn = d * 4096 + 2 * d * 1024 + 4096 * d
    want = L * (2 * (dense_attn + mlp) + norms) + 2 * V * d + 4 * d
    assert counts.weight_bytes(cfg("qwen3-4b-dense")) == want == 8045328384
    lat_attn = d * 4096 + 2 * 2 * d * 256 + 2 * 256 * 512 + 32 * 256 * d
    want = L * (2 * (lat_attn + mlp) + norms) + 2 * V * d + 4 * d
    assert counts.weight_bytes(cfg("qwen3-4b-recalkv")) == want


@pytest.mark.parametrize("name", ["qwen3-4b-recalkv", "qwen3-4b-dense"])
def test_weight_bytes_match_the_generated_shapes(name):
    c = cfg(name)
    total = 0
    for shape, dtype, _ in [weights.shapes(c)["embed"],
                            weights.shapes(c)["final_norm"],
                            *weights.shapes(c)["layers"].values()]:
        n = 1
        for s in shape:
            n *= s
        total += n * (2 if dtype.__name__ == "bfloat16" else 4)
    assert total == counts.weight_bytes(c)


def test_decode_flops_hand_reckoned():
    c = cfg("qwen3-4b-dense")
    ctx = 1000
    attn = 36 * (2 * 32 * 128 * ctx * 2)
    assert counts.decode_attn_flops(c, ctx) == attn
    lat = cfg("qwen3-4b-recalkv")
    rebuild = 36 * 2 * 2 * 256 * 512 * ctx
    attn = 36 * (2 * 32 * 128 + 2 * 32 * 256) * ctx
    assert counts.decode_attn_flops(lat, ctx) == rebuild + attn


def test_prefill_flops_count_causal_pairs():
    c = cfg("qwen3-4b-dense")
    assert counts.prefill_attn_flops(c, 3) == 36 * 2 * 2 * 32 * 128 * 6


def test_roofline_share_cannot_exceed_one_at_the_bound():
    p = peaks.peaks_for("TPU v5 lite")
    for c in (cfg("qwen3-4b-recalkv"), cfg("qwen3-4b-dense")):
        f, b = counts.decode_attn_flops(c, 3000), counts.decode_attn_bytes(c, 3000)
        t = peaks.roofline_seconds(f, b, p)
        assert f / t <= p["bf16_flops_per_s"] * (1 + 1e-12)
        assert b / t <= p["hbm_bytes_per_s"] * (1 + 1e-12)
        assert max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"]) / t \
            == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v4")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
