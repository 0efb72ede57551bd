"""Operations and bytes that one chip's share of a Qwen3-MoE decoder
needs, from the configuration's shapes alone, with the functions of
``bench.models.qwen3.counts`` under the same names.  Attention is Qwen3's
(counted over the live context only, the ReCalKV key rebuild as model
work).  The MoE block counts its active experts: a token is routed to k of
the E experts, and k * Eh / E of those are expected to be held here (one
of 8 in the benchmarked cut), which is all this chip computes for it; the
router is counted whole.  Model FLOPs count each multiply-add as 2."""

from __future__ import annotations

from bench.models.qwen3.counts import (BF16, attn_flops,  # noqa: F401
                                       cache_bytes_per_position,
                                       decode_attn_bytes, decode_attn_flops,
                                       decode_step_fixed_bytes,
                                       lm_head_flops, prefill_attn_bytes,
                                       prefill_attn_flops)
from bench.models.qwen3_moe.weights import dims

F32 = 4


def weight_params(cfg: dict) -> dict:
    """Parameters by part: per layer (attention, held experts, router),
    embedding and lm_head (each V x d)."""
    n = dims(cfg)
    d, H, Hkv, dh, f = n["d"], n["H"], n["Hkv"], n["dh"], n["f"]
    if n["latent"]:
        G, s, rk, rv = n["G"], n["s"], n["rk"], n["rv"]
        attn = d * H * dh + G * d * (rk + rv) + G * rk * s * dh + H * rv * d
    else:
        attn = d * H * dh + 2 * d * Hkv * dh + H * dh * d
    return {"attn": attn, "experts": n["Eh"] * 3 * d * f,
            "router": d * n["E"], "norms": 2 * d + 2 * dh,
            "embed": n["V"] * d, "lm_head": d * n["V"], "final_norm": d}


def weight_bytes(cfg: dict) -> int:
    """Bytes of the served weights: bf16 matrices, float32 router and norm
    gains."""
    n, p = dims(cfg), weight_params(cfg)
    per_layer = ((p["attn"] + p["experts"]) * BF16
                 + (p["router"] + p["norms"]) * F32)
    return (n["L"] * per_layer + (p["embed"] + p["lm_head"]) * BF16
            + p["final_norm"] * F32)


def expert_row_flops(cfg: dict) -> int:
    """One (token, expert) row through an expert: gate, up and down."""
    n = dims(cfg)
    return 2 * 3 * n["d"] * n["f"]


def expert_weight_bytes(cfg: dict) -> int:
    """One expert's three matrices, read once per call that uses it."""
    n = dims(cfg)
    return 3 * n["d"] * n["f"] * BF16


def expert_row_bytes(cfg: dict) -> int:
    """One (token, expert) row's activations read in and written out."""
    return 2 * dims(cfg)["d"] * BF16


def _linear_flops_per_token(cfg: dict) -> int:
    """Weight matmuls of one token through every layer (no lm_head): the
    attention projections, the whole router, and the expected share of its
    k experts that is held here."""
    n, p = dims(cfg), weight_params(cfg)
    attn = p["attn"]
    if n["latent"]:
        attn -= n["G"] * n["rk"] * n["s"] * n["dh"]
    experts = expert_row_flops(cfg) * n["k"] * n["Eh"] // n["E"]
    return n["L"] * (2 * (attn + p["router"]) + experts)


def decode_token_flops(cfg: dict, context: int) -> int:
    """Model FLOPs of one decode token: weights, attention, lm_head."""
    return (_linear_flops_per_token(cfg) + decode_attn_flops(cfg, context)
            + lm_head_flops(cfg))


def prefill_flops(cfg: dict, prompt: int) -> int:
    """Model FLOPs of admitting one prompt: every token's weight matmuls,
    its own key rebuild (ReCalKV), causal attention, and the lm_head for
    the last position only."""
    n = dims(cfg)
    rebuild = 0
    if n["latent"]:
        rebuild = 2 * n["L"] * n["G"] * n["rk"] * n["s"] * n["dh"] * prompt
    return (_linear_flops_per_token(cfg) * prompt + rebuild
            + prefill_attn_flops(cfg, prompt) + lm_head_flops(cfg))
