"""Operations and bytes that the Qwen3 algorithm needs, from the
configuration's shapes alone.  Model FLOPs count each multiply-add as 2.
Attention is counted over the live context only (the positions a token
really attends to), never over the ring's allocated length, so a kernel
that skips dead tiles cannot read above its roofline.

ReCalKV keys must be rebuilt from the latents before RoPE (arXiv:2505.24357,
Sec. 3), so a latent decode step pays the rebuild z_k R_k once per cached
position, group and layer: that is model work.  The dense cache riding the
latent kernel with an identity R_k is not: its identity products are left
out."""

from __future__ import annotations

from bench.models.qwen3.weights import dims

BF16 = 2


def cache_bytes_per_position(cfg: dict) -> int:
    """Cache bytes one slot holds per position, over all layers."""
    n = dims(cfg)
    if n["latent"]:
        per_layer = n["G"] * (n["rk"] + n["rv"]) * BF16
    else:
        per_layer = 2 * n["Hkv"] * n["dh"] * BF16
    return n["L"] * per_layer


def weight_params(cfg: dict) -> dict:
    """Parameters by part: per layer (attention, mlp), embedding."""
    n = dims(cfg)
    d, H, Hkv, dh, f = n["d"], n["H"], n["Hkv"], n["dh"], n["f"]
    if n["latent"]:
        G, s, rk, rv = n["G"], n["s"], n["rk"], n["rv"]
        attn = d * H * dh + G * d * (rk + rv) + G * rk * s * dh + H * rv * d
    else:
        attn = d * H * dh + 2 * d * Hkv * dh + H * dh * d
    norms = 2 * d + 2 * dh
    return {"attn": attn, "mlp": 3 * d * f, "norms": norms,
            "embed": n["V"] * d, "final_norm": d}


def weight_bytes(cfg: dict) -> int:
    """Bytes of the served weights: bf16 matrices, float32 norm gains."""
    n, p = dims(cfg), weight_params(cfg)
    return (n["L"] * ((p["attn"] + p["mlp"]) * BF16 + p["norms"] * 4)
            + p["embed"] * BF16 + p["final_norm"] * 4)


def _linear_flops_per_token(cfg: dict) -> int:
    """Weight matmuls of one token through every layer (no lm_head).  The
    latent rebuild of the token's own key belongs to attention."""
    n, p = dims(cfg), weight_params(cfg)
    attn = p["attn"]
    if n["latent"]:
        attn -= n["G"] * n["rk"] * n["s"] * n["dh"]
    return 2 * n["L"] * (attn + p["mlp"])


def lm_head_flops(cfg: dict) -> int:
    n = dims(cfg)
    return 2 * n["d"] * n["V"]


def attn_flops(cfg: dict, context: int, rebuilt: int) -> int:
    """One query token attending to ``context`` positions in every layer,
    of which ``rebuilt`` positions have their latent key rebuilt here."""
    n = dims(cfg)
    H, dh = n["H"], n["dh"]
    dv = n["rv"] if n["latent"] else dh
    per_layer = 2 * H * dh * context + 2 * H * dv * context
    if n["latent"]:
        per_layer += 2 * n["G"] * n["rk"] * n["s"] * dh * rebuilt
    return n["L"] * per_layer


def decode_attn_flops(cfg: dict, context: int) -> int:
    """Attention of one decode token over ``context`` positions (itself
    included): every cached key is rebuilt in a latent decode step."""
    return attn_flops(cfg, context, context)


def decode_attn_bytes(cfg: dict, context: int) -> int:
    """Cache bytes a decode token must read: ``context`` positions."""
    return cache_bytes_per_position(cfg) * context


def decode_step_fixed_bytes(cfg: dict) -> int:
    """Bytes a decode attention call reads once per step and layer,
    whatever the batch: R_k for the latent kernel."""
    n = dims(cfg)
    if not n["latent"]:
        return 0
    return n["L"] * n["G"] * n["rk"] * n["s"] * n["dh"] * BF16


def decode_token_flops(cfg: dict, context: int) -> int:
    """Model FLOPs of one decode token: weights, attention, lm_head."""
    return (_linear_flops_per_token(cfg) + decode_attn_flops(cfg, context)
            + lm_head_flops(cfg))


def prefill_attn_flops(cfg: dict, prompt: int) -> int:
    """Causal attention of a whole prompt (kernel work: scores and
    values over the t + 1 positions each query t sees)."""
    causal = prompt * (prompt + 1) // 2
    n = dims(cfg)
    dv = n["rv"] if n["latent"] else n["dh"]
    return n["L"] * (2 * n["H"] * n["dh"] + 2 * n["H"] * dv) * causal


def prefill_attn_bytes(cfg: dict, prompt: int) -> int:
    """Bytes the prefill attention kernel must move for one prompt: read
    queries, keys (rebuilt, for ReCalKV) and values (latent, for
    ReCalKV), write outputs."""
    n = dims(cfg)
    H, Hkv, dh = n["H"], n["Hkv"], n["dh"]
    if n["latent"]:
        v, out = n["G"] * n["rv"], H * n["rv"]
    else:
        v, out = Hkv * dh, H * dh
    return n["L"] * prompt * (H * dh + Hkv * dh + v + out) * BF16


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
