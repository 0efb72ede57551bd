"""Model-level ReCalKV compression: dense checkpoint -> latent-KV model.

Operates on *unrolled* models (cfg.scan_layers=False), which is where the
Fisher-guided per-layer rank allocation lives (scanned production configs
use uniform ranks so the period params stack).

Flow (paper Algorithm 1, at model scope):
    stats  = capture_calibration(cfg, params, batches)     # X^T X per layer
    fk, fv = fisher_scores(cfg, params, batches)           # dL/dW_k|v squared
    cfg2, params2 = compress_model(cfg, params, stats, recal_cfg, fk, fv)
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core import pipeline as P
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.config import ModelConfig, ReCalKVRuntime

SELF_ATTN = ("attn", "attn_dense", "local", "attn_cross")


def _unrolled(cfg: ModelConfig) -> tuple[str, ...]:
    if cfg.scan_layers:
        raise ValueError("compression requires cfg.scan_layers=False")
    return cfg.expanded_layers()


def attn_layer_indices(cfg: ModelConfig) -> list[int]:
    """Indices (into the unrolled stack) of self-attention layers."""
    return [i for i, k in enumerate(_unrolled(cfg)) if k in SELF_ATTN]


def capture_calibration(cfg: ModelConfig, params, batches) -> list[P.CalibStats]:
    """Per-self-attention-layer input (post-ln1) second moments."""
    kinds = _unrolled(cfg)

    # params are an argument, not a closure: jit embeds closed-over arrays
    # in the program as constants, which at full width is the whole model.
    def hidden_taps(params, tokens, source=None):
        B, Tn = tokens.shape
        if cfg.encoder_decoder and source is not None:
            source = T.encode(cfg, params, source)
        ctx = {"positions": jnp.broadcast_to(jnp.arange(Tn), (B, Tn)),
               "lengths": jnp.full((B,), Tn, jnp.int32),
               "source": source, "max_len": Tn}
        x = T.embed_tokens(cfg, params, tokens)
        taps = []
        for i, kind in enumerate(kinds):
            p = params["prefix"][i]
            if kind in SELF_ATTN:
                taps.append(L.rmsnorm(x, p["ln1"], cfg.norm_eps))
            x, _, _ = T.block_full(cfg, kind, p, x, ctx, want_cache=False)
        return taps

    tap_fn = jax.jit(hidden_taps)
    stats: list[P.CalibStats] | None = None
    for batch in batches:
        taps = tap_fn(params, batch["tokens"], batch.get("source"))
        new = [P.collect_stats(t) for t in taps]
        stats = new if stats is None else [
            P.merge_stats(a, b) for a, b in zip(stats, new)
        ]
    return stats


def fisher_scores(cfg: ModelConfig, params, batches) -> tuple[list[float], list[float]]:
    """Summed squared gradients of the LM loss wrt each W_k / W_v."""
    idxs = attn_layer_indices(cfg)

    def loss(p, batch):
        val, _ = T.loss_fn(cfg, p, batch)
        return val

    grad_fn = jax.jit(jax.grad(loss))
    fk = [0.0] * len(idxs)
    fv = [0.0] * len(idxs)
    for batch in batches:
        g = grad_fn(params, batch)
        for j, i in enumerate(idxs):
            ga = g["prefix"][i]["attn"]
            fk[j] += float(jnp.sum(ga["wk"].astype(jnp.float32) ** 2))
            fv[j] += float(jnp.sum(ga["wv"].astype(jnp.float32) ** 2))
    return fk, fv


def _to_latent_params(attn_p: dict, ca: P.CompressedAttention, dtype) -> dict:
    out = {
        "wq": ca.W_q.astype(dtype),
        "l_k": ca.L_k.astype(dtype),
        "r_k": ca.R_k.astype(dtype),
        "l_v": ca.L_v.astype(dtype),
        "wo_fused": ca.W_o_fused.astype(dtype),
    }
    for extra in ("q_norm", "k_norm"):
        if extra in attn_p:
            out[extra] = attn_p[extra]
    return out


def compress_model(
    cfg: ModelConfig,
    params,
    stats: Sequence[P.CalibStats],
    recal_cfg: P.ReCalKVConfig,
    fisher_k: Sequence[float] | None = None,
    fisher_v: Sequence[float] | None = None,
):
    """Returns (compressed_cfg, compressed_params).

    Self-attention layers get HSR keys + OCMF values; cross-attention
    layers (if any) are compressed with identity stats (their K/V source
    is the frontend stub).  MLA / attention-free layers pass through
    untouched (DESIGN.md §Arch-applicability).
    """
    kinds = _unrolled(cfg)
    if cfg.mla is not None:
        raise ValueError("MLA models already cache latents; nothing to do")
    idxs = attn_layer_indices(cfg)
    if len(stats) != len(idxs):
        raise ValueError(f"need {len(idxs)} stats, got {len(stats)}")

    weights = []
    for i in idxs:
        a = params["prefix"][i]["attn"]
        weights.append(P.AttnWeights(
            W_q=a["wq"], W_k=a["wk"], W_v=a["wv"], W_o=a["wo"],
            num_q_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        ))
    compressed = P.compress_model_layers(
        weights, list(stats), recal_cfg, fisher_k, fisher_v
    )

    new_prefix = list(params["prefix"])
    for j, i in enumerate(idxs):
        blk = dict(new_prefix[i])
        blk["attn"] = _to_latent_params(blk["attn"], compressed[j], cfg.dtype)
        new_prefix[i] = blk

    # Cross-attention layers: same machinery, identity stats (stub source).
    d = cfg.d_model
    for i, kind in enumerate(kinds):
        if kind not in ("cross", "attn_cross"):
            continue
        blk = dict(new_prefix[i])
        a = blk["cross"]
        w = P.AttnWeights(
            W_q=a["wq"], W_k=a["wk"], W_v=a["wv"], W_o=a["wo"],
            num_q_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        )
        s = recal_cfg.effective_group_size(cfg.num_kv_heads)
        width = s * cfg.d_head
        rk = compressed[0].rank_k if compressed else recal_cfg.rank_for_width(width)
        ca = P.compress_attention_layer(
            w, P.CalibStats.identity(d), recal_cfg, rk, rk)
        blk["cross"] = _to_latent_params(a, ca, cfg.dtype)
        new_prefix[i] = blk

    new_params = dict(params)
    new_params["prefix"] = tuple(new_prefix)

    r_k = compressed[0].rank_k if compressed else 0
    r_v = compressed[0].rank_v if compressed else 0
    by_layer = [(0, 0)] * cfg.num_layers
    for j, i in enumerate(idxs):
        by_layer[i] = (compressed[j].rank_k, compressed[j].rank_v)
    new_cfg = dataclasses.replace(
        cfg,
        recalkv=ReCalKVRuntime(
            rank_k=r_k, rank_v=r_v,
            group_size=recal_cfg.effective_group_size(cfg.num_kv_heads),
            ranks_by_layer=tuple(by_layer),
        ),
    )
    return new_cfg, new_params
