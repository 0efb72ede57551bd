"""Plain reference for one chip's share of a Qwen3-MoE decoder (HF
``Qwen3MoeForCausalLM``): Qwen3's attention (per-head q/k RMSNorm before
NeoX RoPE, GQA, or the ReCalKV latent K/V path of arXiv:2505.24357), then
in every layer the sparse MoE block of ``Qwen3MoeSparseMoeBlock``:

    h = rms(x);  p = softmax(h W_router) over all E experts (float32)
    top-k of p, renormalised to sum to 1 when norm_topk_prob holds
    x + sum over the chosen experts held here of  w_e * W_down,e(silu(W_gate,e h) * W_up,e h)

The experts not held on this chip are left out, as the program leaves them
out: the result is this chip's share of each layer.  Untied embeddings.

It imports nothing of the program under test.  One sequence at a time over
the whole prompt plus served tokens (no cache, no batching, no kernels),
layer by layer, in float32 at ``highest`` matmul precision.  Each held
expert runs over the rows routed to it, gathered by indices read back to
the host each layer and padded to a power of two so that few shapes
compile.

``precision="fp8"`` is the control: Qwen3's fp8 rounding (weights per
output channel, activations per row, cached keys and values or latents
per position) extended to the expert matmuls.  The router stays float32 in
both, as the deployment's router does."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.qwen3.reference import (_attend, _fp8, _mm, _rms, _rope,
                                          padded_len)
from bench.models.qwen3_moe.weights import dims


@functools.lru_cache(maxsize=None)
def _attn_fn(n_items: tuple, eps: float, theta: float, precision: str):
    """x + attn(rms(x)) of one layer, and rms(x) for its MoE block."""
    n = dict(n_items)
    H, Hkv, dh = n["H"], n["Hkv"], n["dh"]
    kv_of = np.arange(H) // (H // Hkv)

    def cache_round(z):
        return _fp8(z, -1) if precision == "fp8" else z

    def layer(x, layers, i):
        w = jax.tree.map(lambda a: a[i], layers)
        T = x.shape[0]
        pos = jnp.arange(T)
        h = _rms(x, w["ln1"], eps)
        q = _mm(h, w["wq"], precision).reshape(T, H, dh)
        q = _rope(_rms(q, w["q_norm"], eps), pos, theta)
        if n["latent"]:
            G, s = n["G"], n["s"]
            zk = jnp.stack([_mm(h, w["l_k"][g], precision) for g in range(G)], 1)
            zv = jnp.stack([_mm(h, w["l_v"][g], precision) for g in range(G)], 1)
            zk, zv = cache_round(zk), cache_round(zv)
            k = jnp.stack([_mm(zk[:, g], w["r_k"][g], precision)
                           for g in range(G)], 1).reshape(T, Hkv, dh)
            k = _rope(_rms(k, w["k_norm"], eps), pos, theta)
            o = _attend(q, k, zv, (kv_of, kv_of // s))          # (T, H, rv)
            y = sum(_mm(o[:, hh], w["wo_fused"][hh], precision)
                    for hh in range(H))
        else:
            k = _mm(h, w["wk"], precision).reshape(T, Hkv, dh)
            v = _mm(h, w["wv"], precision).reshape(T, Hkv, dh)
            k = _rope(_rms(k, w["k_norm"], eps), pos, theta)
            k, v = cache_round(k), cache_round(v)
            o = _attend(q, k, v, (kv_of, kv_of))
            y = _mm(o.reshape(T, H * dh), w["wo"], precision)
        x = x + y
        return x, _rms(x, w["ln2"], eps)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _router_fn(k: int, norm_topk: bool):
    def route(h, router):
        p = jax.nn.softmax(jnp.matmul(h, router.astype(jnp.float32),
                                      precision=jax.lax.Precision.HIGHEST))
        w, sel = jax.lax.top_k(p, k)
        if norm_topk:
            w = w / w.sum(axis=-1, keepdims=True)
        return w, sel
    return jax.jit(route)


@functools.lru_cache(maxsize=None)
def _expert_fn(precision: str):
    def expert(rows, wg, wu, wd, scale):
        g = _mm(rows, wg, precision)
        u = _mm(rows, wu, precision)
        return _mm(jax.nn.silu(g) * u, wd, precision) * scale[:, None]
    return jax.jit(expert)


def _moe(h, w, i: int, n: dict, n_real: int, precision: str):
    """This chip's share of the MoE block of layer ``i`` for the first
    ``n_real`` rows of h (T, d); rows past them get nothing."""
    wts, sel = _router_fn(n["k"], n["norm_topk"])(h[:n_real],
                                                  w["router"][i])
    wts, sel = np.asarray(wts), np.asarray(sel)
    out = jnp.zeros_like(h)
    expert = _expert_fn(precision)
    for e in range(n["Eh"]):
        tok, slot = np.nonzero(sel == n["e0"] + e)
        if tok.size == 0:
            continue
        R = 1 << (int(tok.size) - 1).bit_length()
        idx = np.zeros(R, np.int32)
        idx[:tok.size] = tok
        scale = np.zeros(R, np.float32)
        scale[:tok.size] = wts[tok, slot]
        y = expert(h[idx], w["w_gate"][i, e], w["w_up"][i, e],
                   w["w_down"][i, e], jnp.asarray(scale))
        out = out.at[idx].add(y)
    return out


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, precision: str):
    def head(x, final_norm, lm_head, rows):
        return _mm(_rms(x[rows], final_norm, eps), lm_head, precision)
    return jax.jit(head)


def logits(cfg: dict, w: dict, tokens, rows, precision: str = "reference"):
    """Float32 logits (len(rows), V) of the next token after each of
    ``rows`` (positions into ``tokens``), from a full causal forward."""
    if precision not in ("reference", "fp8"):
        raise ValueError(f"precision {precision!r}")
    n = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    toks = np.zeros(padded_len(len(tokens)), np.int32)
    toks[:len(tokens)] = tokens
    attn_items = tuple(sorted((k, v) for k, v in n.items()
                              if k in ("H", "Hkv", "dh", "latent", "G", "s")))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
        attn = _attn_fn(attn_items, eps, theta, precision)
        for i in range(n["L"]):
            x, h = attn(x, w["layers"], jnp.int32(i))
            x = x + _moe(h, w["layers"], i, n, len(tokens), precision)
        return _head_fn(eps, precision)(
            x, w["final_norm"], w["lm_head"],
            jnp.asarray(np.asarray(rows, np.int32)))

