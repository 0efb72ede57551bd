"""Plain reference for the Qwen3 decoder (HF ``Qwen3ForCausalLM``: RMSNorm,
GQA with per-head q/k RMSNorm before NeoX-style RoPE, SwiGLU, tied
embeddings), with the ReCalKV latent K/V path of arXiv:2505.24357 as an
option: per group of ``s`` KV heads, z_k = x L_k and z_v = x L_v are the
cached latents; keys are rebuilt as z_k R_k (then qk-norm and RoPE, which
force the rebuild), and values stay latent, read through the fused
output projection W~_o (one (r_v, d) block per query head).

It imports nothing of the program under test.  It runs one sequence at a
time over the whole prompt plus served tokens (no cache, no batching, no
kernels), layer by layer, in float32 at ``highest`` matmul precision.

``precision="fp8"`` is the control: the same computation with every
matmul's weight (per output channel) and activation (per row) rounded to
float8 e4m3, and the cached keys and values (latents for ReCalKV) rounded
per position and head likewise, as an fp8 weight, activation and KV-cache
path would."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.qwen3.weights import dims

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_CHUNK = 512


def _fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, precision):
    """x (..., k) @ w (k, n) in float32; fp8 rounds rows of x and columns
    of w first."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, gain_minus_one, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + gain_minus_one.astype(jnp.float32))


def _rope(x, pos, theta):
    """NeoX rotation (rotate_half).  x (T, heads, dh), pos (T,)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1]))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _attend(q, k, v, head_to_kv):
    """Causal softmax attention, query rows in chunks.  q (T, H, dh),
    k (T, Hk, dh), v (T, Hv, dv); query head h reads key head
    head_to_kv[0][h] and value head head_to_kv[1][h]."""
    T, H, dh = q.shape
    kh = k[:, head_to_kv[0], :]                       # (T, H, dh)
    vh = v[:, head_to_kv[1], :]                       # (T, H, dv)
    outs = []
    for c0 in range(0, T, Q_CHUNK):
        qc = q[c0:c0 + Q_CHUNK]
        s = jnp.einsum("qhd,khd->hqk", qc, kh, precision=HIGHEST) * dh ** -0.5
        qi = jnp.arange(c0, c0 + qc.shape[0])[:, None]
        s = jnp.where(jnp.arange(T)[None, :] <= qi, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, vh, precision=HIGHEST))
    return jnp.concatenate(outs, axis=0)


@functools.lru_cache(maxsize=None)
def _layer_fn(n_items: tuple, eps: float, theta: float, precision: str):
    n = dict(n_items)
    H, Hkv, dh = n["H"], n["Hkv"], n["dh"]
    per_kv = H // Hkv
    kv_of = np.arange(H) // per_kv

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
        h = _rms(x, w["ln2"], eps)
        g = _mm(h, w["w_gate"], precision)
        u = _mm(h, w["w_up"], precision)
        return x + _mm(jax.nn.silu(g) * u, w["w_down"], precision)

    return jax.jit(layer)


@functools.lru_cache(maxsize=None)
def _head_fn(eps: float, precision: str):
    def head(x, final_norm, embed, rows):
        h = _rms(x[rows], final_norm, eps)
        return _mm(h, embed.T, precision)
    return jax.jit(head)


def padded_len(t: int) -> int:
    """Sequences are padded to a multiple of Q_CHUNK so that few shapes
    compile; causal masking keeps the real rows blind to the padding."""
    return -(-t // Q_CHUNK) * Q_CHUNK


def logits(cfg: dict, w: dict, tokens, rows, precision: str = "reference"):
    """Float32 logits (len(rows), V) of the next token after each of
    ``rows`` (positions into ``tokens``), from a full causal forward."""
    if precision not in ("reference", "fp8"):
        raise ValueError(f"precision {precision!r}")
    n = dims(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    toks = np.zeros(padded_len(len(tokens)), np.int32)
    toks[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
        layer = _layer_fn(tuple(sorted(n.items())), eps, theta, precision)
        for i in range(n["L"]):
            x = layer(x, w["layers"], jnp.int32(i))
        head = _head_fn(eps, precision)
        return head(x, w["final_norm"], w["embed"],
                    jnp.asarray(np.asarray(rows, np.int32)))
