"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

Shapes (decode):
  q      (B, G, Hg, dh)   post-RoPE queries, grouped: Hg = s * q_per_kv
  zk     (B, S, G, r_k)   pre-RoPE key latents
  zv     (B, S, G, r_v)   value latents
  r_k    (G, r_k, s*dh)   key reconstruction factors
  cos/sin (B, S, dh/2)    rotation tables for the *stored* positions
  bias   (B, S)           additive mask (0 valid / -inf invalid)
  out    (B, G, Hg, r_v)  per-head latent attention outputs

Grouped matmul (held experts):
  lhs    (m, K)           rows sorted by group
  rhs    (G, K, N)        one weight per group
  group_sizes (G,)        rows per group, summing to at most m
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rotate(k: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """k: (..., S, s, dh); cos/sin: (..., S, dh/2) broadcast over s."""
    half = k.shape[-1] // 2
    k1, k2 = k[..., :half], k[..., half:]
    c, s_ = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([k1 * c - k2 * s_, k2 * c + k1 * s_], axis=-1)


def latent_decode_attention(q, zk, zv, r_k, cos, sin, bias, scale):
    """Reference ReCalKV decode: reconstruct K, RoPE, softmax, latent AV."""
    B, G, Hg, dh = q.shape
    S = zk.shape[1]
    s = r_k.shape[-1] // dh
    qpk = Hg // s
    qf = q.astype(jnp.float32)
    k = jnp.einsum("bsgr,grn->bsgn", zk.astype(jnp.float32),
                   r_k.astype(jnp.float32))
    k = k.reshape(B, S, G, s, dh)
    k = rotate(k.swapaxes(1, 2), cos[:, None], sin[:, None])    # (B,G,S,s,dh)
    qg = qf.reshape(B, G, s, qpk, dh)
    logits = jnp.einsum("bgsjd,bgtsd->bgsjt", qg, k) * scale
    logits = logits + bias[:, None, None, None, :]
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bgsjt,btgr->bgsjr", w, zv.astype(jnp.float32))
    return o.reshape(B, G, Hg, zv.shape[-1])


def latent_decode_attention_quant(q, zk_q, zk_scale, zv_q, zv_scale, r_k,
                                  cos, sin, bias, scale):
    """Int8-latent variant: dequantize then defer to the fp oracle."""
    zk = zk_q.astype(jnp.float32) * zk_scale[..., None]
    zv = zv_q.astype(jnp.float32) * zv_scale[..., None]
    return latent_decode_attention(q, zk, zv, r_k, cos, sin, bias, scale)


def flash_prefill_attention(q, k, v, *, causal=True, window=None, scale=None):
    """Reference causal/windowed prefill attention.

    q: (B, T, H, dh); k/v: (B, T, Hkv, dh).  Returns (B, T, H, dv).
    """
    B, T, H, dh = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    scale = scale if scale is not None else dh ** -0.5
    qr = q.astype(jnp.float32).reshape(B, T, Hkv, g, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qr, k.astype(jnp.float32)) * scale
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    m = jnp.ones((T, T), bool)
    if causal:
        m &= j <= i
    if window is not None:
        m &= j > i - window
    logits = jnp.where(m[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v.astype(jnp.float32))
    return o.reshape(B, T, H, v.shape[-1])


def expert_gmm(lhs, rhs, group_sizes, *, layer=None):
    """Reference grouped matmul: row r of group g is lhs[r] @ rhs[g] in
    float32 (rhs[layer, g] when rhs stacks layers); rows past the last
    group are zero."""
    if layer is not None:
        rhs = rhs[layer]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(lhs.shape[0])[:, None]
    x = lhs.astype(jnp.float32)
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(rhs.shape[0]):
        mine = (row >= starts[g]) & (row < ends[g])
        out = out + jnp.where(mine, x @ rhs[g].astype(jnp.float32), 0.0)
    return out
