"""Pallas TPU kernel: causal / sliding-window flash-attention prefill.

Standard online-softmax tiling: grid (B, H, nQ, nK) with the key axis
minor-most; (m, l, acc) scratch carries the running softmax across key
tiles of one query tile.  GQA folds into the key/value index map
(kv head = h // q_per_kv).  Sliding windows just tighten the in-block
position mask; fully-masked key tiles are skipped with @pl.when (no MXU
work issued) — the TPU analogue of the paper's bounded-reconstruction
concern for keys.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale, causal, window, block_q, block_k, n_k, seq_len):
    i_q = pl.program_id(2)
    i_k = pl.program_id(3)

    @pl.when(i_k == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = i_q * block_q
    k_start = i_k * block_k
    # static-ish skip bounds (depend only on grid indices)
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if window is not None:
        needed = jnp.logical_and(
            needed, k_start + block_k - 1 > q_start - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (Bq, dh)
        k = k_ref[0].astype(jnp.float32)                # (Bk, dh)
        v = v_ref[0].astype(jnp.float32)                # (Bk, dv)
        s = (q @ k.T) * scale                           # (Bq, Bk)
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kp < seq_len
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[:, 0] = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
        m_ref[:, 0] = m_new

    @pl.when(i_k == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"),
)
def flash_prefill_attention(q, k, v, *, causal: bool = True,
                            window: int | None = None, scale: float | None = None,
                            block_q: int = 256, block_k: int = 256,
                            interpret: bool = False):
    """q: (B, T, H, dh); k: (B, T, Hkv, dh); v: (B, T, Hv, dv).

    Returns (B, T, H, dv).  ``Hv`` may differ from ``Hkv`` (latent values:
    one value group per ``Hkv // Hv`` kv heads — the query-head order is
    kv-major, so group = h // (H // Hv)).  Arbitrary T: the tail tile is
    zero-padded internally and masked via ``seq_len``.
    """
    B, T, H, dh = q.shape
    Hkv, dv = k.shape[2], v.shape[3]
    Hv = v.shape[2]
    qpk = H // Hkv
    qpv = H // Hv
    scale = scale if scale is not None else dh ** -0.5
    # Floor tile sizes to powers of two so they nest: the padded length is
    # then a single max-tile multiple instead of an lcm that can balloon
    # (e.g. blocks 100/64 -> lcm 1600 for a 100-token sequence).
    bq = 1 << (min(block_q, T).bit_length() - 1)
    bk = 1 << (min(block_k, T).bit_length() - 1)
    tile = max(bq, bk)
    Tp = -(-T // tile) * tile              # multiple of both tile sizes
    if Tp != T:
        pad = ((0, 0), (0, Tp - T), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    n_q, n_k = Tp // bq, Tp // bk

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, n_k=n_k, seq_len=T)
    # Heads fold into the lane axis — (B, T, H*dh), a free reshape — so a
    # (1, Bq, dh) block at index (b, iq, h) tiles rows on sublanes and one
    # head on lanes; a (1, Bq, 1, dh) block of the 4-D array is refused by
    # Mosaic.  On TPU this needs dh, dv % 128 == 0 (or a single head).
    lanes = lambda x: x.reshape(B, Tp, -1)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, h, iq, ik: (b, iq, h)),
            pl.BlockSpec((1, bk, dh),
                         lambda b, h, iq, ik, qpk=qpk: (b, ik, h // qpk)),
            pl.BlockSpec((1, bk, dv),
                         lambda b, h, iq, ik, qpv=qpv: (b, ik, h // qpv)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, h, iq, ik: (b, iq, h)),
        out_shape=jax.ShapeDtypeStruct((B, Tp, H * dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=interpret,
    )(lanes(q), lanes(k), lanes(v)).reshape(B, Tp, H, dv)
    return out[:, :T] if Tp != T else out
