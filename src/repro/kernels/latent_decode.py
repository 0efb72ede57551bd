"""Pallas TPU kernel: ReCalKV latent-cache flash decode.

The paper's GPU flow reconstructs K into global memory, then runs attention.
The TPU-native version never materializes K in HBM: each grid step streams a
(Sb, r_k) latent tile into VMEM, reconstructs the key tile with an MXU
matmul against the resident R_k factor, applies RoPE from precomputed
cos/sin (stored-position) tables, runs online-softmax flash decoding, and
accumulates A @ z_v directly in value-latent space.  The fused W~_o
projection happens outside (one dense matmul on (B, Hq, r_v)).

Memory traffic per step ~= S * G * (r_k + r_v) bytes — exactly the
compressed cache size; the reconstruction FLOPs ride under the bandwidth
roofline (DESIGN.md §2).

Grid: (B, G, nS) — nS minor-most, so the VMEM scratch (m, l, acc) carries
the online softmax across key tiles of one (batch, group).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def maybe_knorm(k, kn_ref, apply_knorm, norm_eps):
    """Per-head RMSNorm on reconstructed keys (qk-norm models store
    pre-norm latents: normalize between reconstruction and RoPE, same as
    the einsum reference path).  k: (Sb, s, dh); kn_ref: (1, dh)."""
    if not apply_knorm:
        return k
    kn = kn_ref[...].astype(jnp.float32)
    ms = jnp.mean(k * k, axis=-1, keepdims=True)
    return k * jax.lax.rsqrt(ms + norm_eps) * (1.0 + kn[None])


def knorm_operand(k_norm, dh):
    """(apply_knorm, kn array) pair for a pallas_call: the flag is trace-
    static, the array is a real operand either way (dummy when absent)."""
    if k_norm is None:
        return False, jnp.zeros((1, dh), jnp.float32)
    return True, k_norm.reshape(1, dh)


def attend_block(q, k, zv, cos, sin, bias, *, scale, s, qpk, dh,
                 m_ref, l_ref, acc_ref):
    """Shared online-softmax update over one reconstructed key tile.

    RoPE the (Sb, s, dh) keys by the stored-position tables, score the
    (s, qpk) query groups, rescale the running (m, l, acc) scratch.  Both
    decode kernels (bf16 and int8 latents) defer here after reconstructing
    (and dequantizing) their tile."""
    half = dh // 2
    c, si_ = cos[:, None, :], sin[:, None, :]          # (Sb, 1, dh/2)
    k1, k2 = k[..., :half], k[..., half:]
    kr = jnp.concatenate([k1 * c - k2 * si_, k2 * c + k1 * si_], axis=-1)

    qg = q.reshape(s, qpk, dh)
    # one MXU matmul per group-slot (s <= 4, unrolled statically)
    scores = jnp.concatenate(
        [qg[i] @ kr[:, i, :].T for i in range(s)], axis=0
    ) * scale                                          # (Hg, Sb)
    scores = scores + bias                             # bias: (1, Sb)

    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[:, None])               # (Hg, Sb)
    l_ref[:, 0] = l_prev * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ zv
    m_ref[:, 0] = m_new


def split_out_refs(rest, return_lse):
    """(mo_ref, lo_ref, m_ref, l_ref, acc_ref) from a kernel's trailing
    refs: with ``return_lse`` the pallas_call has two extra outputs (the
    running max and denominator) ahead of the VMEM scratch."""
    if return_lse:
        return rest
    m_ref, l_ref, acc_ref = rest
    return None, None, m_ref, l_ref, acc_ref


def finish_tile(o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref):
    """Write the finished output (and, for a partial-softmax caller, the
    raw m/l state the cross-shard LSE merge needs).  A fully-masked row
    finishes as exactly 0 (l == 0, acc == 0): under the merge it then
    contributes weight l * exp(m - m_g) == 0."""
    l = jnp.maximum(l_ref[:, :1], 1e-30)
    o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
    if mo_ref is not None:
        mo_ref[0, 0] = m_ref[...]
        lo_ref[0, 0] = l_ref[...]


def _kernel(q_ref, zk_ref, zv_ref, rk_ref, kn_ref, cos_ref, sin_ref, bias_ref,
            o_ref, *rest, scale, s, qpk, dh, n_s,
            apply_knorm, norm_eps, return_lse=False):
    i_s = pl.program_id(2)
    mo_ref, lo_ref, m_ref, l_ref, acc_ref = split_out_refs(rest, return_lse)

    @pl.when(i_s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bias = bias_ref[0, 0].astype(jnp.float32)          # (1, Sb)

    # Skip fully-masked key tiles (empty ring regions, internal tail
    # padding): no MXU work, no softmax-state update.
    @pl.when(jnp.max(bias) > NEG_INF * 0.5)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (Hg, dh), Hg = s*qpk
        zk = zk_ref[0].astype(jnp.float32)             # (Sb, r_k)
        rk = rk_ref[0].astype(jnp.float32)             # (r_k, s*dh)
        k = zk @ rk                                    # (Sb, s*dh)  reconstruct
        sb = k.shape[0]
        k = maybe_knorm(k.reshape(sb, s, dh), kn_ref, apply_knorm, norm_eps)
        attend_block(q, k, zv_ref[0].astype(jnp.float32),
                     cos_ref[0].astype(jnp.float32),
                     sin_ref[0].astype(jnp.float32), bias,
                     scale=scale, s=s, qpk=qpk, dh=dh,
                     m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref)

    @pl.when(i_s == n_s - 1)
    def _finish():
        finish_tile(o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref)


def pad_ring(bias: jax.Array, block_s: int, *arrays: jax.Array):
    """Pad the ring (axis 1) up to a tile multiple.  Padded columns get
    bias = -inf (never attended); data arrays are zero-padded.  Returns
    (padded_len, bias, *arrays)."""
    S = bias.shape[1]
    bs = min(block_s, S)
    Sp = -(-S // bs) * bs
    if Sp == S:
        return S, bias, *arrays
    bias = jnp.pad(bias, ((0, 0), (0, Sp - S)), constant_values=NEG_INF)
    arrays = tuple(
        jnp.pad(a, ((0, 0), (0, Sp - S)) + ((0, 0),) * (a.ndim - 2))
        for a in arrays)
    return Sp, bias, *arrays


def lane_view(x: jax.Array) -> jax.Array:
    """(B, S, G, r) -> (B, S, G*r), a free reshape.  Blocks of (1, Sb, r)
    at index (b, i, g) then put the rank on the 128-lane axis and the key
    rows on sublanes, which Mosaic tiles; a block holding one element of
    the group axis just ahead of the lanes is refused.  On TPU this needs
    r % 128 == 0 (or a single group)."""
    return x.reshape(x.shape[:2] + (-1,))


def tile_bias(bias: jax.Array, bs: int) -> jax.Array:
    """Additive bias (B, [nq,] n_tiles*bs) -> (B, n_tiles, rows, bs) with
    rows = nq (1 for single-query).  A (1, 1, rows, bs) block at index
    (b, i, 0, 0) then spans the array's last two dims whole, which Mosaic
    accepts for any tile width; a (1, bs) block of a (B, S) bias is
    refused, and a lane block of a (B, 1, S) bias needs bs % 128 == 0."""
    if bias.ndim == 2:
        bias = bias[:, None]
    B, rows, cols = bias.shape
    return bias.reshape(B, rows, cols // bs, bs).transpose(0, 2, 1, 3)


def lse_outputs(B, G, rows, rv, dtype, return_lse, prefetch=False):
    """(out_shape, out_specs) for a decode kernel: the finished (B, G,
    rows, r_v) output plus — when ``return_lse`` — the raw (m, l) softmax
    state as two (B, G, rows, 1) f32 outputs for a cross-shard merge."""
    if prefetch:
        def omap(b, g, i, pt):
            return (b, g, 0, 0)
    else:
        def omap(b, g, i):
            return (b, g, 0, 0)
    shapes = [jax.ShapeDtypeStruct((B, G, rows, rv), dtype)]
    specs = [pl.BlockSpec((1, 1, rows, rv), omap)]
    if not return_lse:
        return shapes[0], specs[0]
    shapes += [jax.ShapeDtypeStruct((B, G, rows, 1), jnp.float32)] * 2
    specs += [pl.BlockSpec((1, 1, rows, 1), omap)] * 2
    return shapes, specs


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_s", "interpret", "norm_eps",
                     "return_lse"),
)
def latent_decode_attention(q, zk, zv, r_k, cos, sin, bias, *,
                            scale: float, block_s: int = 256,
                            interpret: bool = False,
                            k_norm: jax.Array | None = None,
                            norm_eps: float = 1e-6,
                            return_lse: bool = False):
    """q: (B, G, Hg, dh); zk: (B, S, G, r_k); zv: (B, S, G, r_v);
    r_k: (G, r_k, s*dh); cos/sin: (B, S, dh/2); bias: (B, S).
    Returns (B, G, Hg, r_v) latent outputs (feed to the fused W~_o).

    ``k_norm`` (dh,), when given, applies per-head RMSNorm to the
    reconstructed keys before RoPE (qk-norm models).  S need not divide
    ``block_s``: the tail tile is padded and masked internally.
    ``return_lse`` additionally returns the raw (m, l) online-softmax
    state — (B, G, Hg, 1) f32 each — so a shard_map caller holding only a
    sequence shard of the ring can LSE-merge partial outputs across
    shards (the manual-axes analogue of the einsum path's psum pair)."""
    B, G, Hg, dh = q.shape
    rk = zk.shape[3]
    rv = zv.shape[3]
    sdh = r_k.shape[-1]
    s = sdh // dh
    qpk = Hg // s
    bs = min(block_s, bias.shape[1])
    S, bias, zk, zv, cos, sin = pad_ring(bias, block_s, zk, zv, cos, sin)
    n_s = S // bs
    half = dh // 2
    apply_knorm, kn = knorm_operand(k_norm, dh)

    grid = (B, G, n_s)
    kernel = functools.partial(
        _kernel, scale=scale, s=s, qpk=qpk, dh=dh, n_s=n_s,
        apply_knorm=apply_knorm, norm_eps=norm_eps, return_lse=return_lse)
    out_shape, out_specs = lse_outputs(B, G, Hg, rv, q.dtype, return_lse)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Hg, dh), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, bs, rk), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, bs, rv), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, rk, sdh), lambda b, g, i: (g, 0, 0)),
            pl.BlockSpec((1, dh), lambda b, g, i: (0, 0)),
            pl.BlockSpec((1, bs, half), lambda b, g, i: (b, i, 0)),
            pl.BlockSpec((1, bs, half), lambda b, g, i: (b, i, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda b, g, i: (b, i, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((Hg, 1), jnp.float32),
            pltpu.VMEM((Hg, 1), jnp.float32),
            pltpu.VMEM((Hg, rv), jnp.float32),
        ],
        interpret=interpret,
    )(q, lane_view(zk), lane_view(zv), r_k, kn, cos, sin, tile_bias(bias, bs))


# ---------------------------------------------------------------------------
# Multi-query variant: S = spec_depth + 1 verify queries in one pass
# ---------------------------------------------------------------------------
#
# The verify step scores S consecutive queries (positions cur..cur+S-1)
# against the ring plus an S-column causal self block.  Rather than a
# second grid axis, the queries ride as extra ROWS: the q operand is
# (B, G, S*Hg, dh) with rows ordered (query, group-slot, head), the
# (m, l, acc) scratch grows to S*Hg rows, and the bias becomes per-query
# (B, S, cols) — each query carries its own causal/window column mask, so
# the joint softmax over [ring | self] matches kv_cache._joint_softmax at
# the logit level (masks enter as additive -inf bias exactly like the
# einsum reader's where(mask, logits, NEG_INF)).  The self block is
# appended by the wrapper as S extra ring columns (the multi-query
# generalization of the deferred-write self column).


def attend_block_mq(q, k, zv, cos, sin, bias, *, scale, nq, s, qpk, dh,
                    m_ref, l_ref, acc_ref):
    """Multi-query online-softmax update over one reconstructed key tile.

    q: (nq*s*qpk, dh) rows ordered (query, group-slot, head);
    bias: (nq, Sb) — per-QUERY column mask.  Reduces to ``attend_block``
    bit-for-bit at nq = 1 (same per-group-slot MXU matmuls, same running
    (m, l, acc) update over nq*Hg rows)."""
    half = dh // 2
    c, si_ = cos[:, None, :], sin[:, None, :]          # (Sb, 1, dh/2)
    k1, k2 = k[..., :half], k[..., half:]
    kr = jnp.concatenate([k1 * c - k2 * si_, k2 * c + k1 * si_], axis=-1)

    sb = k.shape[0]
    qg = q.reshape(nq, s, qpk, dh)
    scores = jnp.stack(
        [(qg[:, i].reshape(nq * qpk, dh) @ kr[:, i, :].T).reshape(nq, qpk, sb)
         for i in range(s)], axis=1
    ) * scale                                          # (nq, s, qpk, Sb)
    scores = scores + bias[:, None, None, :]
    scores = scores.reshape(nq * s * qpk, sb)          # rows (query, slot, head)

    m_prev = m_ref[:, 0]
    l_prev = l_ref[:, 0]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new[:, None])               # (nq*Hg, Sb)
    l_ref[:, 0] = l_prev * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ zv
    m_ref[:, 0] = m_new


def _mq_kernel(q_ref, zk_ref, zv_ref, rk_ref, kn_ref, cos_ref, sin_ref,
               bias_ref, o_ref, *rest, scale, nq, s, qpk, dh, n_s,
               apply_knorm, norm_eps, return_lse=False):
    i_s = pl.program_id(2)
    mo_ref, lo_ref, m_ref, l_ref, acc_ref = split_out_refs(rest, return_lse)

    @pl.when(i_s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bias = bias_ref[0, 0].astype(jnp.float32)          # (nq, Sb)

    @pl.when(jnp.max(bias) > NEG_INF * 0.5)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (nq*Hg, dh)
        zk = zk_ref[0].astype(jnp.float32)
        rk = rk_ref[0].astype(jnp.float32)
        k = zk @ rk
        sb = k.shape[0]
        k = maybe_knorm(k.reshape(sb, s, dh), kn_ref, apply_knorm, norm_eps)
        attend_block_mq(q, k, zv_ref[0].astype(jnp.float32),
                        cos_ref[0].astype(jnp.float32),
                        sin_ref[0].astype(jnp.float32), bias,
                        scale=scale, nq=nq, s=s, qpk=qpk, dh=dh,
                        m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref)

    @pl.when(i_s == n_s - 1)
    def _finish():
        finish_tile(o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref)


def pad_ring_mq(bias: jax.Array, block_s: int, *arrays: jax.Array):
    """Multi-query ``pad_ring``: bias is (B, nq, S) — padding applies to
    the column axis 2 (and axis 1 of the data arrays)."""
    S = bias.shape[2]
    bs = min(block_s, S)
    Sp = -(-S // bs) * bs
    if Sp == S:
        return S, bias, *arrays
    bias = jnp.pad(bias, ((0, 0), (0, 0), (0, Sp - S)),
                   constant_values=NEG_INF)
    arrays = tuple(
        jnp.pad(a, ((0, 0), (0, Sp - S)) + ((0, 0),) * (a.ndim - 2))
        for a in arrays)
    return Sp, bias, *arrays


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_s", "interpret", "norm_eps",
                     "return_lse"),
)
def latent_decode_attention_mq(q, zk, zv, r_k, cos, sin, bias, *,
                               scale: float, block_s: int = 256,
                               interpret: bool = False,
                               k_norm: jax.Array | None = None,
                               norm_eps: float = 1e-6,
                               return_lse: bool = False):
    """Multi-query latent flash decode.

    q: (B, G, nq*Hg, dh) with rows ordered (query, head) — nq verify
    queries pre-rotated at their target positions; zk/zv: (B, S, G, r)
    where S covers [ring | nq appended self columns]; bias: (B, nq, S)
    per-query additive mask.  Returns (B, G, nq*Hg, r_v), plus the (m, l)
    state when ``return_lse`` (see ``latent_decode_attention``)."""
    B, G, QHg, dh = q.shape
    nq = bias.shape[1]
    Hg = QHg // nq
    rk = zk.shape[3]
    rv = zv.shape[3]
    sdh = r_k.shape[-1]
    s = sdh // dh
    qpk = Hg // s
    bs = min(block_s, bias.shape[2])
    S, bias, zk, zv, cos, sin = pad_ring_mq(bias, block_s, zk, zv, cos, sin)
    n_s = S // bs
    half = dh // 2
    apply_knorm, kn = knorm_operand(k_norm, dh)

    grid = (B, G, n_s)
    kernel = functools.partial(
        _mq_kernel, scale=scale, nq=nq, s=s, qpk=qpk, dh=dh, n_s=n_s,
        apply_knorm=apply_knorm, norm_eps=norm_eps, return_lse=return_lse)
    out_shape, out_specs = lse_outputs(B, G, QHg, rv, q.dtype, return_lse)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, QHg, dh), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, bs, rk), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, bs, rv), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, rk, sdh), lambda b, g, i: (g, 0, 0)),
            pl.BlockSpec((1, dh), lambda b, g, i: (0, 0)),
            pl.BlockSpec((1, bs, half), lambda b, g, i: (b, i, 0)),
            pl.BlockSpec((1, bs, half), lambda b, g, i: (b, i, 0)),
            pl.BlockSpec((1, 1, nq, bs), lambda b, g, i: (b, i, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((QHg, 1), jnp.float32),
            pltpu.VMEM((QHg, 1), jnp.float32),
            pltpu.VMEM((QHg, rv), jnp.float32),
        ],
        interpret=interpret,
    )(q, lane_view(zk), lane_view(zv), r_k, kn, cos, sin, tile_bias(bias, bs))


# ---------------------------------------------------------------------------
# Paged variant: gather stage over page indices via scalar prefetch
# ---------------------------------------------------------------------------
#
# In the paged cache layout the latents live page-major in a shared pool
# (n_pages, page_size, G, r) and a (B, n_slot_pages) int32 page table
# maps each slot-page to its physical page.  The gather is an extension
# of the ring kernel's tail-tile masking: the grid's minor axis walks the
# SLOT's pages in order, and each step's physical DMA source comes from
# the scalar-prefetched table (``PrefetchScalarGridSpec`` — the table is
# resident in SMEM before the grid starts, so block index_maps can read
# it).  The self token occupies one extra trailing tile — the same
# [self | -inf padding] column block ``pad_ring`` would produce for the
# ring kernel at block_s = page_size — so with the ring path tiled at
# page_size the two kernels see bitwise-identical tile sequences and
# produce bitwise-identical outputs.


def _paged_kernel(ptab_ref, q_ref, zk_ref, zv_ref, zks_ref, zvs_ref, rk_ref,
                  kn_ref, cos_ref, sin_ref, bias_ref, o_ref,
                  *rest, scale, s, qpk, dh, n_s,
                  apply_knorm, norm_eps, return_lse=False):
    i_s = pl.program_id(2)
    mo_ref, lo_ref, m_ref, l_ref, acc_ref = split_out_refs(rest, return_lse)

    @pl.when(i_s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bias = bias_ref[0, 0].astype(jnp.float32)          # (1, ps)
    is_self = i_s == n_s - 1

    # Same fully-masked-tile skip as the ring kernel: unmapped slot-pages
    # resolve to the null page (pos = -1 -> bias = -inf) and cost no MXU
    # work.  The self tile's column 0 has bias 0, so it always attends.
    @pl.when(jnp.max(bias) > NEG_INF * 0.5)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        zk = jnp.where(is_self, zks_ref[0], zk_ref[0]).astype(jnp.float32)
        zv = jnp.where(is_self, zvs_ref[0], zv_ref[0]).astype(jnp.float32)
        rk = rk_ref[0].astype(jnp.float32)
        k = zk @ rk
        sb = k.shape[0]
        k = maybe_knorm(k.reshape(sb, s, dh), kn_ref, apply_knorm, norm_eps)
        attend_block(q, k, zv, cos_ref[0].astype(jnp.float32),
                     sin_ref[0].astype(jnp.float32), bias,
                     scale=scale, s=s, qpk=qpk, dh=dh,
                     m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref)

    @pl.when(i_s == n_s - 1)
    def _finish():
        finish_tile(o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret", "norm_eps", "return_lse"),
)
def latent_decode_attention_paged(ptab, q, zk, zv, r_k, zk_self, zv_self,
                                  cos, sin, bias, *, scale: float,
                                  interpret: bool = False,
                                  k_norm: jax.Array | None = None,
                                  norm_eps: float = 1e-6,
                                  return_lse: bool = False):
    """Paged-pool flash decode.

    ptab: (B, n_slot_pages) int32 page table (scalar-prefetched);
    zk/zv: (n_pages, page_size, G, r) page-major pools;
    zk_self/zv_self: (B, page_size, G, r) self tiles — row 0 holds the
    deferred-write latent for position cur, rows 1.. are padding;
    cos/sin/bias: (B, n_slot_pages*page_size + page_size, ...) SLOT-major
    tables (ring columns through the table, then the self tile's columns
    with bias [0, -inf...]).  The wrapper in ``kernels.ops`` builds these
    from the pool's gathered ``pos`` — int32-cheap next to the latents,
    which only ever move page-at-a-time inside the kernel.
    Returns (B, G, Hg, r_v) latent outputs."""
    B, n_sp = ptab.shape
    ps = zk.shape[1]
    _, G, Hg, dh = q.shape
    rk = zk.shape[3]
    rv = zv.shape[3]
    sdh = r_k.shape[-1]
    s = sdh // dh
    qpk = Hg // s
    half = dh // 2
    apply_knorm, kn = knorm_operand(k_norm, dh)
    n_s = n_sp + 1                       # slot pages + the self tile

    def pool_map(b, g, i, pt):
        # Clamped on the self step (i == n_sp): the DMA'd page is unused
        # there (the kernel reads the self tile), it just must be in range.
        return (pt[b, jnp.minimum(i, n_sp - 1)], 0, g)

    grid = (B, G, n_s)
    kernel = functools.partial(
        _paged_kernel, scale=scale, s=s, qpk=qpk, dh=dh, n_s=n_s,
        apply_knorm=apply_knorm, norm_eps=norm_eps, return_lse=return_lse)
    out_shape, out_specs = lse_outputs(B, G, Hg, rv, q.dtype, return_lse,
                                       prefetch=True)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Hg, dh), lambda b, g, i, pt: (b, g, 0, 0)),
            pl.BlockSpec((1, ps, rk), pool_map),
            pl.BlockSpec((1, ps, rv), pool_map),
            pl.BlockSpec((1, ps, rk), lambda b, g, i, pt: (b, 0, g)),
            pl.BlockSpec((1, ps, rv), lambda b, g, i, pt: (b, 0, g)),
            pl.BlockSpec((1, rk, sdh), lambda b, g, i, pt: (g, 0, 0)),
            pl.BlockSpec((1, dh), lambda b, g, i, pt: (0, 0)),
            pl.BlockSpec((1, ps, half), lambda b, g, i, pt: (b, i, 0)),
            pl.BlockSpec((1, ps, half), lambda b, g, i, pt: (b, i, 0)),
            pl.BlockSpec((1, 1, 1, ps), lambda b, g, i, pt: (b, i, 0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Hg, 1), jnp.float32),
            pltpu.VMEM((Hg, 1), jnp.float32),
            pltpu.VMEM((Hg, rv), jnp.float32),
        ])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(ptab, q, lane_view(zk), lane_view(zv), lane_view(zk_self),
      lane_view(zv_self), r_k, kn, cos, sin, tile_bias(bias, ps))


def _mq_paged_kernel(ptab_ref, q_ref, zk_ref, zv_ref, zks_ref, zvs_ref,
                     rk_ref, kn_ref, cos_ref, sin_ref, bias_ref, o_ref,
                     *rest, scale, nq, s, qpk, dh, n_sp, n_s,
                     apply_knorm, norm_eps, return_lse=False):
    i_s = pl.program_id(2)
    mo_ref, lo_ref, m_ref, l_ref, acc_ref = split_out_refs(rest, return_lse)

    @pl.when(i_s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bias = bias_ref[0, 0].astype(jnp.float32)          # (nq, ps)
    is_self = i_s >= n_sp

    @pl.when(jnp.max(bias) > NEG_INF * 0.5)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (nq*Hg, dh)
        zk = jnp.where(is_self, zks_ref[0], zk_ref[0]).astype(jnp.float32)
        zv = jnp.where(is_self, zvs_ref[0], zv_ref[0]).astype(jnp.float32)
        rk = rk_ref[0].astype(jnp.float32)
        k = zk @ rk
        sb = k.shape[0]
        k = maybe_knorm(k.reshape(sb, s, dh), kn_ref, apply_knorm, norm_eps)
        attend_block_mq(q, k, zv, cos_ref[0].astype(jnp.float32),
                        sin_ref[0].astype(jnp.float32), bias,
                        scale=scale, nq=nq, s=s, qpk=qpk, dh=dh,
                        m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref)

    @pl.when(i_s == n_s - 1)
    def _finish():
        finish_tile(o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "interpret", "norm_eps", "return_lse"),
)
def latent_decode_attention_mq_paged(ptab, q, zk, zv, r_k, zk_self, zv_self,
                                     cos, sin, bias, *, scale: float,
                                     interpret: bool = False,
                                     k_norm: jax.Array | None = None,
                                     norm_eps: float = 1e-6,
                                     return_lse: bool = False):
    """Multi-query paged flash decode.

    Same pool/page-table contract as ``latent_decode_attention_paged``;
    the differences are multi-query: q is (B, G, nq*Hg, dh) rows ordered
    (query, head); zk_self/zv_self are (B, n_self_tiles*page_size, G, r)
    with the first nq rows holding the deferred verify-window latents
    (n_self_tiles = ceil(nq / page_size) — usually 1); bias is
    (B, nq, (n_slot_pages + n_self_tiles)*page_size) per-query columns.
    The grid walks slot pages then self tiles; on self steps the pool DMA
    is clamped/ignored and the resident self tile attends instead."""
    B, n_sp = ptab.shape
    ps = zk.shape[1]
    _, G, QHg, dh = q.shape
    nq = bias.shape[1]
    Hg = QHg // nq
    rk = zk.shape[3]
    rv = zv.shape[3]
    sdh = r_k.shape[-1]
    s = sdh // dh
    qpk = Hg // s
    half = dh // 2
    apply_knorm, kn = knorm_operand(k_norm, dh)
    n_st = zk_self.shape[1] // ps        # self tiles (>= ceil(nq/ps))
    n_s = n_sp + n_st

    def pool_map(b, g, i, pt):
        return (pt[b, jnp.minimum(i, n_sp - 1)], 0, g)

    def self_map(b, g, i, pt):
        # Before the self region this indexes tile 0 (DMA'd but unused).
        return (b, jnp.maximum(i - n_sp, 0), g)

    grid = (B, G, n_s)
    kernel = functools.partial(
        _mq_paged_kernel, scale=scale, nq=nq, s=s, qpk=qpk, dh=dh,
        n_sp=n_sp, n_s=n_s, apply_knorm=apply_knorm, norm_eps=norm_eps,
        return_lse=return_lse)
    out_shape, out_specs = lse_outputs(B, G, QHg, rv, q.dtype, return_lse,
                                       prefetch=True)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, QHg, dh), lambda b, g, i, pt: (b, g, 0, 0)),
            pl.BlockSpec((1, ps, rk), pool_map),
            pl.BlockSpec((1, ps, rv), pool_map),
            pl.BlockSpec((1, ps, rk), self_map),
            pl.BlockSpec((1, ps, rv), self_map),
            pl.BlockSpec((1, rk, sdh), lambda b, g, i, pt: (g, 0, 0)),
            pl.BlockSpec((1, dh), lambda b, g, i, pt: (0, 0)),
            pl.BlockSpec((1, ps, half), lambda b, g, i, pt: (b, i, 0)),
            pl.BlockSpec((1, ps, half), lambda b, g, i, pt: (b, i, 0)),
            pl.BlockSpec((1, 1, nq, ps), lambda b, g, i, pt: (b, i, 0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((QHg, 1), jnp.float32),
            pltpu.VMEM((QHg, 1), jnp.float32),
            pltpu.VMEM((QHg, rv), jnp.float32),
        ])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(ptab, q, lane_view(zk), lane_view(zv), lane_view(zk_self),
      lane_view(zv_self), r_k, kn, cos, sin, tile_bias(bias, ps))
