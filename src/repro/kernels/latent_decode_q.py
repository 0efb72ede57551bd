"""Pallas TPU kernel: int8-quantized latent-cache flash decode.

Identical dataflow to ``latent_decode`` but the cache tiles arrive as int8
latents with per-token/per-group scales (Table 4 integration: ReCalKV x
per-token quantization).  Dequantization happens in VMEM right before the
reconstruction matmul, so HBM traffic drops by another ~2x vs bf16 latents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.latent_decode import (attend_block, attend_block_mq,
                                         finish_tile, knorm_operand,
                                         lane_view, lse_outputs, maybe_knorm,
                                         pad_ring, pad_ring_mq,
                                         split_out_refs, tile_bias)

NEG_INF = -1e30


def _dequant(q_ref, s_ref, g):
    """int8 latents x per-token/per-group scales -> f32 tile in VMEM.

    q_ref: (1, Sb, r) — this group's lanes of the (B, S, G*r) view;
    s_ref: (1, Sb, G) — every group's scales (a (1, Sb, 1) block of the
    (B, S, G) scales is refused by Mosaic), so this group's column is
    picked out by an exact one-hot select-and-sum (``g`` is the grid's
    group index, read outside any ``pl.when`` body)."""
    sc = s_ref[0].astype(jnp.float32)                      # (Sb, G)
    col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1) == g
    sc = jnp.sum(jnp.where(col, sc, 0.0), axis=1, keepdims=True)
    return q_ref[0].astype(jnp.float32) * sc


def _kernel(q_ref, zkq_ref, zks_ref, zvq_ref, zvs_ref, rk_ref, kn_ref,
            cos_ref, sin_ref, bias_ref, o_ref, *rest,
            scale, s, qpk, dh, n_s, apply_knorm, norm_eps,
            return_lse=False):
    g, i_s = pl.program_id(1), pl.program_id(2)
    mo_ref, lo_ref, m_ref, l_ref, acc_ref = split_out_refs(rest, return_lse)

    @pl.when(i_s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bias = bias_ref[0, 0].astype(jnp.float32)          # (1, Sb)

    @pl.when(jnp.max(bias) > NEG_INF * 0.5)       # skip fully-masked tiles
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (Hg, dh)
        zk = _dequant(zkq_ref, zks_ref, g)                   # (Sb, r_k)
        rk = rk_ref[0].astype(jnp.float32)
        k = zk @ rk
        sb = k.shape[0]
        k = maybe_knorm(k.reshape(sb, s, dh), kn_ref, apply_knorm, norm_eps)
        attend_block(q, k, _dequant(zvq_ref, zvs_ref, g),
                     cos_ref[0].astype(jnp.float32),
                     sin_ref[0].astype(jnp.float32), bias,
                     scale=scale, s=s, qpk=qpk, dh=dh,
                     m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref)

    @pl.when(i_s == n_s - 1)
    def _finish():
        finish_tile(o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_s", "interpret", "norm_eps",
                              "return_lse"))
def latent_decode_attention_quant(q, zk_q, zk_scale, zv_q, zv_scale, r_k,
                                  cos, sin, bias, *, scale: float,
                                  block_s: int = 256, interpret: bool = False,
                                  k_norm: jax.Array | None = None,
                                  norm_eps: float = 1e-6,
                                  return_lse: bool = False):
    """zk_q/zv_q: int8 (B, S, G, r); zk_scale/zv_scale: (B, S, G) f32.
    Tail tiles are padded/masked internally; ``k_norm`` as in
    :func:`~repro.kernels.latent_decode.latent_decode_attention`."""
    B, G, Hg, dh = q.shape
    rk = zk_q.shape[3]
    rv = zv_q.shape[3]
    sdh = r_k.shape[-1]
    s = sdh // dh
    qpk = Hg // s
    bs = min(block_s, bias.shape[1])
    S, bias, zk_q, zk_scale, zv_q, zv_scale, cos, sin = pad_ring(
        bias, block_s, zk_q, zk_scale, zv_q, zv_scale, cos, sin)
    n_s = S // bs
    half = dh // 2
    apply_knorm, kn = knorm_operand(k_norm, dh)

    kernel = functools.partial(
        _kernel, scale=scale, s=s, qpk=qpk, dh=dh, n_s=n_s,
        apply_knorm=apply_knorm, norm_eps=norm_eps, return_lse=return_lse)
    out_shape, out_specs = lse_outputs(B, G, Hg, rv, q.dtype, return_lse)
    return pl.pallas_call(
        kernel,
        grid=(B, G, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, Hg, dh), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, bs, rk), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, bs, G), lambda b, g, i: (b, i, 0)),
            pl.BlockSpec((1, bs, rv), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, bs, G), lambda b, g, i: (b, i, 0)),
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
    )(q, lane_view(zk_q), zk_scale, lane_view(zv_q), zv_scale, r_k, kn, cos,
      sin, tile_bias(bias, bs))


def _mq_kernel_q(q_ref, zkq_ref, zks_ref, zvq_ref, zvs_ref, rk_ref, kn_ref,
                 cos_ref, sin_ref, bias_ref, o_ref, *rest,
                 scale, nq, s, qpk, dh, n_s, apply_knorm, norm_eps,
                 return_lse=False):
    g, i_s = pl.program_id(1), pl.program_id(2)
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
        zk = _dequant(zkq_ref, zks_ref, g)
        rk = rk_ref[0].astype(jnp.float32)
        k = zk @ rk
        sb = k.shape[0]
        k = maybe_knorm(k.reshape(sb, s, dh), kn_ref, apply_knorm, norm_eps)
        attend_block_mq(q, k, _dequant(zvq_ref, zvs_ref, g),
                        cos_ref[0].astype(jnp.float32),
                        sin_ref[0].astype(jnp.float32), bias,
                        scale=scale, nq=nq, s=s, qpk=qpk, dh=dh,
                        m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref)

    @pl.when(i_s == n_s - 1)
    def _finish():
        finish_tile(o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_s", "interpret", "norm_eps",
                              "return_lse"))
def latent_decode_attention_mq_quant(q, zk_q, zk_scale, zv_q, zv_scale, r_k,
                                     cos, sin, bias, *, scale: float,
                                     block_s: int = 256,
                                     interpret: bool = False,
                                     k_norm: jax.Array | None = None,
                                     norm_eps: float = 1e-6,
                                     return_lse: bool = False):
    """Multi-query int8 latent flash decode.

    q: (B, G, nq*Hg, dh) rows ordered (query, head); bias: (B, nq, S)
    per-query columns over [ring | nq appended self columns].  The self
    columns carry the quantize-then-dequantize verify-window latents, so
    in-kernel dequantization reproduces the einsum reader's
    ``latent_cache_arrays(entry)`` round-trip exactly."""
    B, G, QHg, dh = q.shape
    nq = bias.shape[1]
    Hg = QHg // nq
    rk = zk_q.shape[3]
    rv = zv_q.shape[3]
    sdh = r_k.shape[-1]
    s = sdh // dh
    qpk = Hg // s
    bs = min(block_s, bias.shape[2])
    S, bias, zk_q, zk_scale, zv_q, zv_scale, cos, sin = pad_ring_mq(
        bias, block_s, zk_q, zk_scale, zv_q, zv_scale, cos, sin)
    n_s = S // bs
    half = dh // 2
    apply_knorm, kn = knorm_operand(k_norm, dh)

    kernel = functools.partial(
        _mq_kernel_q, scale=scale, nq=nq, s=s, qpk=qpk, dh=dh, n_s=n_s,
        apply_knorm=apply_knorm, norm_eps=norm_eps, return_lse=return_lse)
    out_shape, out_specs = lse_outputs(B, G, QHg, rv, q.dtype, return_lse)
    return pl.pallas_call(
        kernel,
        grid=(B, G, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, QHg, dh), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, bs, rk), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, bs, G), lambda b, g, i: (b, i, 0)),
            pl.BlockSpec((1, bs, rv), lambda b, g, i: (b, i, g)),
            pl.BlockSpec((1, bs, G), lambda b, g, i: (b, i, 0)),
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
    )(q, lane_view(zk_q), zk_scale, lane_view(zv_q), zv_scale, r_k, kn, cos,
      sin, tile_bias(bias, bs))
