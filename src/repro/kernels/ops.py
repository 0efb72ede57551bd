"""Jit'd convenience wrappers around the Pallas kernels.

These adapt model-layer tensors (cache dicts, position arrays) to kernel
calling conventions, pick block sizes, and resolve interpret mode from the
platform: on CPU/GPU the kernels run through the Pallas interpreter (the
validation mode used by every test); on TPU the same calls lower through
Mosaic.  Pass ``interpret=True/False`` to override.

Decode-side wrappers accept a ``self_entry`` — the current token's K/V (or
latents), which the model keeps out of the ring until after the layer scan
(deferred writes).  The wrapper appends it as an extra ring column at
position ``cur`` before calling the kernel, so the joint softmax over
[cache | self] matches the model's two-part einsum softmax exactly.

Known cost of that design: the concat materializes a ring copy per layer
per step, and when the ring length is a tile multiple the S+1-th column
opens one extra (otherwise dead) key tile — the kernels skip fully-masked
tiles, so the extra tile costs a DMA but no MXU work.  Eliminating the
copy needs write-before-attend (ring writes inside the layer scan), which
trades back the scan-rematerialization cost deferred writes exist to
avoid (EXPERIMENTS.md §Perf iteration 3) — revisit on TPU profiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.expert_gmm import expert_gmm as _expert_gmm
from repro.kernels.flash_prefill import flash_prefill_attention
from repro.kernels.latent_decode import (NEG_INF, latent_decode_attention,
                                         latent_decode_attention_mq,
                                         latent_decode_attention_mq_paged,
                                         latent_decode_attention_paged)
from repro.kernels.latent_decode_q import (latent_decode_attention_mq_quant,
                                           latent_decode_attention_quant)
from repro.sharding import rules as R


def default_interpret() -> bool:
    """Interpret mode for the current platform: real lowering only on TPU."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: bool | None) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def decode_bias(pos: jax.Array, cur: jax.Array, window: int | None) -> jax.Array:
    """Additive (B, S) mask from stored slot positions + current position."""
    valid = (pos >= 0) & (pos <= cur[:, None])
    if window is not None:
        valid &= pos > (cur[:, None] - window)
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


def rope_tables_for(pos: jax.Array, dh: int, theta: float):
    """cos/sin (B, S, dh/2) for stored (clamped) positions."""
    half = dh // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.maximum(pos, 0).astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def group_queries(q: jax.Array, num_groups: int) -> jax.Array:
    """(B, H, dh) -> (B, G, Hg, dh) in kernel head order (kv-major)."""
    B, H, dh = q.shape
    return q.reshape(B, num_groups, H // num_groups, dh)


def ungroup_outputs(o: jax.Array) -> jax.Array:
    """(B, G, Hg, rv) -> (B, H, rv)."""
    B, G, Hg, rv = o.shape
    return o.reshape(B, G * Hg, rv)


def _extend_ring(cache: dict, self_entry: dict | None, cur: jax.Array):
    """Append the (deferred-write) current token as one extra ring column.

    cache leaves are (B, S, ...); self_entry leaves are the matching
    (B, ...) slot values.  Returns (arrays, pos) with S+1 columns."""
    pos = cache["pos"]
    arrs = {k: v for k, v in cache.items() if k != "pos"}
    if self_entry is None:
        return arrs, pos
    arrs = {k: jnp.concatenate([v, self_entry[k][:, None].astype(v.dtype)],
                               axis=1)
            for k, v in arrs.items()}
    pos = jnp.concatenate([pos, cur[:, None].astype(pos.dtype)], axis=1)
    return arrs, pos


def _extend_ring_mq(cache: dict, self_entries: dict, pos_q: jax.Array):
    """Multi-query ``_extend_ring``: append the nq deferred verify-window
    tokens as nq extra ring columns.  self_entries leaves are (B, nq, ...)
    — the same layout each leaf has at one column, stacked; pos_q (B, nq)
    are their target positions."""
    pos = cache["pos"]
    arrs = {k: jnp.concatenate([v, self_entries[k].astype(v.dtype)], axis=1)
            for k, v in cache.items() if k != "pos"}
    pos = jnp.concatenate([pos, pos_q.astype(pos.dtype)], axis=1)
    return arrs, pos


def group_queries_mq(q: jax.Array, num_groups: int) -> jax.Array:
    """(B, nq, H, dh) -> (B, G, nq*Hg, dh), rows ordered (query, head) —
    the multi-query kernels' row layout (see latent_decode._mq_kernel)."""
    B, nq, H, dh = q.shape
    hg = H // num_groups
    q = q.reshape(B, nq, num_groups, hg, dh)
    return q.transpose(0, 2, 1, 3, 4).reshape(B, num_groups, nq * hg, dh)


def ungroup_outputs_mq(o: jax.Array, nq: int) -> jax.Array:
    """(B, G, nq*Hg, rv) -> (B, nq, H, rv)."""
    B, G, QHg, rv = o.shape
    hg = QHg // nq
    o = o.reshape(B, G, nq, hg, rv)
    return o.transpose(0, 2, 1, 3, 4).reshape(B, nq, G * hg, rv)


def verify_bias(pos_ext: jax.Array, pos_q: jax.Array, feed_mask: jax.Array,
                window: int | None, self_start: int) -> jax.Array:
    """Additive (B, nq, S_ext) mask for nq verify queries over extended
    columns [ring | self].  Ring-mask semantics apply everywhere — the
    self columns store pos_q, so causality (j >= n) and the window fall
    out of the stored-position compare — then ``feed_mask`` is AND'd onto
    the nq real self columns at ``self_start``.  Logit-level match for
    kv_cache._verify_masks' (ring_mask, self_mask) pair."""
    nq = pos_q.shape[1]
    valid = (pos_ext[:, None, :] >= 0) & (pos_ext[:, None, :] <= pos_q[:, :, None])
    if window is not None:
        valid &= pos_ext[:, None, :] > (pos_q[:, :, None] - window)
    sl = slice(self_start, self_start + nq)
    valid = valid.at[:, :, sl].set(
        valid[:, :, sl] & feed_mask[:, None, :].astype(bool))
    return jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)


# ---------------------------------------------------------------------------
# shard_map routing: the kernels under SPMD
# ---------------------------------------------------------------------------
#
# Under pjit the einsum decode readers get sequence-parallel flash
# attention for free (the softmax over the "model"-sharded S axis becomes
# a psum pair).  A pallas_call cannot ride that: inside pjit it demands
# fully replicated operands, and partial-auto shard_map around it trips
# XLA's manual-subgroup check.  So the kernels go under a FULL-manual
# shard_map over every mesh axis: each shard runs the unmodified kernel
# on its local ring/page slice with ``return_lse`` on, the deferred self
# column is enabled on exactly one "model" shard, and the partial outputs
# merge with the same LSE algebra pjit would have synthesized.

def _seq_shardable(mesh, cols: int) -> bool:
    """Kernel-under-shard_map eligibility: >1 "model" shard and the
    sharded column count (ring length / page size) divides evenly."""
    n = R.kernel_seq_shards(mesh)
    return n > 1 and cols % n == 0


def _merge_partial_softmax(o, m, l):
    """LSE merge of per-shard partial flash outputs across "model": each
    shard's o = acc/l at running max m; reweight by l*exp(m - max) and
    renormalize.  A fully-masked shard has l == 0 and drops out."""
    mg = jax.lax.pmax(m, "model")
    w = l * jnp.exp(m - mg)                        # (B, G, rows, 1)
    num = jax.lax.psum(o.astype(jnp.float32) * w, "model")
    den = jax.lax.psum(w, "model")
    return (num / jnp.maximum(den, 1e-30)).astype(o.dtype)


def _shard_kernel_call(mesh, B: int, main, main_spec, slot, repl, body):
    """Run ``body`` under a full-manual shard_map over the serving mesh.

    main: the cache pytree, sharded by ``main_spec(leaf, batch)`` (ring
    leaves split (batch, model); paged pools split page rows on "model");
    slot: per-slot operands (q, cur, self entries, ...) split on batch
    only; repl: replicated params (R_k, k_norm).  body(main, slot, repl,
    self_on) returns the (o, m, l) partial-softmax triple; ``self_on`` is
    true on exactly one "model" shard so the deferred self token scores
    once.  Returns the merged grouped output, replicated over "model"."""
    batch = R.kernel_batch_axes(mesh, B)
    n_sh = R.kernel_seq_shards(mesh)
    in_specs = (
        jax.tree.map(lambda x: main_spec(x, batch), main),
        jax.tree.map(lambda x: R.kernel_slot_spec(x, batch), slot),
        jax.tree.map(R.kernel_repl_spec, repl),
    )

    def wrapped(main_l, slot_l, repl_l):
        self_on = jax.lax.axis_index("model") == n_sh - 1
        o, m, l = body(main_l, slot_l, repl_l, self_on)
        return _merge_partial_softmax(o, m, l)

    return jax.shard_map(wrapped, mesh=mesh, in_specs=in_specs,
                         out_specs=P(batch, None, None, None),
                         check_vma=False)(main, slot, repl)


def _mask_self_cols(bias, self_on, start):
    """-inf the appended self columns unless this shard owns them.
    bias: (B, S) single-query or (B, nq, S) multi-query."""
    if self_on is None:
        return bias
    idx = (slice(None),) * (bias.ndim - 1) + (slice(start, None),)
    return bias.at[idx].set(jnp.where(self_on, bias[idx], NEG_INF))


# ---------------------------------------------------------------------------
# Ring-layout cores + public wrappers
# ---------------------------------------------------------------------------


def _latent_ring_core(qg, arrs, pos, r_k, cur, *, theta, window, scale,
                      block_s, interpret, k_norm, norm_eps,
                      self_on=None, with_lse=False):
    """Ring latent attention over an already-extended cache (grouped in,
    grouped out).  ``self_on``/``with_lse`` serve the shard_map caller:
    keep the appended self column on one shard only, and return the
    (o, m, l) triple for the cross-shard merge."""
    quant = "zk_q" in arrs
    S = pos.shape[1]
    dh = qg.shape[-1]
    cos, sin = rope_tables_for(pos, dh, theta)
    bias = _mask_self_cols(decode_bias(pos, cur, window), self_on, -1)
    kw = dict(scale=scale, block_s=min(block_s, S), interpret=interpret,
              k_norm=k_norm, norm_eps=norm_eps, return_lse=with_lse)
    if quant:
        return latent_decode_attention_quant(
            qg, arrs["zk_q"], arrs["zk_s"], arrs["zv_q"], arrs["zv_s"],
            r_k, cos, sin, bias, **kw)
    return latent_decode_attention(qg, arrs["zk"], arrs["zv"], r_k,
                                   cos, sin, bias, **kw)


def _dense_ring_core(qg, arrs, pos, cur, *, window, scale, block_s,
                     interpret, self_on=None, with_lse=False):
    """Dense ring decode as the degenerate latent case: identity
    reconstruction (r_k = I), identity rotation (keys stored post-RoPE)."""
    k, v = arrs["k"], arrs["v"]
    B, S, Hkv, dh = k.shape
    eye = jnp.broadcast_to(jnp.eye(dh, dtype=k.dtype), (Hkv, dh, dh))
    ones = jnp.ones((B, S, dh // 2), jnp.float32)
    bias = _mask_self_cols(decode_bias(pos, cur, window), self_on, -1)
    return latent_decode_attention(qg, k, v, eye, ones, jnp.zeros_like(ones),
                                   bias, scale=scale, block_s=min(block_s, S),
                                   interpret=interpret, return_lse=with_lse)


def _latent_ring_core_mq(qg, arrs, pos_ext, r_k, pos_q, feed_mask, *,
                         theta, window, scale, block_s, interpret, k_norm,
                         norm_eps, self_on=None, with_lse=False):
    nq = pos_q.shape[1]
    quant = "zk_q" in arrs
    S = pos_ext.shape[1]
    dh = qg.shape[-1]
    cos, sin = rope_tables_for(pos_ext, dh, theta)
    bias = verify_bias(pos_ext, pos_q, feed_mask, window, S - nq)
    bias = _mask_self_cols(bias, self_on, S - nq)
    kw = dict(scale=scale, block_s=min(block_s, S), interpret=interpret,
              k_norm=k_norm, norm_eps=norm_eps, return_lse=with_lse)
    if quant:
        return latent_decode_attention_mq_quant(
            qg, arrs["zk_q"], arrs["zk_s"], arrs["zv_q"], arrs["zv_s"],
            r_k, cos, sin, bias, **kw)
    return latent_decode_attention_mq(qg, arrs["zk"], arrs["zv"], r_k,
                                      cos, sin, bias, **kw)


def _dense_ring_core_mq(qg, arrs, pos_ext, pos_q, feed_mask, *, window,
                        scale, block_s, interpret, self_on=None,
                        with_lse=False):
    nq = pos_q.shape[1]
    k, v = arrs["k"], arrs["v"]
    B, S, Hkv, dh = k.shape
    eye = jnp.broadcast_to(jnp.eye(dh, dtype=k.dtype), (Hkv, dh, dh))
    ones = jnp.ones((B, S, dh // 2), jnp.float32)
    bias = verify_bias(pos_ext, pos_q, feed_mask, window, S - nq)
    bias = _mask_self_cols(bias, self_on, S - nq)
    return latent_decode_attention_mq(
        qg, k, v, eye, ones, jnp.zeros_like(ones), bias, scale=scale,
        block_s=min(block_s, S), interpret=interpret, return_lse=with_lse)


def latent_decode(q, cache, r_k, cur, *, theta: float, window: int | None,
                  scale: float, block_s: int = 256, use_kernel: bool = True,
                  interpret: bool | None = None, self_entry: dict | None = None,
                  k_norm: jax.Array | None = None, norm_eps: float = 1e-6,
                  mesh=None):
    """End-to-end latent decode from a model cache dict.

    q: (B, H, dh) post-RoPE grouped-orderable queries;
    cache: {"zk","zv","pos"} — or the int8 ring {"zk_q","zk_s","zv_q",
    "zv_s","pos"} — as produced by the model layer.  ``self_entry`` holds
    the current token's latents in the same (quantized or not) layout.
    With ``mesh`` (and >1 "model" shard dividing the ring length), the
    kernel runs under shard_map on each shard's ring slice with an LSE
    merge across shards.  Returns (B, H, r_v) latent outputs.
    """
    quant = "zk_q" in cache
    G = (cache["zk_q"] if quant else cache["zk"]).shape[2]
    qg = group_queries(q, G)
    itp = _resolve_interpret(interpret)
    if (use_kernel and self_entry is not None
            and _seq_shardable(mesh, cache["pos"].shape[1])):
        def body(cache_l, slot_l, repl_l, self_on):
            qg_l, cur_l, entry_l = slot_l
            r_k_l, kn_l = repl_l
            arrs, pos = _extend_ring(cache_l, entry_l, cur_l)
            return _latent_ring_core(
                qg_l, arrs, pos, r_k_l, cur_l, theta=theta, window=window,
                scale=scale, block_s=block_s, interpret=itp, k_norm=kn_l,
                norm_eps=norm_eps, self_on=self_on, with_lse=True)
        o = _shard_kernel_call(mesh, q.shape[0], cache, R.kernel_ring_spec,
                               (qg, cur, self_entry), (r_k, k_norm), body)
        return ungroup_outputs(o)
    arrs, pos = _extend_ring(cache, self_entry, cur)
    if use_kernel:
        o = _latent_ring_core(qg, arrs, pos, r_k, cur, theta=theta,
                              window=window, scale=scale, block_s=block_s,
                              interpret=itp, k_norm=k_norm, norm_eps=norm_eps)
    else:
        if quant:
            from repro.quant import dequantize
            zk = dequantize(arrs["zk_q"], arrs["zk_s"][..., None])
            zv = dequantize(arrs["zv_q"], arrs["zv_s"][..., None])
        else:
            zk, zv = arrs["zk"], arrs["zv"]
        if k_norm is not None:
            raise NotImplementedError("ref path applies no k-norm")
        dh = q.shape[-1]
        cos, sin = rope_tables_for(pos, dh, theta)
        bias = decode_bias(pos, cur, window)
        o = ref.latent_decode_attention(qg, zk, zv, r_k, cos, sin, bias, scale)
    return ungroup_outputs(o)


def dense_decode(q, cache, cur, *, window: int | None, scale: float,
                 block_s: int = 256, interpret: bool | None = None,
                 self_entry: dict | None = None, mesh=None):
    """Dense-cache decode through the latent kernel.

    The dense ring {"k","v","pos"} is the degenerate latent cache: one kv
    head per group, identity reconstruction (r_k = I), identity rotation
    (keys are stored post-RoPE, so cos=1/sin=0).  q: (B, H, dh) post-RoPE;
    self_entry: {"k","v"} (B, Hkv, dh) post-RoPE/norm.  ``mesh`` shards the
    ring as in :func:`latent_decode`.  Returns (B, H, dh).
    """
    Hkv = cache["k"].shape[2]
    qg = group_queries(q, Hkv)
    itp = _resolve_interpret(interpret)
    if (self_entry is not None
            and _seq_shardable(mesh, cache["pos"].shape[1])):
        def body(cache_l, slot_l, repl_l, self_on):
            qg_l, cur_l, entry_l = slot_l
            arrs, pos = _extend_ring(cache_l, entry_l, cur_l)
            return _dense_ring_core(
                qg_l, arrs, pos, cur_l, window=window, scale=scale,
                block_s=block_s, interpret=itp, self_on=self_on,
                with_lse=True)
        o = _shard_kernel_call(mesh, q.shape[0], cache, R.kernel_ring_spec,
                               (qg, cur, self_entry), (), body)
        return ungroup_outputs(o)
    arrs, pos = _extend_ring(cache, self_entry, cur)
    o = _dense_ring_core(qg, arrs, pos, cur, window=window, scale=scale,
                         block_s=block_s, interpret=itp)
    return ungroup_outputs(o)


def latent_decode_mq(q, cache, r_k, cur, feed_mask, self_entries, *,
                     theta: float, window: int | None, scale: float,
                     block_s: int = 256, interpret: bool | None = None,
                     k_norm: jax.Array | None = None, norm_eps: float = 1e-6,
                     mesh=None):
    """Multi-query (verify-step) latent decode over a ring cache.

    q: (B, nq, H, dh) queries pre-rotated at positions cur..cur+nq-1;
    feed_mask: (B, nq) bool — which candidate tokens were actually fed;
    self_entries: the nq deferred verify-window latents, same leaf layout
    as the cache at leading shape (B, nq, ...).  Scores all nq queries in
    one kernel pass against [ring | nq self columns] with a joint softmax
    matching the einsum verify readers.  Returns (B, nq, H, r_v)."""
    B, nq = feed_mask.shape
    quant = "zk_q" in cache
    G = (cache["zk_q"] if quant else cache["zk"]).shape[2]
    pos_q = cur[:, None] + jnp.arange(nq, dtype=cur.dtype)
    qg = group_queries_mq(q, G)
    itp = _resolve_interpret(interpret)
    if _seq_shardable(mesh, cache["pos"].shape[1]):
        def body(cache_l, slot_l, repl_l, self_on):
            qg_l, pos_q_l, feed_l, entries_l = slot_l
            r_k_l, kn_l = repl_l
            arrs, pos_ext = _extend_ring_mq(cache_l, entries_l, pos_q_l)
            return _latent_ring_core_mq(
                qg_l, arrs, pos_ext, r_k_l, pos_q_l, feed_l, theta=theta,
                window=window, scale=scale, block_s=block_s, interpret=itp,
                k_norm=kn_l, norm_eps=norm_eps, self_on=self_on,
                with_lse=True)
        o = _shard_kernel_call(mesh, B, cache, R.kernel_ring_spec,
                               (qg, pos_q, feed_mask, self_entries),
                               (r_k, k_norm), body)
    else:
        arrs, pos_ext = _extend_ring_mq(cache, self_entries, pos_q)
        o = _latent_ring_core_mq(qg, arrs, pos_ext, r_k, pos_q, feed_mask,
                                 theta=theta, window=window, scale=scale,
                                 block_s=block_s, interpret=itp,
                                 k_norm=k_norm, norm_eps=norm_eps)
    return ungroup_outputs_mq(o, nq)


def dense_decode_mq(q, cache, cur, feed_mask, self_entries, *,
                    window: int | None, scale: float, block_s: int = 256,
                    interpret: bool | None = None, mesh=None):
    """Multi-query dense verify decode — degenerate-latent trick over the
    dense ring.  q and self_entries["k"] arrive post-RoPE (rotated at
    cur..cur+nq-1), so the identity tables apply.  Returns (B, nq, H, dh)."""
    B, nq = feed_mask.shape
    Hkv = cache["k"].shape[2]
    pos_q = cur[:, None] + jnp.arange(nq, dtype=cur.dtype)
    qg = group_queries_mq(q, Hkv)
    itp = _resolve_interpret(interpret)
    if _seq_shardable(mesh, cache["pos"].shape[1]):
        def body(cache_l, slot_l, repl_l, self_on):
            qg_l, pos_q_l, feed_l, entries_l = slot_l
            arrs, pos_ext = _extend_ring_mq(cache_l, entries_l, pos_q_l)
            return _dense_ring_core_mq(
                qg_l, arrs, pos_ext, pos_q_l, feed_l, window=window,
                scale=scale, block_s=block_s, interpret=itp,
                self_on=self_on, with_lse=True)
        o = _shard_kernel_call(mesh, B, cache, R.kernel_ring_spec,
                               (qg, pos_q, feed_mask, self_entries), (), body)
    else:
        arrs, pos_ext = _extend_ring_mq(cache, self_entries, pos_q)
        o = _dense_ring_core_mq(qg, arrs, pos_ext, pos_q, feed_mask,
                                window=window, scale=scale, block_s=block_s,
                                interpret=itp)
    return ungroup_outputs_mq(o, nq)


def _paged_pos_view(pool_pos: jax.Array, ptab: jax.Array) -> jax.Array:
    """Slot-major (B, n_slot_pages*page_size) positions gathered through the
    page table — int32-cheap; the latents themselves stay page-major and
    only move inside the kernel."""
    B, n_sp = ptab.shape
    ps = pool_pos.shape[1]
    return jnp.take(pool_pos, ptab.reshape(-1), axis=0).reshape(B, n_sp * ps)


def _self_tile(entry: jax.Array, ps: int) -> jax.Array:
    """(B, ...) self entry -> (B, page_size, ...) tile with row 0 real and
    rows 1.. zero — the same [self | padding] block ``pad_ring`` yields
    for the ring kernel when the ring length is a tile multiple."""
    B = entry.shape[0]
    tile = jnp.zeros((B, ps) + entry.shape[1:], entry.dtype)
    return tile.at[:, 0].set(entry)


def _paged_tables(pos_view: jax.Array, cur: jax.Array, window: int | None,
                  dh: int, theta: float | None, ps: int):
    """Slot-major bias/cos/sin covering [table-gathered ring | self tile].

    Self-tile columns: col 0 gets bias 0 and the rotation for position
    ``cur`` (identity when theta is None — dense caches store post-RoPE
    keys), cols 1.. get bias -inf and zero tables, matching ``pad_ring``'s
    padding bitwise."""
    B = cur.shape[0]
    half = dh // 2
    bias_r = decode_bias(pos_view, cur, window)
    bias_s = jnp.full((B, ps), NEG_INF, jnp.float32).at[:, 0].set(0.0)
    if theta is None:
        cos_r = jnp.ones((B, pos_view.shape[1], half), jnp.float32)
        sin_r = jnp.zeros_like(cos_r)
        cos_1 = jnp.ones((B, 1, half), jnp.float32)
        sin_1 = jnp.zeros((B, 1, half), jnp.float32)
    else:
        cos_r, sin_r = rope_tables_for(pos_view, dh, theta)
        cos_1, sin_1 = rope_tables_for(cur[:, None], dh, theta)
    cos_s = jnp.zeros((B, ps, half), jnp.float32).at[:, :1].set(cos_1)
    sin_s = jnp.zeros((B, ps, half), jnp.float32).at[:, :1].set(sin_1)
    return (jnp.concatenate([bias_r, bias_s], axis=1),
            jnp.concatenate([cos_r, cos_s], axis=1),
            jnp.concatenate([sin_r, sin_s], axis=1))


def _self_tiles_mq(entry: jax.Array, ps: int, n_st: int) -> jax.Array:
    """(B, nq, ...) self entries -> (B, n_st*page_size, ...) tiles with
    rows 0..nq-1 real and the rest zero padding."""
    B, nq = entry.shape[:2]
    tiles = jnp.zeros((B, n_st * ps) + entry.shape[2:], entry.dtype)
    return tiles.at[:, :nq].set(entry)


def _mq_paged_setup(pool_pos, ptab, pos_q, feed_mask, window, dh, theta):
    """(n_st, ring_cols, bias, cos, sin) for the multi-query paged
    kernels: slot-major tables over [gathered ring | self tiles], with
    the self tiles' first nq columns carrying pos_q (padding rows get
    pos = -1 -> bias = -inf, same as unmapped slot pages)."""
    B, nq = pos_q.shape
    ps = pool_pos.shape[1]
    n_st = -(-nq // ps)
    pos_view = _paged_pos_view(pool_pos, ptab)
    L = pos_view.shape[1]
    pos_self = jnp.full((B, n_st * ps), -1,
                        pos_view.dtype).at[:, :nq].set(pos_q)
    pos_ext = jnp.concatenate([pos_view, pos_self], axis=1)
    bias = verify_bias(pos_ext, pos_q, feed_mask, window, L)
    half = dh // 2
    if theta is None:
        cos = jnp.ones((B, pos_ext.shape[1], half), jnp.float32)
        sin = jnp.zeros_like(cos)
    else:
        cos, sin = rope_tables_for(pos_ext, dh, theta)
    return n_st, L, bias, cos, sin


def _latent_paged_core(qg, pool, ptab, r_k, cur, entry, *, theta, window,
                       scale, interpret, k_norm, norm_eps,
                       self_on=None, with_lse=False):
    ps = pool["pos"].shape[1]
    dh = qg.shape[-1]
    pos_view = _paged_pos_view(pool["pos"], ptab)
    bias, cos, sin = _paged_tables(pos_view, cur, window, dh, theta, ps)
    bias = _mask_self_cols(bias, self_on, pos_view.shape[1])
    return latent_decode_attention_paged(
        ptab, qg, pool["zk"], pool["zv"], r_k,
        _self_tile(entry["zk"], ps), _self_tile(entry["zv"], ps),
        cos, sin, bias, scale=scale, interpret=interpret,
        k_norm=k_norm, norm_eps=norm_eps, return_lse=with_lse)


def _dense_paged_core(qg, pool, ptab, cur, entry, *, window, scale,
                      interpret, self_on=None, with_lse=False):
    ps = pool["pos"].shape[1]
    k = pool["k"]
    Hkv, dh = k.shape[2], k.shape[3]
    eye = jnp.broadcast_to(jnp.eye(dh, dtype=k.dtype), (Hkv, dh, dh))
    pos_view = _paged_pos_view(pool["pos"], ptab)
    bias, cos, sin = _paged_tables(pos_view, cur, window, dh, None, ps)
    bias = _mask_self_cols(bias, self_on, pos_view.shape[1])
    return latent_decode_attention_paged(
        ptab, qg, k, pool["v"], eye,
        _self_tile(entry["k"], ps), _self_tile(entry["v"], ps),
        cos, sin, bias, scale=scale, interpret=interpret,
        return_lse=with_lse)


def _latent_paged_core_mq(qg, pool, ptab, r_k, pos_q, feed_mask, entries, *,
                          theta, window, scale, interpret, k_norm, norm_eps,
                          self_on=None, with_lse=False):
    ps = pool["pos"].shape[1]
    dh = qg.shape[-1]
    n_st, L, bias, cos, sin = _mq_paged_setup(
        pool["pos"], ptab, pos_q, feed_mask, window, dh, theta)
    bias = _mask_self_cols(bias, self_on, L)
    return latent_decode_attention_mq_paged(
        ptab, qg, pool["zk"], pool["zv"], r_k,
        _self_tiles_mq(entries["zk"], ps, n_st),
        _self_tiles_mq(entries["zv"], ps, n_st),
        cos, sin, bias, scale=scale, interpret=interpret,
        k_norm=k_norm, norm_eps=norm_eps, return_lse=with_lse)


def _dense_paged_core_mq(qg, pool, ptab, pos_q, feed_mask, entries, *,
                         window, scale, interpret, self_on=None,
                         with_lse=False):
    ps = pool["pos"].shape[1]
    k = pool["k"]
    Hkv, dh = k.shape[2], k.shape[3]
    eye = jnp.broadcast_to(jnp.eye(dh, dtype=k.dtype), (Hkv, dh, dh))
    n_st, L, bias, cos, sin = _mq_paged_setup(
        pool["pos"], ptab, pos_q, feed_mask, window, dh, None)
    bias = _mask_self_cols(bias, self_on, L)
    return latent_decode_attention_mq_paged(
        ptab, qg, k, pool["v"], eye,
        _self_tiles_mq(entries["k"], ps, n_st),
        _self_tiles_mq(entries["v"], ps, n_st),
        cos, sin, bias, scale=scale, interpret=interpret,
        return_lse=with_lse)


def latent_decode_paged(q, cache, ptab, r_k, cur, *, theta: float,
                        window: int | None, scale: float,
                        interpret: bool | None = None,
                        self_entry: dict | None = None,
                        k_norm: jax.Array | None = None,
                        norm_eps: float = 1e-6, mesh=None):
    """Paged-pool latent decode: ``cache`` holds page-major {"zk","zv",
    "pos"} pools (n_pages, page_size, ...) and ``ptab`` (B, n_slot_pages)
    maps this batch's slot pages.  The kernel gathers latent pages via
    scalar prefetch; the self entry rides as one extra trailing tile (the
    deferred-write analogue of ``_extend_ring``).  With ``mesh`` (and >1
    "model" shard dividing page_size), each shard runs on its slice of
    every page's rows — the page table stays global — with the same LSE
    merge as the ring path.  Returns (B, H, r_v)."""
    G = cache["zk"].shape[2]
    qg = group_queries(q, G)
    itp = _resolve_interpret(interpret)
    if _seq_shardable(mesh, cache["pos"].shape[1]):
        def body(pool_l, slot_l, repl_l, self_on):
            qg_l, ptab_l, cur_l, entry_l = slot_l
            r_k_l, kn_l = repl_l
            return _latent_paged_core(
                qg_l, pool_l, ptab_l, r_k_l, cur_l, entry_l, theta=theta,
                window=window, scale=scale, interpret=itp, k_norm=kn_l,
                norm_eps=norm_eps, self_on=self_on, with_lse=True)
        o = _shard_kernel_call(mesh, q.shape[0], cache,
                               lambda x, b: R.kernel_pool_spec(x),
                               (qg, ptab, cur, self_entry),
                               (r_k, k_norm), body)
    else:
        o = _latent_paged_core(qg, cache, ptab, r_k, cur, self_entry,
                               theta=theta, window=window, scale=scale,
                               interpret=itp, k_norm=k_norm,
                               norm_eps=norm_eps)
    return ungroup_outputs(o)


def dense_decode_paged(q, cache, ptab, cur, *, window: int | None,
                       scale: float, interpret: bool | None = None,
                       self_entry: dict | None = None, mesh=None):
    """Paged dense decode through the paged latent kernel — the same
    degenerate-latent trick as ``dense_decode`` (identity reconstruction,
    cos=1/sin=0 since keys are stored post-RoPE), over page-major
    {"k","v","pos"} pools."""
    Hkv = cache["k"].shape[2]
    qg = group_queries(q, Hkv)
    itp = _resolve_interpret(interpret)
    if _seq_shardable(mesh, cache["pos"].shape[1]):
        def body(pool_l, slot_l, repl_l, self_on):
            qg_l, ptab_l, cur_l, entry_l = slot_l
            return _dense_paged_core(
                qg_l, pool_l, ptab_l, cur_l, entry_l, window=window,
                scale=scale, interpret=itp, self_on=self_on, with_lse=True)
        o = _shard_kernel_call(mesh, q.shape[0], cache,
                               lambda x, b: R.kernel_pool_spec(x),
                               (qg, ptab, cur, self_entry), (), body)
    else:
        o = _dense_paged_core(qg, cache, ptab, cur, self_entry,
                              window=window, scale=scale, interpret=itp)
    return ungroup_outputs(o)


def latent_decode_mq_paged(q, cache, ptab, r_k, cur, feed_mask,
                           self_entries, *, theta: float,
                           window: int | None, scale: float,
                           interpret: bool | None = None,
                           k_norm: jax.Array | None = None,
                           norm_eps: float = 1e-6, mesh=None):
    """Multi-query (verify-step) latent decode over a paged pool — the
    paged counterpart of :func:`latent_decode_mq`: the nq deferred
    verify-window latents ride as ceil(nq/page_size) trailing self tiles.
    Returns (B, nq, H, r_v)."""
    B, nq = feed_mask.shape
    G = cache["zk"].shape[2]
    pos_q = cur[:, None] + jnp.arange(nq, dtype=cur.dtype)
    qg = group_queries_mq(q, G)
    itp = _resolve_interpret(interpret)
    if _seq_shardable(mesh, cache["pos"].shape[1]):
        def body(pool_l, slot_l, repl_l, self_on):
            qg_l, ptab_l, pos_q_l, feed_l, entries_l = slot_l
            r_k_l, kn_l = repl_l
            return _latent_paged_core_mq(
                qg_l, pool_l, ptab_l, r_k_l, pos_q_l, feed_l, entries_l,
                theta=theta, window=window, scale=scale, interpret=itp,
                k_norm=kn_l, norm_eps=norm_eps, self_on=self_on,
                with_lse=True)
        o = _shard_kernel_call(mesh, B, cache,
                               lambda x, b: R.kernel_pool_spec(x),
                               (qg, ptab, pos_q, feed_mask, self_entries),
                               (r_k, k_norm), body)
    else:
        o = _latent_paged_core_mq(qg, cache, ptab, r_k, pos_q, feed_mask,
                                  self_entries, theta=theta, window=window,
                                  scale=scale, interpret=itp, k_norm=k_norm,
                                  norm_eps=norm_eps)
    return ungroup_outputs_mq(o, nq)


def dense_decode_mq_paged(q, cache, ptab, cur, feed_mask, self_entries, *,
                          window: int | None, scale: float,
                          interpret: bool | None = None, mesh=None):
    """Multi-query dense verify decode over page-major {"k","v","pos"}
    pools.  Returns (B, nq, H, dh)."""
    B, nq = feed_mask.shape
    Hkv = cache["k"].shape[2]
    pos_q = cur[:, None] + jnp.arange(nq, dtype=cur.dtype)
    qg = group_queries_mq(q, Hkv)
    itp = _resolve_interpret(interpret)
    if _seq_shardable(mesh, cache["pos"].shape[1]):
        def body(pool_l, slot_l, repl_l, self_on):
            qg_l, ptab_l, pos_q_l, feed_l, entries_l = slot_l
            return _dense_paged_core_mq(
                qg_l, pool_l, ptab_l, pos_q_l, feed_l, entries_l,
                window=window, scale=scale, interpret=itp,
                self_on=self_on, with_lse=True)
        o = _shard_kernel_call(mesh, B, cache,
                               lambda x, b: R.kernel_pool_spec(x),
                               (qg, ptab, pos_q, feed_mask, self_entries),
                               (), body)
    else:
        o = _dense_paged_core_mq(qg, cache, ptab, pos_q, feed_mask,
                                 self_entries, window=window, scale=scale,
                                 interpret=itp)
    return ungroup_outputs_mq(o, nq)


def flash_prefill(q, k, v, *, causal: bool = True, window: int | None = None,
                  scale: float | None = None, block: int = 256,
                  interpret: bool | None = None, mesh=None):
    """Full-sequence flash attention for prefill/training forward paths.

    q: (B, T, H, dh); k: (B, T, Hkv, dh); v: (B, T, Hv, dv) — Hv may be the
    latent group count G.  Arbitrary T (tail tiles padded internally).
    On a multi-device ``mesh`` the kernel runs under a full-manual
    shard_map (Mosaic cannot be partitioned by pjit): rows split over the
    batch axes, heads and sequence whole on every "model" shard."""
    def call(q, k, v):
        return flash_prefill_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            block_q=block, block_k=block,
            interpret=_resolve_interpret(interpret))

    if mesh is None or mesh.size == 1:
        return call(q, k, v)
    spec = P(R.kernel_batch_axes(mesh, q.shape[0]), None, None, None)
    return jax.shard_map(call, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)


def expert_gmm(lhs, rhs, group_sizes, *, layer=None,
               interpret: bool | None = None):
    """Grouped matmul over held experts (see kernels/expert_gmm.py): lhs
    (m, K) rows sorted by expert, group_sizes (G,) int32, and rhs one
    layer's (G, K, N) or a layer stack's (L, G, K, N) read in place at the
    traced int32 ``layer``.  Runs on one device; an expert-parallel mesh
    would exchange rows first, which this layer does not do."""
    return _expert_gmm(lhs, rhs, group_sizes, layer=layer,
                       interpret=_resolve_interpret(interpret))


__all__ = [
    "expert_gmm",
    "decode_bias", "verify_bias", "rope_tables_for",
    "group_queries", "ungroup_outputs",
    "group_queries_mq", "ungroup_outputs_mq",
    "default_interpret", "latent_decode", "dense_decode", "flash_prefill",
    "latent_decode_paged", "dense_decode_paged",
    "latent_decode_mq", "dense_decode_mq",
    "latent_decode_mq_paged", "dense_decode_mq_paged",
    "latent_decode_attention", "latent_decode_attention_quant",
    "latent_decode_attention_paged", "latent_decode_attention_mq",
    "latent_decode_attention_mq_quant", "latent_decode_attention_mq_paged",
    "flash_prefill_attention",
]
