"""Layer primitives for the model zoo.

Pure functions over explicit parameter dicts (row-vector convention,
``y = x @ W``).  Everything is jit/scan/pjit-friendly: no Python state,
shapes static, f32 for softmax/norm/recurrent accumulators, model dtype
(bf16) for weights and matmul operands.

Attention comes in two data paths:
  * dense     — standard KV (the paper's uncompressed baseline)
  * latent    — ReCalKV: key latents reconstructed (grouped R_k) before
                RoPE; value latents consumed directly via the fused W~_o.
Cross-attention latents use *key absorption* (no RoPE on cross keys, so
``q' = q @ R_k^T`` folds reconstruction into the query — beyond-paper,
see DESIGN.md §2).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models.config import ModelConfig

Params = dict[str, Any]

# ---------------------------------------------------------------------------
# Norms & RoPE
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def rope_tables(positions: jax.Array, dim: int, theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for GPT-NeoX-style rotation.  positions: any shape."""
    half = dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq  # (..., half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., H, dh) with cos/sin (..., dh/2) broadcast over the H axis."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :].astype(jnp.float32)
    s = sin[..., None, :].astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * c - x2f * s, x2f * c + x1f * s], axis=-1
    ).astype(x.dtype)


def maybe_head_norm(x: jax.Array, scale: jax.Array | None, eps: float) -> jax.Array:
    """Per-head RMSNorm (qk-norm).  x: (..., H, dh), scale: (dh,)."""
    if scale is None:
        return x
    return rmsnorm(x, scale, eps)


# ---------------------------------------------------------------------------
# Masked softmax-attention core (query-chunked, O(chunk * S) memory)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def shard_hint(x: jax.Array, roles: tuple[str | None, ...]) -> jax.Array:
    """Best-effort sharding constraint by logical role.

    roles: per-dim "batch" / "seq" / None.  Resolves against the ambient
    mesh (try (pod, data) then data for batch; "model" for seq); outside
    any mesh the constraint raises and we no-op — tests and single-device
    runs are unaffected."""
    for batch_axes in (("pod", "data"), "data"):
        spec = tuple(
            batch_axes if r == "batch" else ("model" if r == "seq" else None)
            for r in roles)
        try:
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.PartitionSpec(*spec))
        except (RuntimeError, ValueError, KeyError, TypeError):
            continue
    return x


def _attend(
    q: jax.Array,            # (B, Tq, Hq, dh)
    k: jax.Array,            # (B, S, Hkv, dh)
    v: jax.Array,            # (B, S, Hkv, dv)
    mask: jax.Array | None,  # broadcastable to (B, Hq, Tq, S) or None
    scale: float,
) -> jax.Array:
    """Plain masked attention for one query chunk.  Returns (B, Tq, Hq, dv)."""
    B, Tq, Hq, dh = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qr = q.reshape(B, Tq, Hkv, g, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qr, k).astype(jnp.float32) * scale
    if mask is not None:
        m = jnp.broadcast_to(mask, (B, Hq, Tq, k.shape[1])).reshape(
            B, Hkv, g, Tq, k.shape[1]
        )
        logits = jnp.where(m, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskd->bqkgd", w, v)
    return o.reshape(B, Tq, Hq, v.shape[-1])


def _attend_latent_v(
    q: jax.Array,            # (B, Tq, Hq, dh)
    k: jax.Array,            # (B, S, Hkv, dh)   (reconstructed keys)
    zv: jax.Array,           # (B, S, G, r_v)    value latents
    mask: jax.Array | None,
    scale: float,
    group_size: int,
) -> jax.Array:
    """Attention that keeps values in latent space: out (B, Tq, Hq, r_v)."""
    B, Tq, Hq, dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    G = zv.shape[2]
    s = group_size
    qr = q.reshape(B, Tq, Hkv, g, dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qr, k).astype(jnp.float32) * scale
    if mask is not None:
        m = jnp.broadcast_to(mask, (B, Hq, Tq, S)).reshape(B, Hkv, g, Tq, S)
        logits = jnp.where(m, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(zv.dtype)
    # kv-head (G, s) reads value-group G: fold kv axis -> (G, s*g) query heads
    wg = w.reshape(B, G, s * g, Tq, S)
    o = jnp.einsum("bGhqs,bsGr->bqGhr", wg, zv)
    return o.reshape(B, Tq, Hq, zv.shape[-1])


def causal_mask(q_pos: jax.Array, k_pos: jax.Array, window: int | None) -> jax.Array:
    """(..., Tq, S) boolean mask from absolute positions (−1 = invalid slot)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    m = (kp >= 0) & (kp <= qp)
    if window is not None:
        m &= kp > qp - window
    return m


def chunked_attention(q, k, v, q_pos, k_pos, *, window, scale, chunk,
                      latent_v=False, group_size=1, causal=True):
    """Query-chunked attention; bounds live memory to (B, chunk, S) logits.

    q_pos: (B, Tq) absolute positions; k_pos: (B, S) (−1 marks empty slots).
    """
    B, T = q.shape[0], q.shape[1]
    attend = (
        partial(_attend_latent_v, group_size=group_size) if latent_v else _attend
    )

    def one(qc, qpc):
        if causal:
            m = causal_mask(qpc, k_pos, window)[:, None, :, :]
        else:
            m = (k_pos >= 0)[:, None, None, :]
        return attend(qc, k, v, m, scale)

    if T <= chunk:
        return one(q, q_pos)
    n = T // chunk
    if T % chunk:
        # Fall back to a single pass for ragged tails (rare: tests only).
        return one(q, q_pos)
    qs = q.reshape(B, n, chunk, *q.shape[2:]).swapaxes(0, 1)
    ps = q_pos.reshape(B, n, chunk).swapaxes(0, 1)
    out = jax.lax.map(lambda ab: one(*ab), (qs, ps))
    return out.swapaxes(0, 1).reshape(B, T, *out.shape[3:])


# ---------------------------------------------------------------------------
# Dense & latent self-attention (full-sequence: training / prefill)
# ---------------------------------------------------------------------------

def reconstruct_keys(zk: jax.Array, r_k: jax.Array, num_kv_heads: int,
                     d_head: int) -> jax.Array:
    """(B, S, G, r_k) x (G, r_k, s*dh) -> (B, S, Hkv, dh)."""
    B, S, _, _ = zk.shape
    k = jnp.einsum("bsgr,grn->bsgn", zk, r_k)              # (B, S, G, s*dh)
    return k.reshape(B, S, num_kv_heads, d_head)


def self_attention_dense(p: Params, x: jax.Array, cfg: ModelConfig,
                         positions: jax.Array, window: int | None,
                         theta: float | None = None, causal: bool = True,
                         use_kernel: bool = True, mesh=None):
    """Returns (y, (k_roped, v)) — the tuple feeds prefill cache writes.

    ``use_kernel=False`` forces the einsum path even under
    ``attn_backend="pallas"`` — the training forward needs it (the Pallas
    kernels carry no autodiff rule).  ``mesh`` as in ``kops.flash_prefill``."""
    B, T, _ = x.shape
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.d_head
    q = (x @ p["wq"]).reshape(B, T, H, dh)
    k = (x @ p["wk"]).reshape(B, T, Hkv, dh)
    v = (x @ p["wv"]).reshape(B, T, Hkv, dh)
    q = maybe_head_norm(q, p.get("q_norm"), cfg.norm_eps)
    k = maybe_head_norm(k, p.get("k_norm"), cfg.norm_eps)
    cos, sin = rope_tables(positions, dh, theta or cfg.rope_theta)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if cfg.attn_seq_shard:
        # Sequence-parallel keys (§Perf iteration 6): head counts that
        # don't divide the model axis would otherwise run attention fully
        # replicated; sharding the key/value sequence axis keeps scores,
        # softmax reductions, and AV contractions distributed.
        k = shard_hint(k, ("batch", "seq", None, None))
        v = shard_hint(v, ("batch", "seq", None, None))
    if use_kernel and cfg.attn_backend == "pallas":
        # Prefill positions are always 0..T-1, which is exactly the flash
        # kernel's block-position mask.
        o = kops.flash_prefill(q, k, v, causal=causal, window=window,
                               scale=dh ** -0.5, block=cfg.attn_block,
                               mesh=mesh)
    else:
        o = chunked_attention(q, k, v, positions, positions, window=window,
                              scale=dh ** -0.5, chunk=cfg.attn_chunk,
                              causal=causal)
    return o.reshape(B, T, H * dh) @ p["wo"], (k, v)


def self_attention_latent(p: Params, x: jax.Array, cfg: ModelConfig,
                          positions: jax.Array, window: int | None,
                          theta: float | None = None,
                          use_kernel: bool = True, mesh=None):
    """Full-sequence ReCalKV attention.  Returns (y, (zk, zv)) — the latents
    are exactly what prefill writes into the ring cache (pre-RoPE).
    ``use_kernel`` and ``mesh`` as in :func:`self_attention_dense`."""
    B, T, _ = x.shape
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.d_head
    rt = cfg.recalkv
    s = max(1, min(rt.group_size, Hkv))
    q = (x @ p["wq"]).reshape(B, T, H, dh)
    zk = jnp.einsum("btd,gdr->btgr", x, p["l_k"])        # (B, T, G, r_k)
    zv = jnp.einsum("btd,gdr->btgr", x, p["l_v"])        # (B, T, G, r_v)
    k = jnp.einsum("btgr,grn->btgn", zk, p["r_k"]).reshape(B, T, Hkv, dh)
    q = maybe_head_norm(q, p.get("q_norm"), cfg.norm_eps)
    k = maybe_head_norm(k, p.get("k_norm"), cfg.norm_eps)
    cos, sin = rope_tables(positions, dh, theta or cfg.rope_theta)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    if cfg.attn_seq_shard:
        k = shard_hint(k, ("batch", "seq", None, None))
        zv = shard_hint(zv, ("batch", "seq", None, None))
    if use_kernel and cfg.attn_backend == "pallas":
        # The flash kernel consumes latent values directly: one value
        # group per s kv heads (v head index = h // (s*g)), producing
        # (B, T, H, r_v) outputs for the fused W~_o — K is reconstructed
        # once here but never cached.
        o_lat = kops.flash_prefill(q, k, zv, causal=True, window=window,
                                   scale=dh ** -0.5, block=cfg.attn_block,
                                   mesh=mesh)
    else:
        o_lat = chunked_attention(q, k, zv, positions, positions,
                                  window=window, scale=dh ** -0.5,
                                  chunk=cfg.attn_chunk,
                                  latent_v=True, group_size=s)
    return jnp.einsum("bthr,hrd->btd", o_lat, p["wo_fused"]), (zk, zv)


# ---------------------------------------------------------------------------
# Cross-attention (VLM / enc-dec).  No RoPE on cross keys.
# ---------------------------------------------------------------------------

def cross_attention_dense(p: Params, x: jax.Array, source_kv: tuple[jax.Array, jax.Array],
                          cfg: ModelConfig) -> jax.Array:
    B, T, _ = x.shape
    H, dh = cfg.num_heads, cfg.d_head
    k, v = source_kv                                      # (B, S, Hkv, dh)
    q = (x @ p["wq"]).reshape(B, T, H, dh)
    q = maybe_head_norm(q, p.get("q_norm"), cfg.norm_eps)
    o = _attend(q, k, v, None, dh ** -0.5)
    return o.reshape(B, T, H * dh) @ p["wo"]


def cross_attention_latent(p: Params, x: jax.Array,
                           source_latents: tuple[jax.Array, jax.Array],
                           cfg: ModelConfig) -> jax.Array:
    """Latent cross-attention with *key absorption*: scores = (q R_k^T) z_k^T.

    Because cross keys carry no positional rotation, reconstruction commutes
    with the score product and we never materialize K (beyond-paper).
    """
    B, T, _ = x.shape
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.d_head
    zk, zv = source_latents                               # (B, S, G, r)
    G = zk.shape[2]
    s = Hkv // G
    g = H // Hkv
    rank_k, rank_v = zk.shape[-1], zv.shape[-1]
    q = (x @ p["wq"]).reshape(B, T, H, dh)
    q = maybe_head_norm(q, p.get("q_norm"), cfg.norm_eps)
    # Absorb R_k into q:  q'_(h) = q_h @ R_k[g, :, slice(h)]^T  -> (B,T,H,r_k)
    r_k = p["r_k"].reshape(G, rank_k, s, dh)              # (G, r_k, s, dh)
    qg = q.reshape(B, T, G, s * g, dh).reshape(B, T, G, s, g, dh)
    q_abs = jnp.einsum("btGsgd,Grsd->btGsgr", qg, r_k)
    logits = jnp.einsum("btGsgr,bSGr->bGsgtS", q_abs, zk).astype(jnp.float32)
    w = jax.nn.softmax(logits * dh ** -0.5, axis=-1).astype(zv.dtype)
    o_lat = jnp.einsum("bGsgtS,bSGr->btGsgr", w, zv).reshape(B, T, H, rank_v)
    return jnp.einsum("bthr,hrd->btd", o_lat, p["wo_fused"])


def make_cross_source_dense(p: Params, source: jax.Array, cfg: ModelConfig):
    B, S, _ = source.shape
    k = (source @ p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.d_head)
    v = (source @ p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.d_head)
    k = maybe_head_norm(k, p.get("k_norm"), cfg.norm_eps)
    return k, v


def make_cross_source_latent(p: Params, source: jax.Array, cfg: ModelConfig):
    zk = jnp.einsum("bsd,gdr->bsgr", source, p["l_k"])
    zv = jnp.einsum("bsd,gdr->bsgr", source, p["l_v"])
    return zk, zv


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3): trained-from-scratch latent KV — the paper's "built-in"
# alternative; implemented natively (DESIGN.md §Arch-applicability).
# ---------------------------------------------------------------------------

def mla_attention(p: Params, x: jax.Array, cfg: ModelConfig,
                  positions: jax.Array):
    """Full-sequence MLA (training / prefill), non-absorbed form.
    Returns (y, (c_kv, k_rope_post_rope)) for the latent cache."""
    a = cfg.mla
    B, T, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = a.qk_nope_dim, a.qk_rope_dim, a.v_head_dim
    q_lat = rmsnorm(x @ p["wq_a"], p["q_a_norm"], cfg.norm_eps)
    q = (q_lat @ p["wq_b"]).reshape(B, T, H, dn + dr)
    kv_a = x @ p["wkv_a"]                                  # (B,T,r_kv + dr)
    c_kv = rmsnorm(kv_a[..., : a.kv_lora_rank], p["kv_a_norm"], cfg.norm_eps)
    k_rope = kv_a[..., a.kv_lora_rank:].reshape(B, T, 1, dr)
    kv = (c_kv @ p["wkv_b"]).reshape(B, T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    cos, sin = rope_tables(positions, dr, cfg.rope_theta)
    q_pe = apply_rope(q[..., dn:], cos, sin)
    k_pe = jnp.broadcast_to(apply_rope(k_rope, cos, sin), (B, T, H, dr))
    q_full = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k_full = jnp.concatenate([k_nope, k_pe], axis=-1)
    o = chunked_attention(q_full, k_full, v, positions, positions, window=None,
                          scale=(dn + dr) ** -0.5, chunk=cfg.attn_chunk)
    k_pe_cache = apply_rope(k_rope, cos, sin)[:, :, 0, :]   # (B, T, dr) shared
    return o.reshape(B, T, H * dv) @ p["wo"], (c_kv, k_pe_cache)


# ---------------------------------------------------------------------------
# FFN: dense SwiGLU & dropless held-expert MoE
# ---------------------------------------------------------------------------

# What a layer adds besides its output: the training loss term, and under
# cfg.moe the decode counters of its routing (int32: token-expert pairs
# routed over all experts, pairs whose expert is held here, and non-empty
# held groups), summed over the layers like the loss.
MOE_COUNTERS = ("moe_decode_rows_routed", "moe_decode_rows",
                "moe_decode_groups")


def zero_aux(cfg: ModelConfig) -> Params:
    aux = {"loss": jnp.float32(0.0)}
    if cfg.moe is not None:
        aux["moe"] = jnp.zeros((len(MOE_COUNTERS),), jnp.int32)
    return aux


def add_aux(a: Params, b: Params) -> Params:
    return jax.tree.map(jnp.add, a, b)


def swiglu(p: Params, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])) @ p["wo"]


def _grouped(rows, w, sizes, use_kernel: bool, layer=None):
    """rows (m, K) sorted by held expert @ w (E_held, K, N) per group, or
    @ w[layer] of a stacked (L, E_held, K, N)."""
    if use_kernel:
        return kops.expert_gmm(rows, w, sizes, layer=layer)
    if layer is not None:
        w = w[layer]
    return jax.lax.ragged_dot(rows, w, sizes,
                              preferred_element_type=jnp.float32
                              ).astype(rows.dtype)


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig,
            valid: jax.Array | None = None,
            use_kernel: bool = False,
            layer: jax.Array | None = None) -> tuple[jax.Array, Params]:
    """Dropless top-k MoE over the experts this device holds.  Returns
    (out, aux).

    Every real token is routed over all ``num_experts`` (float32 softmax,
    top-k, renormalised to sum to 1); the (token, held expert)
    pairs are computed exactly, with no capacity, so a token's output never
    depends on its batch-mates.  The absent experts' part is left out: the
    result is this device's share of the layer.  ``valid`` (B, T) bool
    marks real rows; pad rows and idle slots are routed to no expert.
    ``use_kernel`` runs the grouped products in the Pallas kernel, else in
    ``jax.lax.ragged_dot``.  With ``layer`` (traced int32), ``wg``/``wi``/
    ``wo`` are a layer scan's whole stacks and layer ``layer`` is used."""
    m = cfg.moe
    B, T, d = x.shape
    N = B * T
    E, k = m.num_experts, m.top_k
    n_held = m.held_count

    xt = x.reshape(N, d)
    logits = jnp.matmul(xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)     # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, sel = jax.lax.top_k(probs, k)                             # (N, k)
    w = w / w.sum(axis=-1, keepdims=True)

    # Aux losses: load-balance + router z-loss.
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[sel.reshape(-1)].add(1.0) / (N * k)
    loss = E * jnp.sum(me * ce) + m.router_zloss * jnp.mean(
        jax.nn.logsumexp(logits, axis=-1) ** 2
    )

    live = (jnp.ones((N,), bool) if valid is None
            else valid.reshape(N).astype(bool))
    local = sel - m.first_held
    held = live[:, None] & (local >= 0) & (local < n_held)       # (N, k)
    group = jnp.where(held, local, n_held).reshape(-1)           # (N*k,)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[group].add(1)[:n_held]

    rows = jnp.take(xt, order // k, axis=0)                      # (N*k, d)
    h = _grouped(rows, p["wg"], sizes, use_kernel, layer)
    u = _grouped(rows, p["wi"], sizes, use_kernel, layer)
    hu = (jax.nn.silu(h.astype(jnp.float32))
          * u.astype(jnp.float32)).astype(x.dtype)
    y = _grouped(hu, p["wo"], sizes, use_kernel, layer)          # (N*k, d)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(N * k, dtype=order.dtype))
    y = jnp.take(y, back, axis=0).reshape(N, k, d).astype(jnp.float32)
    out = jnp.sum(jnp.where(held[..., None], y * w[..., None], 0.0), axis=1)
    out = out.astype(x.dtype)
    if m.num_shared:
        out = out + swiglu(p["shared"], xt)

    counts = jnp.stack([live.sum() * k, held.sum(),
                        (sizes > 0).sum()]).astype(jnp.int32)
    return out.reshape(B, T, d), {"loss": loss, "moe": counts}


def ffn(p: Params, x: jax.Array, cfg: ModelConfig, dense: bool,
        valid: jax.Array | None = None,
        use_kernel: bool = False,
        layer: jax.Array | None = None) -> tuple[jax.Array, Params]:
    """The block's FFN: dense SwiGLU (``valid`` and ``layer`` ignored) or,
    under cfg.moe and not ``dense``, :func:`moe_ffn`.  Returns (out, aux)
    with aux as :func:`zero_aux` builds it."""
    if cfg.moe is not None and not dense:
        return moe_ffn(p, x, cfg, valid,
                       use_kernel=use_kernel and cfg.attn_backend == "pallas",
                       layer=layer)
    return swiglu(p, x), zero_aux(cfg)


# ---------------------------------------------------------------------------
# Mamba-1 (falcon-mamba): chunked selective scan
# ---------------------------------------------------------------------------

def _causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array,
                   state: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv.  x: (B, T, C), w: (K, C).  Returns (y, new_state)
    where state carries the last K-1 inputs for streaming decode."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)               # (B, T+K-1, C)
    y = sum(xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(K))
    return y + b[None, None, :], xp[:, -(K - 1):, :]


def _ssm_chunk_scan(decay: jax.Array, drive: jax.Array, h0: jax.Array):
    """Associative scan of h_t = decay_t * h_{t-1} + drive_t within a chunk.

    decay/drive: (B, Tc, d, n) f32;  h0: (B, d, n).  Returns (h_all, h_last).
    """
    def comb(a, b):
        return (a[0] * b[0], a[1] * b[0] + b[1])
    # prepend h0 as a pseudo-step with decay 1
    a = jnp.concatenate([jnp.ones_like(decay[:, :1]), decay], axis=1)
    b = jnp.concatenate([h0[:, None], drive], axis=1)
    aa, bb = jax.lax.associative_scan(comb, (a, b), axis=1)
    return bb[:, 1:], bb[:, -1]


def mamba_mixer(p: Params, x: jax.Array, cfg: ModelConfig,
                state: Params | None = None, chunk: int = 128):
    """Mamba-1 block.  Returns (y, new_state).  state carries (h, conv)."""
    mc = cfg.mamba
    B, T, d = x.shape
    di, ds = cfg.mamba_d_inner, mc.d_state
    xz = x @ p["in_proj"]
    xin, z = xz[..., :di], xz[..., di:]
    conv_state = None if state is None else state["conv"]
    xc, new_conv = _causal_conv1d(xin, p["conv_w"], p["conv_b"], conv_state)
    xc = jax.nn.silu(xc)
    dbc = xc @ p["x_proj"]
    dtr = cfg.mamba_dt_rank
    dt = jax.nn.softplus(dbc[..., :dtr] @ p["dt_proj"] + p["dt_bias"]).astype(jnp.float32)
    Bmat = dbc[..., dtr : dtr + ds].astype(jnp.float32)    # (B,T,ds)
    Cmat = dbc[..., dtr + ds :].astype(jnp.float32)        # (B,T,ds)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))           # (di, ds)

    h0 = (jnp.zeros((B, di, ds), jnp.float32) if state is None
          else state["h"].astype(jnp.float32))

    def step_chunk(h, args):
        xc_c, dt_c, B_c, C_c = args                        # (B, Tc, ...)
        decay = jnp.exp(dt_c[..., None] * A[None, None])   # (B,Tc,di,ds)
        drive = (dt_c * xc_c.astype(jnp.float32))[..., None] * B_c[:, :, None, :]
        h_all, h_new = _ssm_chunk_scan(decay, drive, h)
        y_c = jnp.einsum("btdn,btn->btd", h_all, C_c)
        return h_new, y_c

    if T == 1:
        hT, y = step_chunk(h0, (xc, dt, Bmat, Cmat))
    elif T % chunk == 0 and T > chunk:
        n = T // chunk
        rs = lambda a: a.reshape(B, n, chunk, *a.shape[2:]).swapaxes(0, 1)
        hT, ys = jax.lax.scan(step_chunk, h0, (rs(xc), rs(dt), rs(Bmat), rs(Cmat)))
        y = ys.swapaxes(0, 1).reshape(B, T, di)
    else:
        hT, y = step_chunk(h0, (xc, dt, Bmat, Cmat))

    y = y.astype(x.dtype) + xc * p["D"].astype(x.dtype)[None, None, :]
    out = (y * jax.nn.silu(z)) @ p["out_proj"]
    return out.astype(x.dtype), {"h": hT, "conv": new_conv}


# ---------------------------------------------------------------------------
# RG-LRU (RecurrentGemma / Griffin)
# ---------------------------------------------------------------------------

_RGLRU_C = 8.0


def rglru_mixer(p: Params, x: jax.Array, cfg: ModelConfig,
                state: Params | None = None):
    """Gated linear recurrent unit block.  Returns (y, new_state)."""
    B, T, d = x.shape
    W = cfg.lru_width
    main = x @ p["in_main"]                                # (B,T,W)
    gate = jax.nn.gelu(x @ p["in_gate"])
    conv_state = None if state is None else state["conv"]
    main, new_conv = _causal_conv1d(main, p["conv_w"], p["conv_b"], conv_state)

    rg = jax.nn.sigmoid(main @ p["w_a"]).astype(jnp.float32)   # recurrence gate
    ig = jax.nn.sigmoid(main @ p["w_x"]).astype(jnp.float32)   # input gate
    log_a = -_RGLRU_C * jax.nn.softplus(p["a_param"].astype(jnp.float32)) * rg
    a = jnp.exp(log_a)                                         # (B,T,W)
    gated = ig * main.astype(jnp.float32)
    drive = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9)) * gated

    h0 = (jnp.zeros((B, W), jnp.float32) if state is None
          else state["h"].astype(jnp.float32))
    if T == 1:
        h = a[:, 0] * h0 + drive[:, 0]
        hs = h[:, None]
        hT = h
    else:
        def comb(u, v):
            return (u[0] * v[0], u[1] * v[0] + v[1])
        a_ext = jnp.concatenate([jnp.ones_like(a[:, :1]), a], axis=1)
        d_ext = jnp.concatenate([h0[:, None], drive], axis=1)
        _, hh = jax.lax.associative_scan(comb, (a_ext, d_ext), axis=1)
        hs, hT = hh[:, 1:], hh[:, -1]
    y = (hs.astype(x.dtype) * gate) @ p["out_proj"]
    return y, {"h": hT, "conv": new_conv}
