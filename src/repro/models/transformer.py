"""Transformer stack assembly: init / train-forward / prefill / decode.

Layer layout (cfg.scan_layers=True):
    params = {
      "embed": (V, d), ["lm_head": (d, V)], "final_norm": (d,),
      "prefix": tuple(block-dicts),             # unrolled leading layers
      "blocks": tuple over period positions,    # leaves stacked (n_periods, ...)
      "suffix": tuple(block-dicts),             # unrolled remainder
      ["encoder"]: {"blocks": tuple(block-dicts), "final_norm": (d,)},
    }
The scan body lowers each pattern period once — HLO stays O(period) even for
94-layer models, which is what makes the 40-cell dry-run tractable.

With cfg.scan_layers=False every layer sits in "prefix" (heterogeneous
per-layer ranks from Fisher allocation become possible; used by the
small-scale quality benchmarks).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import kv_cache as KC
from repro.models import layers as L
from repro.models.config import ModelConfig

Params = dict[str, Any]

# Self-attention block kinds whose FFN is the held-expert MoE under
# cfg.moe (the others' is dense), and that FFN's per-expert weight leaves.
MOE_FFN_KINDS = ("attn", "local")
EXPERT_LEAVES = ("wg", "wi", "wo")


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _dense_init(key, shape, scale, dtype, stack=None):
    if stack is not None:
        shape = (stack,) + tuple(shape)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _zeros(shape, dtype, stack=None):
    if stack is not None:
        shape = (stack,) + tuple(shape)
    return jnp.zeros(shape, dtype)


def init_attn_params(cfg: ModelConfig, key, *, cross: bool = False,
                     stack=None) -> Params:
    d, dt = cfg.d_model, cfg.dtype
    ks = jax.random.split(key, 8)
    sc = d ** -0.5
    if cfg.mla is not None and not cross:
        a = cfg.mla
        H = cfg.num_heads
        return {
            "wq_a": _dense_init(ks[0], (d, a.q_lora_rank), sc, dt, stack),
            "q_a_norm": _zeros((a.q_lora_rank,), jnp.float32, stack),
            "wq_b": _dense_init(ks[1], (a.q_lora_rank, H * (a.qk_nope_dim + a.qk_rope_dim)),
                                a.q_lora_rank ** -0.5, dt, stack),
            "wkv_a": _dense_init(ks[2], (d, a.kv_lora_rank + a.qk_rope_dim), sc, dt, stack),
            "kv_a_norm": _zeros((a.kv_lora_rank,), jnp.float32, stack),
            "wkv_b": _dense_init(ks[3], (a.kv_lora_rank, H * (a.qk_nope_dim + a.v_head_dim)),
                                 a.kv_lora_rank ** -0.5, dt, stack),
            "wo": _dense_init(ks[4], (H * a.v_head_dim, d),
                              (H * a.v_head_dim) ** -0.5, dt, stack),
        }
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.d_head
    p: Params = {"wq": _dense_init(ks[0], (d, H * dh), sc, dt, stack)}
    if cfg.recalkv is not None:
        rt = cfg.recalkv
        s = max(1, min(rt.group_size, Hkv))
        G = Hkv // s
        p |= {
            "l_k": _dense_init(ks[1], (G, d, rt.rank_k), sc, dt, stack),
            "r_k": _dense_init(ks[2], (G, rt.rank_k, s * dh), rt.rank_k ** -0.5, dt, stack),
            "l_v": _dense_init(ks[3], (G, d, rt.rank_v), sc, dt, stack),
            "wo_fused": _dense_init(ks[4], (H, rt.rank_v, d), (H * rt.rank_v) ** -0.5,
                                    dt, stack),
        }
    else:
        p |= {
            "wk": _dense_init(ks[1], (d, Hkv * dh), sc, dt, stack),
            "wv": _dense_init(ks[2], (d, Hkv * dh), sc, dt, stack),
            "wo": _dense_init(ks[4], (H * dh, d), (H * dh) ** -0.5, dt, stack),
        }
    if cfg.qk_norm:
        p["q_norm"] = _zeros((dh,), jnp.float32, stack)
        p["k_norm"] = _zeros((dh,), jnp.float32, stack)
    return p


def init_ffn_params(cfg: ModelConfig, key, *, dense: bool, stack=None) -> Params:
    d, dt = cfg.d_model, cfg.dtype
    ks = jax.random.split(key, 7)
    if cfg.moe is None or dense:
        f = cfg.d_ff
        return {
            "wi": _dense_init(ks[0], (d, f), d ** -0.5, dt, stack),
            "wg": _dense_init(ks[1], (d, f), d ** -0.5, dt, stack),
            "wo": _dense_init(ks[2], (f, d), f ** -0.5, dt, stack),
        }
    m = cfg.moe
    E, f = m.held_count, m.d_expert
    p = {
        "router": _dense_init(ks[0], (d, m.num_experts), d ** -0.5,
                              jnp.float32, stack),
        "wi": _dense_init(ks[1], (E, d, f), d ** -0.5, dt, stack),
        "wg": _dense_init(ks[2], (E, d, f), d ** -0.5, dt, stack),
        "wo": _dense_init(ks[3], (E, f, d), f ** -0.5, dt, stack),
    }
    if m.num_shared:
        fs = f * m.num_shared
        p["shared"] = {
            "wi": _dense_init(ks[4], (d, fs), d ** -0.5, dt, stack),
            "wg": _dense_init(ks[5], (d, fs), d ** -0.5, dt, stack),
            "wo": _dense_init(ks[6], (fs, d), fs ** -0.5, dt, stack),
        }
    return p


def init_mamba_params(cfg: ModelConfig, key, stack=None) -> Params:
    d, dt = cfg.d_model, cfg.dtype
    mc = cfg.mamba
    di, ds, dtr = cfg.mamba_d_inner, mc.d_state, cfg.mamba_dt_rank
    ks = jax.random.split(key, 6)
    A = jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32), (di, ds))
    p = {
        "in_proj": _dense_init(ks[0], (d, 2 * di), d ** -0.5, dt, stack),
        "conv_w": _dense_init(ks[1], (mc.d_conv, di), mc.d_conv ** -0.5, dt, stack),
        "conv_b": _zeros((di,), dt, stack),
        "x_proj": _dense_init(ks[2], (di, dtr + 2 * ds), di ** -0.5, dt, stack),
        "dt_proj": _dense_init(ks[3], (dtr, di), dtr ** -0.5, dt, stack),
        "dt_bias": _zeros((di,), jnp.float32, stack) - 4.0,
        "A_log": (jnp.log(A) if stack is None
                  else jnp.broadcast_to(jnp.log(A), (stack, di, ds))),
        "D": _zeros((di,), jnp.float32, stack) + 1.0,
        "out_proj": _dense_init(ks[4], (di, d), di ** -0.5, dt, stack),
    }
    return p


def init_rglru_params(cfg: ModelConfig, key, stack=None) -> Params:
    d, dt = cfg.d_model, cfg.dtype
    W = cfg.lru_width
    K = cfg.rglru.conv_width
    ks = jax.random.split(key, 6)
    return {
        "in_main": _dense_init(ks[0], (d, W), d ** -0.5, dt, stack),
        "in_gate": _dense_init(ks[1], (d, W), d ** -0.5, dt, stack),
        "conv_w": _dense_init(ks[2], (K, W), K ** -0.5, dt, stack),
        "conv_b": _zeros((W,), dt, stack),
        "w_a": _dense_init(ks[3], (W, W), W ** -0.5, dt, stack),
        "w_x": _dense_init(ks[4], (W, W), W ** -0.5, dt, stack),
        "a_param": _zeros((W,), jnp.float32, stack) + 0.65,
        "out_proj": _dense_init(ks[5], (W, d), W ** -0.5, dt, stack),
    }


def init_block_params(cfg: ModelConfig, kind: str, key, stack=None) -> Params:
    ks = jax.random.split(key, 4)
    norm = lambda: _zeros((cfg.d_model,), jnp.float32, stack)
    if kind == "mamba":
        return {"ln": norm(), "mixer": init_mamba_params(cfg, ks[0], stack)}
    if kind == "rglru":
        return {"ln1": norm(), "mixer": init_rglru_params(cfg, ks[0], stack),
                "ln2": norm(), "mlp": init_ffn_params(cfg, ks[1], dense=True, stack=stack)}
    if kind == "cross":
        return {"ln1": norm(), "cross": init_attn_params(cfg, ks[0], cross=True, stack=stack),
                "ln2": norm(), "mlp": init_ffn_params(cfg, ks[1], dense=True, stack=stack)}
    if kind == "attn_cross":
        return {"ln1": norm(), "attn": init_attn_params(cfg, ks[0], stack=stack),
                "lnx": norm(), "cross": init_attn_params(cfg, ks[1], cross=True, stack=stack),
                "ln2": norm(), "mlp": init_ffn_params(cfg, ks[2], dense=True, stack=stack)}
    dense = kind not in MOE_FFN_KINDS
    return {"ln1": norm(), "attn": init_attn_params(cfg, ks[0], stack=stack),
            "ln2": norm(), "mlp": init_ffn_params(cfg, ks[1], dense=dense, stack=stack)}


def init_params(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 8)
    d, V = cfg.d_model, cfg.vocab_size
    params: Params = {
        "embed": _dense_init(ks[0], (V, d), 0.02, cfg.dtype),
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(ks[1], (d, V), d ** -0.5, cfg.dtype)

    if cfg.scan_layers:
        prefix, pattern, suffix = (cfg.prefix_pattern, cfg.layer_pattern,
                                   cfg.suffix_pattern)
        n_per = cfg.num_periods
    else:
        prefix, pattern, suffix, n_per = cfg.expanded_layers(), (), (), 0

    params["prefix"] = tuple(
        init_block_params(cfg, k, jax.random.fold_in(ks[2], i))
        for i, k in enumerate(prefix)
    )
    params["blocks"] = tuple(
        init_block_params(cfg, k, jax.random.fold_in(ks[3], i), stack=n_per)
        for i, k in enumerate(pattern)
    ) if n_per > 0 else ()
    params["suffix"] = tuple(
        init_block_params(cfg, k, jax.random.fold_in(ks[4], i))
        for i, k in enumerate(suffix)
    )
    if cfg.encoder_decoder:
        params["encoder"] = {
            "blocks": tuple(
                init_block_params(cfg, "attn_dense", jax.random.fold_in(ks[5], i))
                for i in range(cfg.num_encoder_layers)
            ),
            "final_norm": jnp.zeros((d,), jnp.float32),
        }
    return params


# ---------------------------------------------------------------------------
# Block application — full sequence (train / prefill)
# ---------------------------------------------------------------------------

def _theta(cfg: ModelConfig, kind: str) -> float:
    if kind in ("attn", "attn_dense") and getattr(cfg, "rope_theta_global", None):
        return cfg.rope_theta_global
    return cfg.rope_theta


def block_full(cfg: ModelConfig, kind: str, p: Params, x: jax.Array,
               ctx: dict, want_cache: bool):
    """One block over a full (B, T, d) sequence.  Returns (x, cache, aux)."""
    aux = L.zero_aux(cfg)
    cache = None
    pos = ctx["positions"]
    causal = ctx.get("causal", True)
    if kind in ("mamba", "rglru"):
        mixer = L.mamba_mixer if kind == "mamba" else L.rglru_mixer
        ln = p["ln"] if kind == "mamba" else p["ln1"]
        y, state = mixer(p["mixer"], L.rmsnorm(x, ln, cfg.norm_eps), cfg)
        x = x + y
        if kind == "rglru":
            h, a = L.ffn(p["mlp"], L.rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, dense=True)
            x, aux = x + h, L.add_aux(aux, a)
        cache = state if want_cache else None
        return x, cache, aux

    if kind == "cross":
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        if cfg.recalkv is not None:
            src = L.make_cross_source_latent(p["cross"], ctx["source"], cfg)
            y = L.cross_attention_latent(p["cross"], h, src, cfg)
            cache = {"cross": {"zk": src[0], "zv": src[1]}} if want_cache else None
        else:
            src = L.make_cross_source_dense(p["cross"], ctx["source"], cfg)
            y = L.cross_attention_dense(p["cross"], h, src, cfg)
            cache = {"cross": {"k": src[0], "v": src[1]}} if want_cache else None
        x = x + y
        h, a = L.ffn(p["mlp"], L.rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, dense=True)
        return x + h, cache, L.add_aux(aux, a)

    # self-attention kinds.  The pallas backend applies to inference
    # passes only (want_cache=True, i.e. prefill): the kernels have no
    # autodiff rule, so the training forward keeps the einsum path.
    window = cfg.window_for(kind)
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    self_cache = None
    if cfg.mla is not None:
        y, kv = L.mla_attention(p["attn"], h, cfg, pos)
        if want_cache:
            self_cache = _prefill_self_cache(cfg, kind, ctx, {"ckv": kv[0], "krope": kv[1]})
    elif cfg.recalkv is not None:
        y, kv = L.self_attention_latent(p["attn"], h, cfg, pos, window,
                                        theta=_theta(cfg, kind),
                                        use_kernel=want_cache,
                                        mesh=ctx.get("mesh"))
        if want_cache:
            self_cache = _prefill_self_cache(
                cfg, kind, ctx, KC.latent_cache_entry(cfg, kv[0], kv[1]))
    else:
        y, kv = L.self_attention_dense(p["attn"], h, cfg, pos, window,
                                       theta=_theta(cfg, kind), causal=causal,
                                       use_kernel=want_cache,
                                       mesh=ctx.get("mesh"))
        if want_cache:
            self_cache = _prefill_self_cache(cfg, kind, ctx, {"k": kv[0], "v": kv[1]})
    x = x + y

    if kind == "attn_cross":
        hx = L.rmsnorm(x, p["lnx"], cfg.norm_eps)
        if cfg.recalkv is not None:
            src = L.make_cross_source_latent(p["cross"], ctx["source"], cfg)
            y = L.cross_attention_latent(p["cross"], hx, src, cfg)
            cross_cache = {"zk": src[0], "zv": src[1]}
        else:
            src = L.make_cross_source_dense(p["cross"], ctx["source"], cfg)
            y = L.cross_attention_dense(p["cross"], hx, src, cfg)
            cross_cache = {"k": src[0], "v": src[1]}
        x = x + y

    h, a = L.ffn(p["mlp"], L.rmsnorm(x, p["ln2"], cfg.norm_eps), cfg,
                 dense=kind not in MOE_FFN_KINDS,
                 valid=ctx.get("valid"), use_kernel=want_cache,
                 layer=ctx.get("layer"))
    x = x + h
    aux = L.add_aux(aux, a)
    if want_cache:
        cache = {"self": self_cache}
        if kind == "attn_cross":
            cache["cross"] = cross_cache
    return x, cache, aux


def _prefill_self_cache(cfg: ModelConfig, kind: str, ctx: dict,
                        values: Params) -> Params:
    """Scatter full-sequence K/V (or latents) into a fresh ring cache.

    Shapes come from the values themselves, so per-layer (Fisher-allocated)
    ranks need no config plumbing."""
    B, T = ctx["positions"].shape
    Lr = cfg.cache_len(kind, ctx["max_len"])
    out = {}
    for name, val in values.items():
        empty = jnp.zeros((B, Lr) + val.shape[2:], val.dtype)
        out[name] = KC.write_prefill(empty, val, ctx["lengths"])
    out["pos"] = KC.prefill_pos(ctx["lengths"], T, Lr)
    return out


# ---------------------------------------------------------------------------
# Block application — single decode step
# ---------------------------------------------------------------------------

def block_decode(cfg: ModelConfig, kind: str, p: Params, x: jax.Array,
                 cache: Params, ctx: dict):
    """One block for a (B, 1, d) decode step.  Returns (x, updates, aux).

    ``updates`` are DEFERRED cache writes (slot entries / state
    replacements / None) merged once after the layer scan by
    kv_cache.apply_decode_writes — carrying full updated caches through
    the scan ys forced per-iteration rematerialization of the whole ring
    (EXPERIMENTS.md §Perf iteration 3)."""
    aux = L.zero_aux(cfg)
    cur = ctx["cur"]
    if kind in ("mamba", "rglru"):
        mixer = L.mamba_mixer if kind == "mamba" else L.rglru_mixer
        ln = p["ln"] if kind == "mamba" else p["ln1"]
        y, state = mixer(p["mixer"], L.rmsnorm(x, ln, cfg.norm_eps), cfg, state=cache)
        x = x + y
        if kind == "rglru":
            h, a = L.ffn(p["mlp"], L.rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, dense=True)
            x, aux = x + h, L.add_aux(aux, a)
        return x, state, aux

    if kind == "cross":
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        reader = (KC.decode_cross_latent if cfg.recalkv is not None
                  else KC.decode_cross_dense)
        y, _ = reader(p["cross"], h, cache["cross"], cfg)
        x = x + y
        h, a = L.ffn(p["mlp"], L.rmsnorm(x, p["ln2"], cfg.norm_eps), cfg, dense=True)
        return x + h, {"cross": None}, L.add_aux(aux, a)

    window = cfg.window_for(kind)
    pages = ctx.get("pages")
    mesh = ctx.get("mesh")
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        y, sc = KC.decode_attn_mla(p["attn"], h, cache["self"], cfg, cur,
                                   pages=pages)
    elif cfg.recalkv is not None:
        y, sc = KC.decode_attn_latent(p["attn"], h, cache["self"], cfg, cur, window,
                                      theta=_theta(cfg, kind), pages=pages,
                                      mesh=mesh)
    else:
        y, sc = KC.decode_attn_dense(p["attn"], h, cache["self"], cfg, cur, window,
                                     theta=_theta(cfg, kind), pages=pages,
                                     mesh=mesh)
    x = x + y
    updates = {"self": sc}

    if kind == "attn_cross":
        hx = L.rmsnorm(x, p["lnx"], cfg.norm_eps)
        reader = (KC.decode_cross_latent if cfg.recalkv is not None
                  else KC.decode_cross_dense)
        y, _ = reader(p["cross"], hx, cache["cross"], cfg)
        x = x + y
        updates["cross"] = None

    h, a = L.ffn(p["mlp"], L.rmsnorm(x, p["ln2"], cfg.norm_eps), cfg,
                 dense=kind not in MOE_FFN_KINDS,
                 valid=ctx.get("valid"), use_kernel=True,
                 layer=ctx.get("layer"))
    return x + h, updates, L.add_aux(aux, a)


# ---------------------------------------------------------------------------
# Block application — multi-token verify step (speculative decoding)
# ---------------------------------------------------------------------------

VERIFY_KINDS = ("attn", "attn_dense", "local", "cross", "attn_cross")


def block_verify(cfg: ModelConfig, kind: str, p: Params, x: jax.Array,
                 cache: Params, ctx: dict):
    """One block for a (B, S, d) verify step over S fed tokens at
    positions cur..cur+S-1.  Returns (x, updates, aux) with DEFERRED
    (B, S, ...) entry updates — the caller commits only the accepted
    prefix via ``kv_cache.apply_verify_writes``.

    Recurrent blocks (mamba / rglru) are unsupported: their state update
    is not position-addressed, so a rejected token could not be rolled
    back by masking the write."""
    if kind in ("mamba", "rglru"):
        raise NotImplementedError(
            f"speculative verify is unsupported for recurrent "
            f"{kind!r} blocks")
    aux = L.zero_aux(cfg)
    cur, feed_mask = ctx["cur"], ctx["feed_mask"]
    if kind == "cross":
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        reader = (KC.decode_cross_latent if cfg.recalkv is not None
                  else KC.decode_cross_dense)
        y, _ = reader(p["cross"], h, cache["cross"], cfg)
        x = x + y
        h, a = L.ffn(p["mlp"], L.rmsnorm(x, p["ln2"], cfg.norm_eps), cfg,
                     dense=True)
        return x + h, {"cross": None}, L.add_aux(aux, a)

    window = cfg.window_for(kind)
    pages = ctx.get("pages")
    mesh = ctx.get("mesh")
    h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla is not None:
        y, sc = KC.verify_attn_mla(p["attn"], h, cache["self"], cfg, cur,
                                   feed_mask, pages=pages)
    elif cfg.recalkv is not None:
        y, sc = KC.verify_attn_latent(p["attn"], h, cache["self"], cfg, cur,
                                      feed_mask, window,
                                      theta=_theta(cfg, kind), pages=pages,
                                      mesh=mesh)
    else:
        y, sc = KC.verify_attn_dense(p["attn"], h, cache["self"], cfg, cur,
                                     feed_mask, window,
                                     theta=_theta(cfg, kind), pages=pages,
                                     mesh=mesh)
    x = x + y
    updates = {"self": sc}

    if kind == "attn_cross":
        hx = L.rmsnorm(x, p["lnx"], cfg.norm_eps)
        reader = (KC.decode_cross_latent if cfg.recalkv is not None
                  else KC.decode_cross_dense)
        y, _ = reader(p["cross"], hx, cache["cross"], cfg)
        x = x + y
        updates["cross"] = None

    h, a = L.ffn(p["mlp"], L.rmsnorm(x, p["ln2"], cfg.norm_eps), cfg,
                 dense=kind not in MOE_FFN_KINDS,
                 valid=feed_mask, use_kernel=True, layer=ctx.get("layer"))
    return x + h, updates, L.add_aux(aux, a)


# ---------------------------------------------------------------------------
# Stack runner (prefix unrolled -> scanned periods -> suffix unrolled)
# ---------------------------------------------------------------------------

def _layer_layout(cfg: ModelConfig):
    if cfg.scan_layers:
        return cfg.prefix_pattern, cfg.layer_pattern, cfg.suffix_pattern, cfg.num_periods
    return cfg.expanded_layers(), (), (), 0


def run_stack(cfg: ModelConfig, params: Params, x: jax.Array, ctx: dict,
              caches: Params | None, *, decode: bool = False,
              verify: bool = False):
    """Apply the whole stack.  Returns (x, new_caches, aux), aux summed
    over the layers as :func:`layers.zero_aux` builds it."""
    prefix, pattern, suffix, n_per = _layer_layout(cfg)
    if verify:
        apply_fn = block_verify
    elif decode:
        apply_fn = block_decode
    else:
        apply_fn = partial(block_full, want_cache=caches is not None)
    want_cache = caches is not None
    aux = L.zero_aux(cfg)
    new_caches: Params = {"prefix": [], "blocks": None, "suffix": []}

    def run_one(kind, p, x, c, ctx=ctx):
        if decode:
            return apply_fn(cfg, kind, p, x, c, ctx)
        return apply_fn(cfg, kind, p, x, ctx)

    for i, kind in enumerate(prefix):
        c_in = caches["prefix"][i] if (decode and want_cache) else None
        x, c, a = run_one(kind, params["prefix"][i], x, c_in)
        aux = L.add_aux(aux, a)
        new_caches["prefix"].append(c)

    if n_per > 0:
        # In inference passes (decode, verify, prefill with a cache) a
        # scanned MoE block's expert stacks stay out of the scanned xs: the
        # loop closes over them whole and the kernel reads the period's
        # experts in place at ctx["layer"].  Scanned, each would be sliced
        # out into a copy per layer and call.  Training keeps the slices,
        # whose gradients are per layer.
        experts = {}
        if cfg.moe is not None and (decode or want_cache):
            experts = {j: {n: params["blocks"][j]["mlp"][n]
                           for n in EXPERT_LEAVES}
                       for j, kind in enumerate(pattern)
                       if kind in MOE_FFN_KINDS}
        blocks = tuple(
            dict(blk, mlp={n: v for n, v in blk["mlp"].items()
                           if n not in EXPERT_LEAVES})
            if j in experts else blk
            for j, blk in enumerate(params["blocks"]))

        def body(carry, xs):
            x, aux = carry
            period_params, period_caches, layer = xs
            ctx_p = dict(ctx, layer=layer) if experts else ctx
            outs = []
            for j, kind in enumerate(pattern):
                p = period_params[j]
                if j in experts:
                    p = dict(p, mlp=dict(p["mlp"], **experts[j]))
                c_in = period_caches[j] if decode else None
                x, c, a = run_one(kind, p, x, c_in, ctx_p)
                aux = L.add_aux(aux, a)
                outs.append(c)
            return (x, aux), tuple(outs)

        if (not decode) and cfg.remat and not want_cache:
            body = jax.checkpoint(body)
        period_caches = (caches["blocks"] if (decode and want_cache)
                         else tuple(None for _ in pattern))
        xs = (blocks, period_caches,
              jnp.arange(n_per, dtype=jnp.int32) if experts else None)
        (x, aux), scan_caches = jax.lax.scan(body, (x, aux), xs)
        new_caches["blocks"] = scan_caches if want_cache else None

    for i, kind in enumerate(suffix):
        c_in = caches["suffix"][i] if (decode and want_cache) else None
        x, c, a = run_one(kind, params["suffix"][i], x, c_in)
        aux = L.add_aux(aux, a)
        new_caches["suffix"].append(c)

    if want_cache:
        new_caches["prefix"] = tuple(new_caches["prefix"])
        new_caches["suffix"] = tuple(new_caches["suffix"])
        return x, new_caches, aux
    return x, None, aux


# ---------------------------------------------------------------------------
# Top-level model functions
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
    return x


def encode(cfg: ModelConfig, params: Params, frames: jax.Array) -> jax.Array:
    """Encoder for enc-dec models.  frames: (B, S, d) stub embeddings."""
    enc = params["encoder"]
    B, S, _ = frames.shape
    ctx = {"positions": jnp.broadcast_to(jnp.arange(S), (B, S)),
           "causal": False, "lengths": jnp.full((B,), S), "max_len": S}
    x = frames.astype(cfg.dtype)
    for blk in enc["blocks"]:
        x, _, _ = block_full(cfg, "attn_dense", blk, x, ctx, want_cache=False)
    return L.rmsnorm(x, enc["final_norm"], cfg.norm_eps)


def forward_hidden(cfg: ModelConfig, params: Params, tokens: jax.Array,
                   source: jax.Array | None = None):
    """Training forward: tokens (B, T) -> hidden (B, T, d), aux loss
    (a float32 scalar)."""
    B, T = tokens.shape
    if cfg.encoder_decoder and source is not None:
        source = encode(cfg, params, source)
    ctx = {
        "positions": jnp.broadcast_to(jnp.arange(T), (B, T)),
        "lengths": jnp.full((B,), T, jnp.int32),
        "source": source, "max_len": T,
    }
    x = embed_tokens(cfg, params, tokens)
    x, _, aux = run_stack(cfg, params, x, ctx, caches=None)
    return L.rmsnorm(x, params["final_norm"], cfg.norm_eps), aux["loss"]


def _lm_head_weight(cfg: ModelConfig, params: Params) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def logits_for(cfg: ModelConfig, params: Params, hidden: jax.Array) -> jax.Array:
    return (hidden @ _lm_head_weight(cfg, params)).astype(jnp.float32)


def chunked_xent(cfg: ModelConfig, params: Params, hidden: jax.Array,
                 labels: jax.Array, chunk: int = 512):
    """Cross-entropy without materializing (B, T, V) logits at once."""
    B, T, d = hidden.shape
    W = _lm_head_weight(cfg, params)

    def one(h_c, l_c):
        logits = (h_c @ W).astype(jnp.float32)
        mask = (l_c >= 0).astype(jnp.float32)
        safe = jnp.maximum(l_c, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - gold) * mask), jnp.sum(mask)

    if T <= chunk or T % chunk:
        return one(hidden, labels)
    n = T // chunk
    hs = hidden.reshape(B, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)

    def body(acc, xs):
        s, c = one(*xs)
        return (acc[0] + s, acc[1] + c), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (hs, ls))
    return tot, cnt


def loss_fn(cfg: ModelConfig, params: Params, batch: dict):
    """Causal LM loss.  batch: tokens (B,T), labels (B,T) (-1 = pad),
    optional source (B,S,d) frontend embeddings."""
    hidden, aux = forward_hidden(cfg, params, batch["tokens"],
                                 batch.get("source"))
    tot, cnt = chunked_xent(cfg, params, hidden, batch["labels"])
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss + aux, {"xent": loss, "aux": aux, "tokens": cnt}


def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int,
                      pages: tuple[int, int] | None = None) -> Params:
    """Decode cache pool.  With ``pages`` = (n_pages, page_size) every
    block's ring is built page-major — leaves (n_pages, page_size, ...)
    shared across slots through a page table — instead of per-slot
    (batch, max_len, ...) rows.  Callers gate paged mode to full-length
    self-attention stacks (no recurrent/cross/sliding-window blocks);
    page 0 is the reserved null page (pos = -1, never written)."""
    prefix, pattern, suffix, n_per = _layer_layout(cfg)
    b, ml = (batch, max_len) if pages is None else pages
    def stack_cache(kind):
        one = KC.init_block_cache(cfg, kind, b, ml)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_per,) + a.shape), one)
    n_scanned = n_per * len(pattern)
    return {
        "prefix": tuple(
            KC.init_block_cache(cfg, k, b, ml, layer_idx=i)
            for i, k in enumerate(prefix)),
        "blocks": tuple(stack_cache(k) for k in pattern) if n_per else None,
        "suffix": tuple(
            KC.init_block_cache(cfg, k, b, ml,
                                layer_idx=len(prefix) + n_scanned + i)
            for i, k in enumerate(suffix)),
    }


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array,
            lengths: jax.Array, max_len: int, source: jax.Array | None = None,
            mesh=None):
    """Aligned right-padded prefill.  Returns (last_logits (B,V), caches).
    ``mesh`` (closure capture, as in :func:`decode_step`) runs the pallas
    prefill kernel under shard_map on a multi-device mesh."""
    B, T = tokens.shape
    if cfg.encoder_decoder and source is not None:
        source = encode(cfg, params, source)
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    ctx = {
        "positions": positions,
        "lengths": lengths, "source": source, "max_len": max_len,
        "mesh": mesh, "valid": positions < lengths[:, None],
    }
    x = embed_tokens(cfg, params, tokens)
    x, caches, _ = run_stack(cfg, params, x, ctx, caches={})
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    return logits_for(cfg, params, last[:, None, :])[:, 0], caches


def decode_step(cfg: ModelConfig, params: Params, caches: Params,
                tokens: jax.Array, cur: jax.Array,
                active: jax.Array | None = None, *,
                cache_shardings=None, pages=None, mesh=None,
                moe_counts: bool = False):
    """One decode step.  tokens: (B,) int32, cur: (B,) absolute positions.
    ``active`` (B,) bool masks cache writes for idle batch rows (serving
    slots between requests).  ``cache_shardings`` (optional NamedSharding
    tree matching ``caches``) pins the updated cache's layout so a fused
    multi-step loop never reshards its carry mid-scan.  ``pages``
    (ptab (B, n_slot_pages) int32, page_size) switches reads and the
    deferred write to the page-major pool layout.  ``mesh`` (closure
    capture, never a traced argument) lets the pallas decode readers run
    under shard_map over the mesh's "model" axis.  ``active`` also keeps
    idle rows out of MoE routing.  Returns (logits (B, V), new caches),
    and with ``moe_counts`` (cfg.moe only) the step's routing counters
    summed over the MoE layers, (len(layers.MOE_COUNTERS),) int32."""
    x = embed_tokens(cfg, params, tokens[:, None])
    ctx = {"cur": cur, "pages": pages, "mesh": mesh,
           "valid": None if active is None else active[:, None]}
    x, updates, aux = run_stack(cfg, params, x, ctx, caches=caches,
                                decode=True)
    caches = KC.apply_decode_writes(caches, updates, cur, active, pages=pages)
    caches = KC.constrain_caches(caches, cache_shardings)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = logits_for(cfg, params, x)[:, 0]
    if moe_counts:
        return logits, caches, aux["moe"]
    return logits, caches


def verify_step(cfg: ModelConfig, params: Params, caches: Params,
                tokens: jax.Array, cur: jax.Array, feed_mask: jax.Array,
                pages=None, mesh=None):
    """Speculative-decoding target verification: logits for S fed tokens
    in ONE pass (one weight/cache read amortized over S positions — the
    step-count lever low-rank caches leave on the table).

    tokens: (B, S) int32 — tokens[:, 0] is the slot's next sequential
    feed, columns 1.. are draft proposals.  cur: (B,) absolute position
    of column 0.  feed_mask: (B, S) bool marks candidate columns (masked
    columns contribute no K/V and their logits are garbage).

    Cache writes are NOT applied here: the deferred (B, S, ...) updates
    are returned so the caller can run accept/reject on the logits and
    commit only the accepted prefix via :func:`commit_verify_writes` —
    the ring then never sees a rejected token.  Returns
    (logits (B, S, V) float32, updates)."""
    x = embed_tokens(cfg, params, jnp.maximum(tokens, 0))
    ctx = {"cur": cur, "feed_mask": feed_mask, "pages": pages, "mesh": mesh}
    x, updates, _ = run_stack(cfg, params, x, ctx, caches=caches,
                              decode=True, verify=True)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return logits_for(cfg, params, x), updates


def commit_verify_writes(caches: Params, updates: Params, cur: jax.Array,
                         mask: jax.Array, *, cache_shardings=None,
                         pages=None) -> Params:
    """Apply a verify step's deferred writes for the accepted prefix
    (``mask`` (B, S) bool) and re-pin the cache layout (see
    :func:`decode_step`)."""
    caches = KC.apply_verify_writes(caches, updates, cur, mask, pages=pages)
    return KC.constrain_caches(caches, cache_shardings)


def swap_cache_slot(caches: Params, stage: Params, slot: jax.Array,
                    q: jax.Array) -> Params:
    """Install staged-row ``q`` of a stage cache (same tree as the ring
    pool, see :func:`init_decode_cache`) into serving-slot row ``slot``
    of the resident pool — the device side of a mid-window continuous-
    batching swap.  ``slot``/``q`` are traced scalars; passing a
    ``slot`` >= batch makes the scatter a no-op (``mode="drop"``), which
    is how the fused window expresses "no swap this iteration" without a
    branch.  Ring layout only: paged swaps go through the page table
    (the staged request's pages are scattered into the shared pool at
    stage time, so installing is just a carry-row copy)."""
    def leaf(axis):
        def f(pool, srow):
            idx = (slice(None),) * axis + (slot,)
            return pool.at[idx].set(jnp.take(srow, q, axis=axis),
                                    mode="drop")
        return f
    return {
        # prefix/suffix leaves are (B, ...); scanned blocks carry a
        # leading (n_per,) layer axis before the slot axis.
        "prefix": jax.tree.map(leaf(0), caches["prefix"], stage["prefix"]),
        "blocks": jax.tree.map(leaf(1), caches["blocks"], stage["blocks"]),
        "suffix": jax.tree.map(leaf(0), caches["suffix"], stage["suffix"]),
    }


def wipe_pages(caches: Params, pages: jax.Array) -> Params:
    """Reset the ``pos`` stamps of physical ``pages`` (1-D int32) of a
    page-major cache pool to -1 (empty).  Position masking is the pool's
    ONLY validity mechanism — a recycled page still holds its previous
    holder's pos values, which ``_decode_mask`` would read as valid for
    any new holder whose ``cur`` has passed them — so every page that is
    mapped into a slot WITHOUT being covered by a prefill scatter (lazy
    page reservation allocating ahead of ``cur``) must be wiped first.
    Content leaves are left as-is: garbage latents under pos = -1 are
    unreadable.  Padding ``pages`` with the null page 0 is harmless (its
    pos is already -1 and nothing ever reads it as non-empty)."""
    def one(path, leaf):
        if getattr(path[-1], "key", None) != "pos":
            return leaf
        # scanned blocks carry a leading (n_per,) layer axis before the
        # page axis; prefix/suffix leaves are page-major directly
        if getattr(path[0], "key", None) == "blocks":
            return leaf.at[:, pages].set(-1)
        return leaf.at[pages].set(-1)
    return jax.tree_util.tree_map_with_path(one, caches)


def preempt_slot(st: dict, slot: int) -> dict:
    """Evict serving-slot row ``slot`` from a fused-window carry: the
    slot goes inactive (no further decode steps, no cache writes) and,
    under continuous batching, its generation counter bumps so any
    in-flight host scatter or harvested status targeting the old
    occupant is redirected/stale-ified by the existing gen guards.  The
    evicted request's sampling state was snapshotted host-side before
    this call (see the engine's preemption path); everything else about
    the row is dead until a new occupant installs over it."""
    out = dict(st)
    out["act"] = st["act"].at[slot].set(False)
    if "gen" in st:
        out["gen"] = st["gen"].at[slot].add(1)
    return out


def decode_loop(cfg: ModelConfig, params: Params, caches: Params,
                tokens: jax.Array, cur: jax.Array, steps: int, *,
                active: jax.Array | None = None, rng: jax.Array | None = None,
                sample_fn=None, cache_shardings=None):
    """Fused multi-token decode: ``steps`` iterations of step -> sample ->
    feed under one ``lax.scan``, the sampled token living in device carry
    (no host round-trip per token — the caller syncs once per loop).

    tokens/cur: (B,) as in :func:`decode_step`.  ``sample_fn(logits, key)
    -> (B,) int32`` picks the next token (greedy argmax when None; ``rng``
    seeds the per-step key split, only used when sampling).
    ``cache_shardings`` accepts pre-sharded caches: the scan carry is
    pinned to that layout every iteration, so a mesh caller pays zero
    reshards inside the loop.  Returns (caches, last_tokens, cur,
    out_tokens (B, steps))."""
    key0 = rng if rng is not None else jax.random.PRNGKey(0)

    def body(carry, _):
        caches, tok, cur, key = carry
        logits, caches = decode_step(cfg, params, caches, tok, cur, active,
                                     cache_shardings=cache_shardings)
        if sample_fn is None:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            nxt = sample_fn(logits, sub)
        inc = 1 if active is None else active.astype(cur.dtype)
        return (caches, nxt, cur + inc, key), nxt

    (caches, tok, cur, _), toks = jax.lax.scan(
        body, (caches, tokens, cur, key0), None, length=steps)
    return caches, tok, cur, jnp.moveaxis(toks, 0, 1)
