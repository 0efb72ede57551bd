"""Seeded random weights for a Qwen3 decoder, dense GQA or with a ReCalKV
latent K/V path, made on the device in one jitted call in the type they
are served in (bfloat16 matrices, float32 norm gains).

Layout, every per-layer leaf stacked on a leading layer axis:
  embed (V, d); final_norm (d,)
  layers: ln1, ln2 (L, d); q_norm, k_norm (L, dh); wq (L, d, H*dh);
          w_gate, w_up (L, d, f); w_down (L, f, d); and either
          dense:   wk, wv (L, d, Hkv*dh); wo (L, H*dh, d)
          recalkv: l_k, l_v (L, G, d, r); r_k (L, G, r, s*dh);
                   wo_fused (L, H, r, d)
Norm leaves hold the gain minus one: a layer scales by ``1 + leaf``.
Matrix scales follow fan-in (std = fan_in ** -0.5); the embedding has
std 0.02 (the config's initializer_range)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib.seeds import jax_key

NORM_STD = 0.1


def dims(cfg: dict) -> dict:
    """Named sizes of one configuration file."""
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {"L": cfg["num_hidden_layers"], "d": cfg["hidden_size"], "H": H,
           "Hkv": Hkv, "dh": cfg["head_dim"], "f": cfg["intermediate_size"],
           "V": cfg["vocab_size"], "latent": cfg["kv_cache"]["kind"] == "recalkv"}
    if out["latent"]:
        kv = cfg["kv_cache"]
        s = kv["group_size"]
        out |= {"s": s, "G": Hkv // s, "rk": kv["rank_k"], "rv": kv["rank_v"]}
    return out


def shapes(cfg: dict) -> dict:
    """(shape, dtype, std) of every leaf; std None marks a norm gain."""
    n = dims(cfg)
    L, d, H, Hkv, dh, f = n["L"], n["d"], n["H"], n["Hkv"], n["dh"], n["f"]
    bf, f32 = jnp.bfloat16, jnp.float32
    layers = {
        "ln1": ((L, d), f32, None), "ln2": ((L, d), f32, None),
        "q_norm": ((L, dh), f32, None), "k_norm": ((L, dh), f32, None),
        "wq": ((L, d, H * dh), bf, d ** -0.5),
        "w_gate": ((L, d, f), bf, d ** -0.5),
        "w_up": ((L, d, f), bf, d ** -0.5),
        "w_down": ((L, f, d), bf, f ** -0.5),
    }
    if n["latent"]:
        G, s, rk, rv = n["G"], n["s"], n["rk"], n["rv"]
        layers |= {
            "l_k": ((L, G, d, rk), bf, d ** -0.5),
            "l_v": ((L, G, d, rv), bf, d ** -0.5),
            "r_k": ((L, G, rk, s * dh), bf, rk ** -0.5),
            "wo_fused": ((L, H, rv, d), bf, (H * rv) ** -0.5),
        }
    else:
        layers |= {
            "wk": ((L, d, Hkv * dh), bf, d ** -0.5),
            "wv": ((L, d, Hkv * dh), bf, d ** -0.5),
            "wo": ((L, H * dh, d), bf, (H * dh) ** -0.5),
        }
    return {"embed": ((n["V"], d), bf, 0.02),
            "final_norm": ((d,), f32, None), "layers": layers}


def init(cfg: dict, seed: int) -> dict:
    """Every leaf from ``seed``, in one jitted call on the default device."""
    spec = shapes(cfg)
    flat, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
        and isinstance(x[0], tuple))

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (shape, dtype, std) in zip(keys, flat):
            if std is None:
                out.append(NORM_STD * jax.random.normal(k, shape, dtype))
            else:
                out.append(jax.random.normal(k, shape, dtype)
                           * jnp.asarray(std, dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)(jax_key(seed, stream=1))
