"""Seeded random weights for one chip's share of a Qwen3-MoE decoder
(HF ``Qwen3MoeForCausalLM``), made on the device in one jitted call in the
type they are served in: Qwen3's attention leaves (dense GQA or the
ReCalKV latent path, from ``bench.models.qwen3.weights``) with the dense
MLP replaced by the experts this chip holds and the whole router.

Layout, every per-layer leaf stacked on a leading layer axis:
  embed (V, d); lm_head (d, V); final_norm (d,)
  layers: Qwen3's ln1, ln2, q_norm, k_norm, wq and attention leaves;
          router (L, d, E) float32 over all E routed experts;
          w_gate, w_up (L, Eh, d, f); w_down (L, Eh, f, d) for the Eh
          experts held here (f = moe_intermediate_size)
Norm leaves hold the gain minus one; matrix scales follow fan-in; the
embedding has std 0.02 (initializer_range)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib.seeds import jax_key
from bench.models.qwen3 import weights as qwen3

NORM_STD = qwen3.NORM_STD


def dims(cfg: dict) -> dict:
    """Named sizes: Qwen3's, plus the routed experts (E), those held here
    (Eh, from index e0), experts per token (k) and the expert width (f)."""
    dep = cfg["deployment"]
    out = qwen3.dims(cfg)
    out |= {"E": dep["router_width"], "Eh": cfg["num_experts"],
            "e0": dep["first_expert_held"], "k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "norm_topk": bool(cfg["norm_topk_prob"])}
    return out


def shapes(cfg: dict) -> dict:
    """(shape, dtype, std) of every leaf; std None marks a norm gain."""
    n = dims(cfg)
    L, d, E, Eh, f, V = n["L"], n["d"], n["E"], n["Eh"], n["f"], n["V"]
    out = qwen3.shapes(cfg)
    layers = {k: v for k, v in out["layers"].items()
              if k not in ("w_gate", "w_up", "w_down")}
    bf, f32 = jnp.bfloat16, jnp.float32
    layers |= {
        "router": ((L, d, E), f32, d ** -0.5),
        "w_gate": ((L, Eh, d, f), bf, d ** -0.5),
        "w_up": ((L, Eh, d, f), bf, d ** -0.5),
        "w_down": ((L, Eh, f, d), bf, f ** -0.5),
    }
    return {"embed": out["embed"], "lm_head": ((d, V), bf, d ** -0.5),
            "final_norm": out["final_norm"], "layers": layers}


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
