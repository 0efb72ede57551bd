"""The program under test for a Qwen3 configuration: ``repro``'s model
config, its parameter tree built from the bench's weights (same arrays,
no copy), and ``repro.serving.Engine`` with ``launch.serve``'s settings:
overlapped pipeline 3 windows deep with the admission thread, 8 tokens
per window, FIFO admission, ring cache, Pallas kernels, greedy."""

from __future__ import annotations

import jax.numpy as jnp

from bench.models.qwen3.weights import dims

ENGINE_DEFAULTS = {"overlap": True, "pipeline_depth": 3, "sync_every": 8,
                   "backend": "pallas", "cache_layout": "ring",
                   "policy": "fifo"}


def model_config(cfg: dict):
    from repro.models.config import ModelConfig, ReCalKVRuntime
    n = dims(cfg)
    recalkv = None
    if n["latent"]:
        recalkv = ReCalKVRuntime(rank_k=n["rk"], rank_v=n["rv"],
                                 group_size=n["s"])
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=n["L"], d_model=n["d"],
        num_heads=n["H"], num_kv_heads=n["Hkv"], d_head=n["dh"],
        d_ff=n["f"], vocab_size=n["V"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), recalkv=recalkv,
        attn_backend="pallas", dtype=jnp.bfloat16, scan_layers=True)


def params(cfg: dict, w: dict) -> dict:
    """The program's scanned-layer parameter tree over the bench's arrays."""
    L = w["layers"]
    attn = {"wq": L["wq"], "q_norm": L["q_norm"], "k_norm": L["k_norm"]}
    if dims(cfg)["latent"]:
        attn |= {k: L[k] for k in ("l_k", "r_k", "l_v", "wo_fused")}
    else:
        attn |= {k: L[k] for k in ("wk", "wv", "wo")}
    block = {"ln1": L["ln1"], "ln2": L["ln2"], "attn": attn,
             "mlp": {"wg": L["w_gate"], "wi": L["w_up"], "wo": L["w_down"]}}
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "prefix": (), "blocks": (block,), "suffix": ()}


def engine(cfg: dict, w: dict):
    from repro.serving import Engine
    sv = cfg["serving"]
    return Engine(model_config(cfg), params(cfg, w),
                  max_slots=sv["max_slots"], max_len=sv["max_len"],
                  staging_depth=sv["staging_depth"], **ENGINE_DEFAULTS)


def request(uid: int, prompt, max_new_tokens: int, on_token):
    """A greedy request with no EOS: it emits exactly max_new_tokens."""
    from repro.serving import Request
    return Request(uid=uid, prompt=prompt, max_new_tokens=max_new_tokens,
                   on_token=on_token)
