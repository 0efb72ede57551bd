"""The program under test for one chip's share of a Qwen3-MoE
configuration: ``repro``'s model config with a held-expert ``MoEConfig``,
its parameter tree over the bench's arrays (no copy), and
``repro.serving.Engine`` with Qwen3's ``ENGINE_DEFAULTS`` (overlapped
pipeline 3 windows deep, 8 tokens per window, Pallas kernels, ring cache,
FIFO admission, greedy)."""

from __future__ import annotations

import dataclasses

from bench.models.qwen3 import program as qwen3
from bench.models.qwen3.program import ENGINE_DEFAULTS, request
from bench.models.qwen3_moe.weights import dims


def model_config(cfg: dict):
    from repro.models.config import MoEConfig
    n = dims(cfg)
    moe = MoEConfig(num_experts=n["E"], top_k=n["k"], d_expert=n["f"],
                    first_held=n["e0"], num_held=n["Eh"])
    return dataclasses.replace(qwen3.model_config(cfg), family="moe",
                               d_ff=n["f"], moe=moe)


def params(cfg: dict, w: dict) -> dict:
    """The program's scanned-layer parameter tree over the bench's arrays."""
    L = w["layers"]
    tree = qwen3.params(cfg, w)
    block = dict(tree["blocks"][0])
    block["mlp"] = {"router": L["router"], "wg": L["w_gate"],
                    "wi": L["w_up"], "wo": L["w_down"]}
    return dict(tree, blocks=(block,), lm_head=w["lm_head"])


def engine(cfg: dict, w: dict):
    from repro.serving import Engine
    sv = cfg["serving"]
    return Engine(model_config(cfg), params(cfg, w),
                  max_slots=sv["max_slots"], max_len=sv["max_len"],
                  staging_depth=sv["staging_depth"], **ENGINE_DEFAULTS)


__all__ = ["ENGINE_DEFAULTS", "engine", "model_config", "params", "request"]
