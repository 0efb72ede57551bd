"""A CPU-sized copy of the Qwen3-MoE configuration: the real file with
small widths, a 16-expert router of which experts 4..7 are held here, 4
experts per token, and one ReCalKV group of the 4 KV heads (G = 1), as in
the benchmarked cut."""

from __future__ import annotations

import json
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CONFIG = REPO / "bench/configs/qwen3-235b-a22b-recalkv.json"

TINY_SIZES = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 16, "moe_intermediate_size": 32,
              "num_experts_per_tok": 4, "vocab_size": 257}


def tiny_moe_config(kind: str = "recalkv", *, first_held: int = 4,
                    held: int = 4, router: int = 16) -> dict:
    cfg = dict(json.loads(CONFIG.read_text()), **TINY_SIZES,
               name=f"tiny-moe-{kind}")
    cfg["num_experts"] = held
    cfg["deployment"] = dict(cfg["deployment"], router_width=router,
                             experts_held_per_chip=held,
                             first_expert_held=first_held)
    cfg["kv_cache"] = ({"kind": "recalkv", "group_size": 4, "rank_k": 32,
                        "rank_v": 32} if kind == "recalkv"
                       else {"kind": "dense"})
    cfg["serving"] = {"max_slots": 4, "max_len": 64, "staging_depth": 1}
    return cfg
