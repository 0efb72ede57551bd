"""A tiny copy of the benchmark for CPU tests: the real harness files,
plus a Qwen3 configuration small enough for interpret-mode kernels and
two small traffic mixes, written into a scratch root."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_SIZES = {"num_hidden_layers": 2, "hidden_size": 64,
              "num_attention_heads": 8, "num_key_value_heads": 4,
              "head_dim": 16, "intermediate_size": 128, "vocab_size": 257}

TINY_MIXES = {
    "tiny_closed": {
        "loop": "closed", "clients": 4,
        "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.4,
                   "min": 8, "max": 40},
        "output": {"dist": "uniform", "min": 8, "max": 24},
        "block": 8, "size_seed": 1, "prelude_s": 0.5, "drain_s": 0,
        "sample": 6, "why": "tiny closed loop for CPU tests"},
    "tiny_open": {
        "loop": "open", "rate": 6.0,
        "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                   "min": 8, "max": 40},
        "output": {"dist": "uniform", "min": 8, "max": 20},
        "block": 8, "size_seed": 2, "prelude_s": 0.5, "drain_s": 60,
        "sample": 6, "why": "tiny open loop for CPU tests"},
}


def tiny_config(kind: str) -> dict:
    base = json.loads((REPO / "bench/configs/qwen3-4b-recalkv.json")
                      .read_text())
    cfg = dict(base, **TINY_SIZES, name=f"tiny-{kind}")
    cfg["kv_cache"] = ({"kind": "recalkv", "group_size": 2, "rank_k": 16,
                        "rank_v": 16} if kind == "recalkv"
                       else {"kind": "dense"})
    cfg["serving"] = {"max_slots": 2, "max_len": 64, "staging_depth": 2}
    cfg["reduced"] = sorted(TINY_SIZES)
    return cfg


def make_root(tmp: Path, limit: float = 1.0) -> Path:
    """A scratch root holding BENCHMARK.json and the bench tree, with the
    tiny cells ``tiny-<kind>.<mix>`` added by files and entries only."""
    root = tmp / "root"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for kind in ("recalkv", "dense"):
        cfg = tiny_config(kind)
        path = f"bench/configs/{cfg['name']}.json"
        (root / path).write_text(json.dumps(cfg))
        bench["configs"].append({"name": cfg["name"], "source": cfg["source"],
                                 "file": path, "reduced": cfg["reduced"],
                                 "why": "CPU test size"})
        for mix_name, mix in TINY_MIXES.items():
            (root / f"bench/traffic/{mix_name}.json").write_text(
                json.dumps(mix))
            cell = f"{cfg['name']}.{mix_name}"
            bench["workloads"].append({"name": cell, "config": cfg["name"],
                                       "traffic": mix_name, "chips": 1,
                                       "why": "CPU test"})
            (root / f"bench/limits/{cell}.json").write_text(
                json.dumps({"logit_gap": {"limit": limit}}))
            if mix["loop"] == "closed":
                for m in bench["end_to_end"] + bench["per_layer"]:
                    if any(w.endswith(".longctx")
                           for w in m.get("workloads", [])):
                        m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
