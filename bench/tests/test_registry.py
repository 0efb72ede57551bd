"""Everything BENCHMARK.json names resolves to a file of its own, and a
new cell and a new metric need only new files and entries."""

import json
import re

import pytest

from bench.lib import registry
from bench.tests.tiny import TINY_MIXES, make_root

BENCH = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_workload_resolves(w):
    cell = registry.resolve(w)
    assert cell.cfg["name"] == cell.name.split(".")[0]
    assert cell.mix["loop"] in ("open", "closed")
    assert "logit_gap" in cell.limits
    for part in ("weights", "reference", "program", "counts"):
        registry.family_module(cell.family, part)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer


@pytest.mark.parametrize("m", [m["name"] for m in
                               BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_a_reader(m):
    assert callable(registry.metric_reader(m))


def test_names_and_moves_follow_the_contract():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for item in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                 + BENCH["per_layer"]):
        assert NAME.match(item["name"])
    for m in BENCH["per_layer"]:
        reported = e2e[m["moves"]].get("workloads")
        for w in m.get("workloads", []):
            assert reported is None or w in reported
    for c in BENCH["configs"]:
        assert (registry.ROOT / c["file"]).exists()


def test_a_new_cell_and_metric_are_files_and_entries(tmp_path):
    root = make_root(tmp_path)
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        dict(TINY_MIXES["tiny_open"], rate=1.5)))
    (root / "bench/limits/qwen3-4b-recalkv.burst.json").write_text(
        json.dumps({"logit_gap": {"limit": 1.0}}))
    (root / "bench/metrics/window_len_s.py").write_text(
        "def read(rec):\n    return rec.window_s\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "qwen3-4b-recalkv.burst",
                               "config": "qwen3-4b-recalkv",
                               "traffic": "burst", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "window_len_s", "unit": "s",
                               "better": "lower", "source": "host_clock",
                               "layer": "load generator (bench/lib/drive.py)",
                               "moves": "setup_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = registry.resolve("qwen3-4b-recalkv.burst", root)
    assert cell.mix["rate"] == 1.5
    assert "window_len_s" in [m["name"] for m in cell.per_layer]

    class Rec:
        window_s = 12.5
    assert registry.metric_reader("window_len_s", root)(Rec()) == 12.5
