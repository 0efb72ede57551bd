"""Resolution by name: ``BENCHMARK.json`` names workloads, configurations,
traffic mixes and metrics; each lives in a file of its own that this
module finds from the name alone.

  configuration  bench/configs/<config>.json (the sizes as run), whose
                 "family" names bench/models/<family>/ (weights, the plain
                 reference, the program adapter, the operation counts)
  traffic mix    bench/traffic/<traffic>.json, read by lib/traffic.py
  metric         bench/metrics/<metric name>.py with ``read(record)``
  limits         bench/limits/<workload>.json: each number compared by
                 the check of ``correct`` with its limit and readings

Adding a cell or a metric is adding files and entries; nothing here or in
run.py changes."""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    limits: dict
    family: str


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def family_module(family: str, part: str):
    return importlib.import_module(f"bench.models.{family}.{part}")


def metric_reader(name: str, root: Path = ROOT):
    """The ``read`` function of bench/metrics/<name>.py."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(workload: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    from bench.lib import traffic
    mix = traffic.load(w["traffic"], root / "bench" / "traffic")
    limits_path = root / "bench" / "limits" / f"{workload}.json"
    limits = json.loads(limits_path.read_text())
    return Cell(
        name=workload, chips=w["chips"], cfg=cfg, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if applies(m, workload)],
        limits=limits, family=cfg["family"])
