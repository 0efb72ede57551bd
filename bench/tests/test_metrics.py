"""Metric readers on a hand-made run record."""

import json

import pytest

from bench.lib import cell, registry, traffic
from bench.lib.drive import Live
from bench.models.qwen3 import counts


def _live(i, p, o, due, submitted, times):
    lv = Live(traffic.Spec(i, p, o, 0.0), prompt=None, due_at=due,
              submitted_at=submitted, times=list(times),
              tokens=list(range(len(times))))
    return lv


def _record(live, t0=100.0, t1=110.0, trace=None, workload=None):
    c = registry.resolve(workload or "qwen3-4b-recalkv.longctx")
    m0 = {"windows": 10, "occupancy_mean": 2.0, "sync_every": 8,
          "profile": {"seconds": {"dispatch": 1.0, "admission_stage": 1.0,
                                  "bookkeep": 0.0, "harvest": 5.0}}}
    m1 = {"windows": 30, "occupancy_mean": 4.0, "sync_every": 8,
          "profile": {"seconds": {"dispatch": 1.2, "admission_stage": 1.4,
                                  "bookkeep": 0.2, "harvest": 9.0}}}
    return cell.Record(cell=c, counts=counts, peaks={"bf16_flops_per_s": 197e12,
                                                    "hbm_bytes_per_s": 819e9},
                       t0=t0, t1=t1, setup_s=42.0, live=live, eng_start=m0,
                       eng_end=m1, memory_peak_bytes=2 ** 33, trace=trace)


def read(name, rec):
    return registry.metric_reader(name)(rec)


def test_only_requests_due_in_the_window_count():
    inside = _live(0, 100, 3, 105.0, 105.0, [105.2, 105.3, 105.4])
    before = _live(1, 100, 3, 99.0, 99.0, [104.0, 104.1, 104.2])
    after = _live(2, 100, 3, 110.0, 110.0, [110.2, 110.3, 110.4])
    rec = _record([inside, before, after])
    assert rec.due_in_window() == [inside]


def test_tokens_per_s_counts_tokens_inside_the_window():
    a = _live(0, 1000, 6, 99.0, 99.0, [99.5, 100.0, 101.0, 109.9, 110.0,
                                        111.0])
    rec = _record([a], workload="qwen3-4b-recalkv.longctx")
    assert read("tokens_per_s", rec) == pytest.approx(3 / 10.0)


def test_engine_counters_are_differenced_over_the_window():
    rec = _record([], workload="qwen3-4b-recalkv.longctx")
    slots = rec.cell.cfg["serving"]["max_slots"]
    # occupancy sums 20 -> 120 over 20 windows: 5 slots held on average
    assert read("occupancy", rec) == pytest.approx(100.0 * 5 / slots)
    assert read("host_ms_per_window", rec) == pytest.approx(1e3 * 0.8 / 20)
    assert read("peak_hbm_gib", rec) == pytest.approx(8.0)
    assert read("setup_s", rec) == 42.0


def test_trace_readers_stay_silent_without_a_trace():
    rec = _record([], workload="qwen3-4b-recalkv.longctx")
    for name in ("decode_attn_roofline", "prefill_attn_roofline",
                 "prefill_mfu", "device_idle_share"):
        assert read(name, rec) is None


def test_roofline_reads_100_for_a_kernel_time_at_the_bound():
    cfg = json.loads((registry.ROOT / "bench/configs/qwen3-4b-dense.json")
                     .read_text())
    a = _live(0, 2000, 3, 99.0, 99.0, [101.0, 102.0, 103.0])
    rec = _record([a], workload="qwen3-4b-dense.longctx")
    rec.eng_end = dict(rec.eng_end, windows=rec.eng_start["windows"])
    ctx = [2001, 2002]
    nbytes = sum(counts.decode_attn_bytes(cfg, c) for c in ctx)
    flops = sum(counts.decode_attn_flops(cfg, c) for c in ctx)
    t_bound = max(nbytes / 819e9, flops / 197e12)
    rec.trace = {"custom_calls": {"jit_counted_fn": t_bound}, "programs": {},
                 "busy_s": 5.0, "window_s": 10.0}
    assert read("decode_attn_roofline", rec) == pytest.approx(100.0)
    rec.trace["custom_calls"]["jit_counted_fn"] = 2 * t_bound
    assert read("decode_attn_roofline", rec) == pytest.approx(50.0)
    assert read("device_idle_share", rec) == pytest.approx(50.0)
