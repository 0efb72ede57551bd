"""The engine's spans in a kept trace (``bench/lib/spans.py``), on
hand-made planes and on a CPU trace of a tiny engine."""

import dataclasses
from types import SimpleNamespace as NS

import pytest

from bench.lib import spans

NS_ = 1e-9


def ev(name, start, end, **args):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(args.items()))


def planes():
    """Two boundaries: the first retires slots 0 and 1, the second merges
    uid 7 into slot 1, prepared on the worker from 300 to 650."""
    main = [ev("window", 0, 1000),
            ev("step", 0, 400), ev("engine.step", 0, 400, window=0),
            ev("engine.harvest", 0, 100, window=0),
            ev("engine.bookkeep", 100, 150),
            ev("engine.finish", 110, 140, uids="5 6", slots="0 1"),
            ev("engine.admission_stage", 150, 250),
            ev("engine.dispatch", 250, 390, window=2),
            ev("step", 500, 900), ev("engine.step", 500, 900, window=3),
            ev("engine.harvest", 500, 600, window=1),
            ev("engine.bookkeep", 600, 620),
            ev("engine.admission_stage", 620, 800),
            ev("engine.merge", 700, 780, uids=7, slots=1),
            ev("engine.dispatch", 800, 890, window=3)]
    worker = [ev("admission.prepare", 300, 650, uids=7, rows=1, length=16)]
    backlog = [ev("backlog.drain", 120, 130, window=0)]
    host = NS(name="/host:CPU", lines=[NS(name="python", events=main),
                                       NS(name="python", events=worker),
                                       NS(name="python", events=backlog)])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[
        ev("%fusion.1 = f32[8] fusion()", 0, 200),
        ev("%fusion.2 = f32[8] fusion()", 300, 700),
        ev("%fusion.3 = f32[8] fusion()", 750, 1000)])])
    return [host, dev]


def test_span_totals_inside_the_window():
    s = spans.summarize(planes())
    assert s["window_s"] == pytest.approx(1000 * NS_)
    assert s["spans"]["engine.step"] == {"count": 2,
                                         "seconds": pytest.approx(800 * NS_)}
    assert s["spans"]["engine.merge"]["count"] == 1
    assert s["spans"]["admission.prepare"]["seconds"] == pytest.approx(
        350 * NS_)
    assert "step" not in s["spans"] and "window" not in s["spans"]


def test_idle_goes_to_the_innermost_main_thread_span():
    s = spans.summarize(planes())
    # idle [200, 300): admission_stage then dispatch; [700, 750): merge
    assert s["idle_by_span"] == {
        "engine.admission_stage": pytest.approx(50 * NS_),
        "engine.dispatch": pytest.approx(50 * NS_),
        "engine.merge": pytest.approx(50 * NS_)}
    assert s["host_stall_share"] == pytest.approx(15.0)
    assert s["idle_gaps"] == [
        ["step>engine.admission_stage+step>engine.dispatch",
         pytest.approx(100 * NS_)],
        ["step>engine.merge", pytest.approx(50 * NS_)]]


def test_harvest_is_waiting_not_a_stall():
    ps = planes()
    ps[1].lines[0].events[1] = ev("%fusion.2 = f32[8] fusion()", 300, 500)
    s = spans.summarize(ps)
    # [500, 700): 100 ns in harvest, 20 in bookkeep, 80 in admission_stage
    assert s["idle_by_span"]["engine.harvest"] == pytest.approx(100 * NS_)
    assert s["host_stall_share"] == pytest.approx(
        100 * (50 + 50 + 20 + 80 + 50) / 1000)


def test_refill_pairs_a_finish_with_the_next_merge_of_its_slot():
    s = spans.summarize(planes())
    # slot 1 freed at 140, filled at 700; slot 0 is never refilled
    assert s["refills"] == [{
        "slot": 1, "uid": 7, "seconds": pytest.approx(560 * NS_),
        "queued_s": pytest.approx(160 * NS_),
        "prepare_s": pytest.approx(350 * NS_),
        "staged_s": pytest.approx(50 * NS_)}]


def test_step_coverage_and_stage_medians():
    s = spans.summarize(planes())
    assert s["step_coverage"] == pytest.approx(780 / 800)
    assert s["steps"]["admission"]["n"] == 1
    assert s["steps"]["admission"]["engine.merge"] == pytest.approx(80e-6)
    assert s["steps"]["plain"]["engine.finish"] == pytest.approx(30e-6)
    assert s["steps"]["plain"]["engine.step"] == pytest.approx(400e-6)


def test_a_trace_without_program_spans_is_an_error():
    ps = planes()
    ps[0].lines = []
    with pytest.raises(ValueError):
        spans.summarize(ps)


def test_cpu_trace_of_a_tiny_engine(tmp_path):
    """A host-only trace: spans and refills read, no device time."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import Engine, Request

    cfg = dataclasses.replace(get_config("qwen3-4b", smoke=True,
                                         recalkv_ratio=0.5),
                              dtype=jnp.float32)
    eng = Engine(cfg, T.init_params(cfg, jax.random.PRNGKey(0)),
                 max_slots=2, max_len=40, overlap=True, staging_depth=1)
    g = np.random.default_rng(0)

    def submit(uid, n):
        eng.submit(Request(uid=uid, prompt=g.integers(
            0, cfg.vocab_size, 6 + uid % 4).astype(np.int32),
            max_new_tokens=n))

    for uid in (90, 91):
        submit(uid, 4)
    eng.run()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        for uid in range(4):
            submit(uid, 6 + 4 * uid)
        while any(eng.unfinished.values()):
            with jax.profiler.TraceAnnotation("step"):
                eng.step()
        eng.flush()
    jax.profiler.stop_trace()
    eng.close()
    s = spans.summarize_dir(str(tmp_path))
    assert s["spans"]["engine.step"]["count"] >= 4
    assert s["spans"]["admission.prepare"]["count"] == 4
    assert s["host_stall_share"] is None and s["idle_by_span"] == {}
    assert 0.0 < s["step_coverage"] <= 1.0
    # two slots emptied before the trace take the first requests; which
    # slot the second takes depends on whether the first has finished
    assert {2, 3} <= {r["uid"] for r in s["refills"]} <= {1, 2, 3}
    for r in s["refills"]:
        assert r["seconds"] == pytest.approx(
            r["queued_s"] + r["prepare_s"] + r["staged_s"])
