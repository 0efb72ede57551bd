"""The engine's stages on the profiler clock.

A CPU profiler trace (``jax.profiler.start_trace``) of a tiny overlapped
engine must hold every stage span, nested as the engine runs them, with
the worker threads on lines of their own and the requests' uids on the
spans that admit and retire them; ``metrics()["profile"]`` must sum the
same intervals; no span may run per token; and the prefill counters must
count a hand-sized wave exactly.
"""

import collections
import dataclasses
import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.serving import Engine, Request
from repro.serving.engine import PROFILE_KEYS
from test_async_serving import _model, _prompts

PREFIXES = ("engine.", "admission.", "backlog.")
STAGES = ("engine.step", "engine.harvest", "engine.bookkeep",
          "engine.finish", "engine.admission_stage", "engine.merge",
          "engine.dispatch", "engine.flush", "admission.prepare",
          "backlog.drain")


@dataclasses.dataclass
class Span:
    name: str
    line: tuple
    start: int
    end: int
    args: dict

    def ids(self, key):
        return [int(x) for x in str(self.args[key]).split()]

    def within(self, other):
        return (self.line == other.line and other.start <= self.start
                and self.end <= other.end)


def _spans(trace_dir):
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(Span(e.name, (plane.name, i), e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return out


def _traced(eng, batch, trace_dir):
    """Serve ``batch`` (uid, prompt, max_new) under a profiler trace;
    returns (spans, metrics before, metrics after, perf_counter at start)."""
    jax.profiler.start_trace(str(trace_dir))
    t_start = time.perf_counter()
    m0 = eng.metrics()
    for uid, prompt, n in batch:
        eng.submit(Request(uid=uid, prompt=prompt.copy(), max_new_tokens=n))
    eng.run()
    m1 = eng.metrics()
    jax.profiler.stop_trace()
    return _spans(str(trace_dir)), m0, m1, t_start


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """An overlapped engine with its admission worker: 2 slots, 5
    requests, so slots are emptied and refilled; warmed up first, so the
    trace holds serving, not compiles."""
    cfg, params = _model("latent")
    eng = Engine(cfg, params, max_slots=2, max_len=40, overlap=True,
                 staging_depth=1)
    prompts = _prompts(cfg, n=5)
    for i in range(2):
        eng.submit(Request(uid=100 + i, prompt=prompts[i].copy(),
                           max_new_tokens=6))
    eng.run()
    batch = [(i, p, 6 + 4 * i) for i, p in enumerate(prompts)]
    spans, m0, m1, t_start = _traced(eng, batch,
                                     tmp_path_factory.mktemp("trace"))
    eng.close()
    return spans, m0, m1, t_start, [uid for uid, _, _ in batch]


def _by_name(spans):
    out = collections.defaultdict(list)
    for s in spans:
        out[s.name].append(s)
    return out


def test_every_stage_span_appears_nested(served):
    spans, *_ = served
    by = _by_name(spans)
    for name in STAGES:
        assert by[name], (name, sorted(by))
    outer = by["engine.step"] + by["engine.flush"]
    for name in ("engine.harvest", "engine.bookkeep",
                 "engine.admission_stage", "engine.dispatch"):
        for s in by[name]:
            assert any(s.within(o) for o in outer), (name, s)
    for s in by["engine.finish"]:
        assert any(s.within(b) for b in by["engine.bookkeep"]), s
    for s in by["engine.merge"]:
        assert any(s.within(a) for a in by["engine.admission_stage"]), s
    for s in by["engine.step"] + by["engine.harvest"] + by["engine.dispatch"]:
        assert isinstance(s.args["window"], int), s


def test_workers_have_lines_of_their_own(served):
    spans, *_ = served
    by = _by_name(spans)
    main = {s.line for s in by["engine.step"]}
    prepare = {s.line for s in by["admission.prepare"]}
    drain = {s.line for s in by["backlog.drain"]}
    assert len(main) == len(prepare) == len(drain) == 1
    assert len(main | prepare | drain) == 3
    for s in by["admission.prepare"]:
        assert s.args["rows"] >= 1 and s.args["length"] >= 1, s


def test_merge_and_finish_carry_the_uids_served(served):
    spans, _, _, _, uids = served
    by = _by_name(spans)
    merged = [u for s in by["engine.merge"] for u in s.ids("uids")]
    finished = [u for s in by["engine.finish"] for u in s.ids("uids")]
    prepared = [u for s in by["admission.prepare"] for u in s.ids("uids")]
    assert sorted(merged) == sorted(finished) == sorted(prepared) == uids
    for s in by["engine.merge"] + by["engine.finish"]:
        assert len(s.ids("slots")) == len(s.ids("uids"))
        assert set(s.ids("slots")) <= {0, 1}


def test_profile_seconds_are_the_sums_of_their_spans(served):
    spans, m0, m1, _, _ = served
    total = collections.Counter()
    for s in spans:
        total[s.name] += (s.end - s.start) / 1e9
    a, b = m0["profile"]["seconds"], m1["profile"]["seconds"]
    assert set(b) == set(PROFILE_KEYS.values())
    for name, key in PROFILE_KEYS.items():
        assert b[key] - a[key] == pytest.approx(total[name], abs=1e-3), key


def test_slot_refills_pair_a_finish_with_the_next_merge(served):
    """metrics()["slot_refills"] holds, per refill, the gap from the end
    of the engine.finish that emptied a slot to the start of the
    engine.merge that filled it: the same pairs the spans give."""
    spans, _, m1, t_start, _ = served
    freed, gaps = {}, []
    for s in sorted(spans, key=lambda s: s.start):
        if s.name == "engine.finish":
            for slot in s.ids("slots"):
                freed[slot] = s.end
        elif s.name == "engine.merge":
            for slot in s.ids("slots"):
                if slot in freed:
                    gaps.append((s.start - freed.pop(slot)) / 1e9)
    mine = [g for t, g in m1["slot_refills"] if t - g >= t_start]
    assert len(gaps) >= 3
    assert sorted(mine) == pytest.approx(sorted(gaps), abs=1e-3)


def test_span_count_does_not_grow_with_output_length(tmp_path):
    """Every request fits one 32-token window and admission runs inline,
    so the boundaries are the same at 6 and 24 tokens a request: so must
    the spans be."""
    cfg, params = _model("latent")
    prompts = _prompts(cfg, n=3)
    counts = {}
    for max_new in (6, 24):
        eng = Engine(cfg, params, max_slots=2, max_len=40, overlap=True,
                     sync_every=32, admission_thread=False)
        eng.submit(Request(uid=99, prompt=prompts[0].copy(),
                           max_new_tokens=2))
        eng.run()
        batch = [(i, p, max_new) for i, p in enumerate(prompts)]
        spans, *_ = _traced(eng, batch, tmp_path / str(max_new))
        eng.close()
        counts[max_new] = collections.Counter(s.name for s in spans)
    assert counts[6]["engine.step"] >= 3
    assert counts[6] == counts[24]


@pytest.mark.parametrize("overlap", [False, True])
def test_prefill_counters_for_a_hand_sized_wave(overlap):
    """Prompts of 5, 7 and 9 tokens admitted as one wave pad to the
    (4, 16) bucket: 21 real tokens in 64 positions."""
    cfg, params = _model("latent")
    kw = {"overlap": True, "admission_thread": False} if overlap else {}
    eng = Engine(cfg, params, max_slots=4, max_len=40, **kw)
    g = np.random.default_rng(5)
    for i, n in enumerate((5, 7, 9)):
        eng.submit(Request(uid=i, prompt=g.integers(
            0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=3))
    eng.run()
    eng.close()
    m = eng.metrics()
    assert m["prefill_calls"] == 1
    assert m["prefill_tokens"] == 21
    assert m["prefill_positions"] == 64
    assert m["prefill_positions"] >= m["prefill_tokens"]
