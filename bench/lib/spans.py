"""The engine's own spans in a kept profiler trace (``bench/run.py --trace 1
--keep-trace DIR``): where the host's time goes inside the window, and why
the device idles.

    python3 -m bench.lib.spans DIR      # prints one JSON object

The engine opens ``engine.*`` spans on the thread that calls ``step()``
and ``admission.*`` / ``backlog.*`` spans on its workers
(``repro.serving.engine``).  Inside the benchmark's "window" span this
reports:

  spans          count and seconds of each program span name
  idle_by_span   device-idle seconds by the innermost span open on the
                 main thread (the line holding "window"), or "none"
  host_stall_share  % of the window the device idled while the main
                 thread was inside an engine span other than engine.harvest
  idle_gaps      the ten longest idle gaps, labelled by the driver's span
                 and the innermost engine span, e.g. "step>engine.dispatch"
  step_coverage  share of the main thread's time inside the driver's
                 "step" spans that an engine stage span covers
  steps          median stage ms of the steps that merge an admission and
                 of those that do not
  refills        per slot refill (engine.finish that empties a slot to the
                 engine.merge that fills it): seconds, and how they split
                 into before the request's admission.prepare started, the
                 prepare, and prepared but not yet merged

Every reading works on a host-only (CPU) trace, where no device time is
reported.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import sys

from bench.lib import stats
from bench.lib.trace import _union, find_xplane

PREFIXES = ("engine.", "admission.", "backlog.")
DRIVER = ("step", "submit", "generator", "warmup")
TOP = 10


@dataclasses.dataclass
class Span:
    name: str
    line: int          # index of the host thread line, over all planes
    start: int         # ns
    end: int
    args: dict

    def ids(self, key: str) -> list[int]:
        return [int(x) for x in str(self.args.get(key, "")).split()]


def host_spans(planes):
    """(window (start, end) or None, main line or None, spans): the
    program's spans and the driver's, on the host planes' lines."""
    spans, window, main, line_no = [], None, None, 0
    for p in planes:
        if not p.name.startswith("/host"):
            continue
        for line in p.lines:
            for e in line.events:
                if e.name == "window":
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                    main = line_no
                elif e.name.startswith(PREFIXES) or e.name in DRIVER:
                    spans.append(Span(e.name, line_no, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
            line_no += 1
    if main is None:
        steps = collections.Counter(s.line for s in spans
                                    if s.name == "engine.step")
        main = steps.most_common(1)[0][0] if steps else None
    return window, main, spans


def timeline(spans, lo: int, hi: int):
    """The main line over [lo, hi) as non-overlapping (start, end, stack)
    segments; ``stack`` lists the spans open there, outermost first.
    Spans of one thread nest, so a stack holds them."""
    events = []
    for s in spans:
        if s.end > lo and s.start < hi:
            events.append((max(s.start, lo), 1, -s.end, s.name))
            events.append((min(s.end, hi), 0, 0, s.name))
    events.sort()
    out, stack, t = [], [], lo
    for when, opening, _, name in events:
        if when > t:
            out.append((t, when, tuple(stack)))
            t = when
        if opening:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
    if hi > t:
        out.append((t, hi, tuple(stack)))
    return out


def _engine(stack) -> str:
    """The innermost program span of a stack, or "none"."""
    inner = [n for n in stack if n.startswith(PREFIXES)]
    return inner[-1] if inner else "none"


def _label(stack) -> str:
    outer = [n for n in stack if n in DRIVER]
    parts = outer[:1] + [n for n in [_engine(stack)] if n != "none"]
    return ">".join(parts) or "none"


def _overlaps(intervals, segments):
    """(overlap seconds, stack) of every pair of sorted, disjoint
    ``intervals`` and ``segments``."""
    i = j = 0
    while i < len(intervals) and j < len(segments):
        a, b = intervals[i]
        s, e, stack = segments[j]
        c = min(b, e) - max(a, s)
        if c > 0:
            yield c / 1e9, stack
        if b < e:
            i += 1
        else:
            j += 1


def device_idle(planes, lo: int, hi: int):
    """Per device plane, its idle intervals inside [lo, hi): the gaps in
    the union of its op intervals."""
    out = []
    for p in planes:
        if not p.name.startswith("/device:TPU") or "NON_CORE" in p.name:
            continue
        ops = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
               for line in p.lines if line.name == "XLA Ops"
               for e in line.events
               if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
        gaps, prev = [], lo
        for s, e in _union(ops) + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        out.append(gaps)
    return out


def refills(spans, main: int, lo: int, hi: int):
    """Each refill whose merge starts in [lo, hi): the slot, the uid
    merged, its seconds, and their split (before the uid's
    admission.prepare began, the prepare, prepared until the merge)."""
    prepare = {}
    for s in spans:
        if s.name == "admission.prepare":
            for uid in s.ids("uids"):
                prepare[uid] = s
    freed, out = {}, []
    for s in sorted((s for s in spans if s.line == main), key=lambda s: s.start):
        if s.name == "engine.finish":
            for slot in s.ids("slots"):
                freed[slot] = s.end
        elif s.name == "engine.merge":
            for slot, uid in zip(s.ids("slots"), s.ids("uids")):
                f = freed.pop(slot, None)
                if f is None or not lo <= s.start < hi:
                    continue
                m, p = s.start, prepare.get(uid)
                ps, pe = (p.start, p.end) if p else (f, f)
                out.append({
                    "slot": slot, "uid": uid, "seconds": (m - f) / 1e9,
                    "queued_s": max(0, min(ps, m) - f) / 1e9,
                    "prepare_s": max(0, min(pe, m) - max(ps, f)) / 1e9,
                    "staged_s": max(0, m - max(pe, f)) / 1e9})
    return out


def _children(spans, main: int, parent: Span):
    return [s for s in spans if s.line == main and s is not parent
            and s.name.startswith("engine.")
            and parent.start <= s.start and s.end <= parent.end]


def step_stages(spans, main: int, lo: int, hi: int):
    """Median ms of each stage of the engine.step spans in [lo, hi), for
    the steps that hold an engine.merge and for those that do not."""
    groups = {"admission": collections.defaultdict(list),
              "plain": collections.defaultdict(list)}
    counts = collections.Counter()
    for st in spans:
        if st.name != "engine.step" or st.line != main or not lo <= st.start < hi:
            continue
        kids = _children(spans, main, st)
        kind = ("admission" if any(k.name == "engine.merge" for k in kids)
                else "plain")
        counts[kind] += 1
        g = groups[kind]
        g["engine.step"].append((st.end - st.start) / 1e6)
        per = collections.Counter()
        for k in kids:
            per[k.name] += (k.end - k.start) / 1e6
        for name, ms in per.items():
            g[name].append(ms)
    return {kind: {"n": counts[kind],
                   **{n: stats.percentile(v, 50) for n, v in g.items()}}
            for kind, g in groups.items()}


def summarize(planes) -> dict:
    planes = list(planes)
    window, main, spans = host_spans(planes)
    if window is None:
        ts = [t for s in spans for t in (s.start, s.end)]
        if not ts:
            raise ValueError("trace holds no program spans")
        window = (min(ts), max(ts))
    lo, hi = window
    inside = [s for s in spans if s.start < hi and s.end > lo]
    totals = collections.defaultdict(lambda: [0, 0.0])
    for s in inside:
        if s.name.startswith(PREFIXES):
            totals[s.name][0] += 1
            totals[s.name][1] += (min(s.end, hi) - max(s.start, lo)) / 1e9
    segs = timeline([s for s in inside if s.line == main], lo, hi)
    idle = device_idle(planes, lo, hi)
    n_dev = max(len(idle), 1)
    by_span = collections.Counter()
    for dev in idle:
        for c, stack in _overlaps(dev, segs):
            by_span[_engine(stack)] += c / n_dev
    starts = [s for s, _, _ in segs]
    gaps = []
    for a, b in sorted((g for dev in idle for g in dev),
                       key=lambda g: g[0] - g[1])[:TOP]:
        over = collections.Counter()
        j = max(bisect.bisect_right(starts, a) - 1, 0)
        for c, stack in _overlaps([(a, b)], segs[j:]):
            over[_label(stack)] += c
        gaps.append(["+".join(n for n, _ in over.most_common()) or "none",
                     (b - a) / 1e9])
    stall = sum(v for k, v in by_span.items()
                if k.startswith("engine.") and k != "engine.harvest")
    in_step = [(e - s, stack) for s, e, stack in segs if "step" in stack]
    covered = sum(d for d, stack in in_step
                  if _engine(stack) not in ("none", "engine.step"))
    total = sum(d for d, _ in in_step)
    return {
        "window_s": (hi - lo) / 1e9,
        "spans": {k: {"count": v[0], "seconds": v[1]}
                  for k, v in sorted(totals.items())},
        "idle_by_span": dict(by_span.most_common()),
        "host_stall_share": (100.0 * stall / ((hi - lo) / 1e9)
                             if idle else None),
        "idle_gaps": gaps,
        "step_coverage": covered / total if total else None,
        "steps": step_stages(inside, main, lo, hi),
        "refills": refills(spans, main, lo, hi),
    }


def summarize_dir(directory: str) -> dict:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(find_xplane(directory)).planes)


if __name__ == "__main__":
    print(json.dumps(summarize_dir(sys.argv[1])))
