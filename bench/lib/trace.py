"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the numbers the per-layer metrics read.

  window_s      length of the benchmark's own "window" span (host clock of
                the trace)
  busy_s        union of the device's op intervals inside the window,
                averaged over the device planes
  programs      device seconds per XLA program (module name, id stripped)
  custom_calls  device seconds of custom calls (the Pallas kernels) per
                enclosing program: both kernels are named ``_kernel``, so
                they are told apart by the program that runs them
  device_ops    the ten device operations that took most time
  idle_gaps     the ten longest idle gaps, each named by the benchmark's
                host spans (submit, step, warmup, generator) it overlaps
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

HOST_SPANS = ("step", "submit", "generator", "warmup")
TOP = 10


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def _op(name: str) -> str:
    """``%flash_prefill_attention.6 = bf16[...] custom-call(...), ...``
    -> ``flash_prefill_attention``: the op's name, numbering stripped."""
    return re.sub(r"[.][0-9]+$", "", name.split(" = ", 1)[0].lstrip("%"))


def _is_kernel(name: str) -> bool:
    """A Mosaic (Pallas) kernel: a custom call to ``tpu_custom_call``.
    XLA's own custom calls (AllocateBuffer, ConcatBitcast) are not."""
    return " custom-call(" in name and "tpu_custom_call" in name


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(0, min(e, hi) - max(s, lo))


def reduce_planes(planes) -> dict:
    """``planes``: iterable of objects with ``name`` and ``lines``, each
    line with ``name`` and ``events`` (``name``, ``start_ns``,
    ``duration_ns``, ``stats``), as ``ProfileData`` gives them."""
    host, devices = [], []
    for p in planes:
        if p.name.startswith("/device:TPU") and "NON_CORE" not in p.name:
            devices.append(p)
        elif p.name.startswith("/host"):
            host.append(p)
    spans = []                         # (start, end, name) on host threads
    window = None
    for p in host:
        for line in p.lines:
            for e in line.events:
                if e.name == "window":
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in HOST_SPANS:
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    programs = collections.Counter()
    customs = collections.Counter()
    ops = collections.Counter()
    busy_total, gaps = 0, []
    lo = hi = None
    for p in devices:
        mods, op_events = [], []
        for line in p.lines:
            if "Modules" in line.name:
                mods = [(e.start_ns, e.start_ns + e.duration_ns,
                         _program(e.name)) for e in line.events]
            elif line.name == "XLA Ops":
                op_events = list(line.events)
        if window is None:
            starts = [e.start_ns for e in op_events]
            ends = [e.start_ns + e.duration_ns for e in op_events]
            if not starts:
                continue
            window = (min(starts), max(ends))
        lo, hi = window
        mods.sort()
        mod_starts = [m[0] for m in mods]
        intervals = []
        for s, e, name in mods:
            programs[name] += _clip(s, e, lo, hi)
        # ops nest (a loop holds its body's ops): self time is an op's
        # time less that of the ops inside it
        evs = sorted(((ev.start_ns, ev.duration_ns, ev.name)
                      for ev in op_events), key=lambda x: (x[0], -x[1]))
        stack = []
        for s, d, name in evs:
            e = s + d
            c = _clip(s, e, lo, hi)
            if c <= 0:
                continue
            while stack and stack[-1][0] <= s:
                stack.pop()
            intervals.append((max(s, lo), min(e, hi)))
            i = bisect.bisect_right(mod_starts, s) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            key = f"{prog}/{_op(name)}"
            if stack:
                ops[stack[-1][1]] -= c
            ops[key] += c
            stack.append((e, key))
            if _is_kernel(name):
                customs[prog] += c
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged)
        prev = lo
        for s, e in merged + [[hi, hi]]:
            if s > prev:
                gaps.append((s - prev, prev, s))
            prev = max(prev, e)
    if window is None or not devices:
        raise ValueError("trace holds no device operations")
    n_dev = len(devices)
    gaps.sort(reverse=True)
    idle = []
    for length, s, e in gaps[:TOP]:
        over = collections.Counter()
        for a, b, name in spans:
            c = _clip(a, b, s, e)
            if c > 0:
                over[name] += c
        label = "+".join(n for n, _ in over.most_common()) or "none"
        idle.append([label, length / 1e9])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n_dev / 1e9,
        "programs": {k: v / n_dev / 1e9 for k, v in programs.items()},
        "custom_calls": {k: v / n_dev / 1e9 for k, v in customs.items()},
        "device_ops": [[k, v / n_dev / 1e9] for k, v in ops.most_common(TOP)],
        "idle_gaps": idle,
    }


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_dir(directory: str) -> dict:
    return reduce_file(find_xplane(directory))


def breakdown(summary: dict) -> dict:
    return {"device_ops": summary["device_ops"],
            "idle_gaps": summary["idle_gaps"]}
