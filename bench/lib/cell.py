"""One run of one cell: load, warm up, measure for ``seconds``, check
``correct``, and reduce everything to the result line.

Set-up is everything before the window opens: weights made on the device
from the seed, the engine, compiling (or loading from the persistent cache)
the cell's own shapes, a drained warm-up pass through submit/step, and the
traffic's prelude.  The window then runs with no compile expected; backend
compiles inside it are counted and printed."""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import peaks as peak_table
from bench.lib import check, drive, registry, stats, trace as trace_lib
from bench.lib import traffic

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
WARMUP_TOKENS = 9            # more than one 8-step window per request


class CompileLog:
    """Host times of program builds, from JAX's own events.  JAX reports a
    backend compile for every build, a load from the persistent cache
    included; a cache hit is reported besides, so compiles are builds less
    hits."""

    def __init__(self):
        self.builds: list[float] = []
        self.hits: list[float] = []
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_build)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_build(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.builds.append(time.perf_counter())

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.hits.append(time.perf_counter())

    def between(self, t0: float, t1: float) -> tuple[int, int]:
        """(compiles, loads from the persistent cache) in [t0, t1]."""
        with self._lock:
            builds = sum(t0 <= t <= t1 for t in self.builds)
            hits = sum(t0 <= t <= t1 for t in self.hits)
        return builds - hits, hits


class GcLog:
    """Host times of the interpreter's garbage collections, from
    ``gc.callbacks``: (start, seconds, generation) each."""

    def __init__(self):
        self.pauses: list[tuple[float, float, int]] = []
        self._start = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        elif self._start is not None:
            self.pauses.append((self._start, now - self._start,
                                info["generation"]))
            self._start = None

    def between(self, t0: float, t1: float):
        return [p for p in self.pauses if t0 <= p[0] < t1]

    def close(self):
        gc.callbacks.remove(self._on_gc)


@dataclasses.dataclass
class Record:
    """What the metric readers read."""
    cell: registry.Cell
    counts: object                 # the family's counts module
    peaks: dict
    t0: float
    t1: float
    setup_s: float
    live: list
    eng_start: dict
    eng_end: dict
    memory_peak_bytes: int
    trace: dict | None = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: float) -> bool:
        return self.t0 <= t < self.t1

    def due_in_window(self):
        return [r for r in self.live
                if r.due_at is not None and self.in_window(r.due_at)]


def pow2ceil(n: int) -> int:
    return 1 << (n - 1).bit_length()


def prefill_buckets(cfg: dict, mix: dict) -> list[tuple[int, int]]:
    """The (rows, length) prefill shapes this cell's traffic can reach:
    waves of 1..staging_depth prompts, lengths in the mix's range, each
    rounded up to a power of two as the engine's admission does."""
    sv = cfg["serving"]
    rows = sorted({pow2ceil(w) for w in range(1, sv["staging_depth"] + 1)})
    lens, p = [], pow2ceil(mix["prompt"]["min"])
    while p < mix["prompt"]["max"]:
        lens.append(min(p, sv["max_len"]))
        p *= 2
    lens.append(min(pow2ceil(mix["prompt"]["max"]), sv["max_len"]))
    return [(w, n) for w in rows for n in sorted(set(lens))]


def warm_up(eng, program, cfg: dict, mix: dict, seed: int):
    """Compile (or load) every program the window will run, before it
    opens: each prefill bucket the traffic can reach, the decode window,
    and the small programs of the admission merges, whose shapes follow
    how many rows a wave holds and how many merge at one boundary.
    Returns the seconds of its three phases."""
    marks = [time.perf_counter()]
    with jax.profiler.TraceAnnotation("warmup"):
        prefill = getattr(eng, "_prefill", None)
        if prefill is not None:
            for w, n in prefill_buckets(cfg, mix):
                toks = jnp.zeros((w, n), jnp.int32)
                lens = jnp.ones((w,), jnp.int32)
                jax.block_until_ready(prefill(eng.params, toks, lens))
        marks.append(time.perf_counter())
        sizes = sorted({min(n, mix["prompt"]["max"])
                        for _, n in prefill_buckets(cfg, mix)})
        rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 7])
        uids = iter(range(10 ** 9, 2 * 10 ** 9))

        def submit(i: int, new_tokens: int):
            prompt = rng.integers(0, cfg["vocab_size"], sizes[i % len(sizes)])
            eng.submit(program.request(next(uids), prompt.astype(np.int32),
                                       new_tokens, None))

        def drain():
            while any(eng.unfinished.values()):
                eng.step()
            eng.flush()

        slots, depth = cfg["serving"]["max_slots"], cfg["serving"]["staging_depth"]
        window = eng.sync_every
        # a full queue whose rows of one wave end a window apart, so slots
        # free one at a time: single rows merged out of staged waves, as a
        # saturated loop makes.  With one row a wave every request is as
        # short as a merge allows: its admission, not its decode, is what
        # has to be warmed.
        for k in range(slots + depth):
            submit(k, WARMUP_TOKENS + window * (k % depth))
        drain()
        marks.append(time.perf_counter())
        # an idle engine taking bursts of 1..staging_depth requests: whole
        # waves merged at once, as an open loop below capacity makes
        for m in range(1, depth + 1):
            for k in range(m):
                submit(k, WARMUP_TOKENS)
            drain()
    marks.append(time.perf_counter())
    return [b - a for a, b in zip(marks, marks[1:])]


@dataclasses.dataclass
class Window:
    drv: drive.Driver
    t0: float
    t1: float
    eng_start: dict
    eng_end: dict
    compiles: tuple[int, int]


def describe(win: Window, gcs: GcLog) -> str:
    """One line on where the window's host time went: the engine's own
    counts, the longest steps, and the interpreter's collections."""
    steps = [(a, b) for a, b in win.drv.steps if win.t0 <= a < win.t1]
    longest = sorted(steps, key=lambda s: s[0] - s[1])[:5]
    gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
    e0, e1 = win.eng_start, win.eng_end
    pauses = gcs.between(win.t0, win.t1)
    gen2 = [p for p in pauses if p[2] == 2]
    return (
        f"window: {len(steps)} steps (median "
        f"{1e3 * stats.percentile([b - a for a, b in steps], 50) if steps else 0:.1f} ms, "
        f"longest gap between steps {1e3 * max(gaps, default=0):.1f} ms), "
        f"{e1['windows'] - e0['windows']} engine windows, "
        f"{e1['prefill_calls'] - e0['prefill_calls']} prefill calls, "
        f"{e1['tokens'] - e0['tokens']} tokens; longest steps (s into the "
        f"window, ms): "
        + ", ".join(f"{a - win.t0:.2f} {1e3 * (b - a):.0f}"
                    for a, b in longest)
        + f"; gc: {len(pauses)} collections, "
        f"{1e3 * sum(p[1] for p in pauses):.1f} ms in all, {len(gen2)} of "
        f"generation 2, longest {1e3 * max((p[1] for p in pauses), default=0):.1f} ms")


def open_device(cell: registry.Cell, require_tpu: bool):
    """The devices and their peaks; no TPU (or too few chips) ends the run
    before any result is printed."""
    devices = jax.devices()
    dev = devices[0]
    if not require_tpu:
        return devices, peak_table.PEAKS["TPU v5 lite"]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0] is {dev.platform}")
    if len(devices) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chips, "
                         f"found {len(devices)}")
    from repro.kernels import ops
    if ops.default_interpret():
        raise SystemExit("Pallas kernels would run interpreted")
    return devices, peak_table.peaks_for(dev.device_kind)


def build(cell: registry.Cell, seed: int, log=print):
    """Weights from the seed, the engine, and its warm-up.  Returns
    (family modules, weights, engine)."""
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # small programs (eager scatters of the admission merges) are cached
    # too, so that a warm checkout compiles nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    fam = {part: registry.family_module(cell.family, part)
           for part in ("weights", "program", "reference", "counts")}
    t = [time.perf_counter()]
    w = fam["weights"].init(cell.cfg, seed)
    jax.block_until_ready(w)
    t.append(time.perf_counter())
    eng = fam["program"].engine(cell.cfg, w)
    t.append(time.perf_counter())
    phases = warm_up(eng, fam["program"], cell.cfg, cell.mix, seed)
    t.append(time.perf_counter())
    log(f"set-up: weights {t[1] - t[0]:.2f} s, engine {t[2] - t[1]:.2f} s, "
        f"warm-up {t[3] - t[2]:.2f} s (prefill buckets {phases[0]:.2f}, "
        f"staggered pass {phases[1]:.2f}, bursts {phases[2]:.2f})",
        file=sys.stderr)
    return fam, w, eng


def measure(eng, cell: registry.Cell, mix: dict, seed: int, seconds: float,
            program, compiles: CompileLog, *, trace_dir=None,
            uid_base: int = 0) -> Window:
    """Run the traffic's prelude, then the measured window; for an open
    loop, drain the requests due in the window (up to ``drain_s``)."""
    n_specs = traffic.capacity(mix, seconds, max_rate=50.0)
    drv = drive.Driver(eng, mix, traffic.requests(mix, n_specs), seed,
                       cell.cfg["vocab_size"], program.request, uid_base)
    drv.start()
    drv.run_until(drv.t_start + mix["prelude_s"])
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    eng_start = eng.metrics()
    t0 = time.perf_counter()
    drv.submit_until = t0 + seconds
    with jax.profiler.TraceAnnotation("window"):
        drv.run_until(t0 + seconds)
    t1 = time.perf_counter()
    eng_end = eng.metrics()
    n_compiles = compiles.between(t0, t1)
    if trace_dir is not None:
        jax.profiler.stop_trace()
    drv.stop_traffic()
    due = [r for r in drv.live if r.due_at is not None and t0 <= r.due_at < t1]
    if mix["loop"] == "open" and mix["drain_s"] > 0:
        drv.run_until(time.perf_counter() + mix["drain_s"],
                      until_done=lambda: all(r.done for r in due))
    return Window(drv, t0, t1, eng_start, eng_end, n_compiles)


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t_process: float, require_tpu: bool = True, keep_trace=None,
             root=registry.ROOT, log=print):
    cell = registry.resolve(workload, root)
    devices, peaks = open_device(cell, require_tpu)
    dev = devices[0]
    compiles = CompileLog()
    gcs = GcLog()
    fam, w, eng = build(cell, seed, log)
    trace_dir = None
    if traced:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="bench-trace-")
    win = measure(eng, cell, cell.mix, seed, seconds, fam["program"],
                  compiles, trace_dir=trace_dir)
    setup_s = win.t0 - t_process
    eng.close()
    mem = dev.memory_stats() or {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    del eng, win.drv.eng
    gc.collect()

    rec = Record(cell=cell, counts=fam["counts"], peaks=peaks, t0=win.t0,
                 t1=win.t1, setup_s=setup_s, live=win.drv.live,
                 eng_start=win.eng_start, eng_end=win.eng_end,
                 memory_peak_bytes=peak_bytes)
    breakdown = None
    if traced:
        rec.trace = trace_lib.reduce_dir(trace_dir)
        breakdown = trace_lib.breakdown(rec.trace)
        if keep_trace is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"inside the window: {win.compiles[0]} compiles, {win.compiles[1]} "
        f"programs loaded from the persistent cache", file=sys.stderr)
    log(describe(win, gcs), file=sys.stderr)
    gcs.close()

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = registry.metric_reader(m["name"], root)(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_check = time.perf_counter()
    numbers, attempted, failed = judge(rec, fam["reference"], w, seed)
    log(f"check: {time.perf_counter() - t_check:.2f} s", file=sys.stderr)
    if cell.mix["loop"] == "open":
        n_first = sum(bool(r.times) for r in rec.due_in_window())
        log(f"requests due in the window with a first token: {n_first}, "
            f"{stats.beyond(n_first, 90) if n_first else 0} beyond the 90th "
            f"percentile", file=sys.stderr)
    result = {
        "correct": all(n.ok for n in numbers),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak_bytes},
    }
    if traced:
        result["device"]["busy_s"] = rec.trace["busy_s"]
        result["device"]["window_s"] = rec.trace["window_s"]
        result["breakdown"] = breakdown
    result["checks"] = {n.name: {"value": n.value, "limit": n.limit}
                        for n in numbers}
    return result, numbers


def judge(rec: Record, reference, w, seed: int):
    """The numbers compared for ``correct``, and the request counts.  An
    open loop with a drain also needs every request due in the window
    finished by its end; one offered above capacity (no drain) leaves a
    queue by design."""
    mix, cfg, limits = rec.cell.mix, rec.cell.cfg, rec.cell.limits
    finished = [r for r in rec.live if r.done]
    short = sum(len(r.tokens) != r.spec.output_len for r in finished)
    if mix["loop"] == "open":
        scope = rec.due_in_window()
    else:
        scope = [r for r in rec.live if r.submitted_at is not None
                 and r.submitted_at < rec.t1
                 and (not r.times or r.times[-1] >= rec.t0)]
    failed = 0
    if mix["loop"] == "open" and mix["drain_s"] > 0:
        failed = sum(not r.done for r in scope)
    chosen = check.sample(finished, mix["sample"], seed)
    widest, n_tok = check.widest_gap(reference, cfg, w, chosen)
    numbers = [
        check.Number("logit_gap", widest["reference"],
                     limits["logit_gap"]["limit"]),
        check.Number("none_compared", float(n_tok == 0), 0.0),
        check.Number("outputs_short", float(short), 0.0),
        check.Number("unfinished", float(failed), 0.0),
    ]
    return numbers, len(scope), failed
