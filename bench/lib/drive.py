"""Drives the engine with a traffic mix through ``submit()`` and
``step()``, and records every request's due time, submit time and
per-token host stamps (``Request.on_token``).

Open loop: a generator thread submits each request when it is due, on the
mix's schedule, whatever the server does; a request's latency runs from
when it was due, so a stalled generator or server shows.  Closed loop:
each client sends its next request when its last one finished; the main
thread submits it at the next boundary, and its due time is that moment.
The main thread alone calls ``step()``."""

from __future__ import annotations

import collections
import dataclasses
import threading
import time

import jax

from bench.lib import traffic

IDLE_SLEEP_S = 0.0005


@dataclasses.dataclass
class Live:
    spec: traffic.Spec
    prompt: object = None
    due_at: float | None = None          # perf_counter seconds
    submitted_at: float | None = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.tokens) >= self.spec.output_len


class Driver:
    def __init__(self, engine, mix: dict, specs, seed: int, vocab: int,
                 make_request, uid_base: int = 0):
        self.eng, self.mix, self.seed, self.vocab = engine, mix, seed, vocab
        self.specs = list(specs)
        self.make_request = make_request
        self.uid_base = uid_base
        self.live: list[Live] = []
        self._next = 0
        self._done = collections.deque()
        self._stop = threading.Event()
        self._thread = None
        self.submit_until = float("inf")
        self.t_start = None
        self.steps: list[tuple[float, float]] = []   # host (start, end)

    # -- requests --------------------------------------------------------
    def _new(self) -> Live:
        if self._next >= len(self.specs):
            raise RuntimeError("traffic exhausted: raise traffic.capacity")
        spec = self.specs[self._next]
        self._next += 1
        live = Live(spec, traffic.prompt_tokens(self.seed, spec, self.vocab))
        self.live.append(live)
        return live

    def _on_token(self, live: Live):
        def cb(req, tok):
            live.times.append(time.perf_counter())
            live.tokens.append(int(tok))
            if len(live.tokens) == live.spec.output_len:
                self._done.append(live)
        return cb

    def _submit(self, live: Live, due_at: float):
        req = self.make_request(self.uid_base + live.spec.index, live.prompt,
                                live.spec.output_len, self._on_token(live))
        live.due_at = due_at
        with jax.profiler.TraceAnnotation("submit"):
            live.submitted_at = time.perf_counter()
            self.eng.submit(req)

    def _generate(self):
        while not self._stop.is_set():
            with jax.profiler.TraceAnnotation("generator"):
                live = self._new()
            due = self.t_start + live.spec.due
            if due >= self.submit_until:
                self.live.pop()
                return
            while True:
                wait = due - time.perf_counter()
                if wait <= 0 or self._stop.wait(min(wait, 0.05)):
                    break
            if self._stop.is_set():
                self.live.pop()
                return
            self._submit(live, due)

    # -- loop ------------------------------------------------------------
    def start(self):
        """Start the traffic: the open loop's generator thread, or every
        closed-loop client's first request."""
        self.t_start = time.perf_counter()
        if self.mix["loop"] == "open":
            self._thread = threading.Thread(target=self._generate,
                                            name="bench-generator",
                                            daemon=True)
            self._thread.start()
        else:
            for _ in range(self.mix["clients"]):
                self._submit(self._new(), time.perf_counter())

    def _resubmit(self):
        while self._done:
            self._done.popleft()
            now = time.perf_counter()
            if now < self.submit_until:
                self._submit(self._new(), now)

    def run_until(self, t_end: float, *, until_done=None):
        """Step the engine until ``t_end``, or earlier once
        ``until_done()`` holds."""
        while time.perf_counter() < t_end:
            if until_done is not None and until_done():
                return
            if self.mix["loop"] == "closed":
                self._resubmit()
            queued = self.eng.unfinished
            if queued["queued"] == 0 and queued["in_flight"] == 0:
                time.sleep(IDLE_SLEEP_S)
                continue
            with jax.profiler.TraceAnnotation("step"):
                t = time.perf_counter()
                self.eng.step()
                self.steps.append((t, time.perf_counter()))

    def stop_traffic(self):
        self.submit_until = min(self.submit_until, time.perf_counter())
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            if self._thread.is_alive():
                raise RuntimeError("generator thread did not stop")
