"""Host-side plumbing for the overlapped serving pipeline.

The overlapped engine keeps up to ``pipeline_depth`` decode windows in
flight and blocks the host only on the *trailing* window's packed status
array — everything else the host used to do synchronously at a window
boundary is either expressed as device dataflow (slot merges chained
onto the leading window's output futures) or deferred onto a worker:

  * ``InflightWindow`` is the per-dispatch record: the output futures a
    later boundary will harvest, plus the host-side snapshot (slot ->
    request map, occupancy/queue depth, dispatch index) that makes the
    harvest interpretable after the scheduler has moved on.
  * ``TokenBacklog`` is a single worker thread draining a FIFO of
    closures (MaxText's ``detokenize_backlog`` shape): per-window token
    transfer + detokenize + stream callbacks run there, so the main loop
    never blocks on Python-side token handling.  Exceptions are captured
    and re-raised on the submitting thread at the next ``put``/``flush``
    /``close`` so a crashed worker fails the run instead of hanging it.
  * ``AdmissionWorker`` is the admission-prefill thread: it pops
    queue-head requests (``StagedWave`` granularity) and dispatches
    their wave prefill + first-token sample as DEVICE FUTURES, so a long
    prompt's prefill overlaps in-flight decode instead of stalling the
    dispatch loop.  The worker never mutates scheduler/pool/mirror
    state — everything host-visible merges on the main thread at a
    window boundary, which is what keeps streams token-for-token equal
    to the sync engine (prefill is row-independent and the first-token
    sample is batch-invariant per row, so wave composition is free).

Ordering contract: items are processed strictly in put() order by one
worker, so per-request token order is exactly dispatch order — this is
what keeps overlapped streams token-for-token identical to the sync
engine's.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Callable

__all__ = ["AdmissionWorker", "InflightWindow", "PreemptedRecord",
           "StagedEntry", "StagedWave", "TokenBacklog"]

_STOP = object()


@dataclasses.dataclass
class InflightWindow:
    """One dispatched-but-unharvested decode window.

    ``status`` is the only array the boundary blocks on: a packed 1-D
    int32 concatenation of (active, buffer position[, gen][, accept/
    propose sums][, swap seq/slot], active-iteration count) built on
    device at dispatch, so harvesting costs one transfer instead of one
    per leaf; the harvest parses it positionally by the same layout.  ``toks`` /
    ``emits`` (and the spec counters) are handed to the backlog worker,
    which transfers them off the critical path.  ``slot_reqs`` snapshots
    the slot -> request map at dispatch: the scheduler may re-assign a
    slot at a later boundary before this window is harvested, and tokens
    must be credited to the request that actually occupied the slot.
    """

    index: int                      # dispatch sequence number
    status: Any                     # (R, B) int32 device future
    toks: Any                       # (B, steps[, S]) token futures
    emits: Any                      # (B, steps[, S]) emit-mask futures
    slot_reqs: list                 # slot -> Request at dispatch time
    occ: int                        # scheduler occupancy at dispatch
    qd: int                         # scheduler queue depth at dispatch
    overlapped: bool                # dispatched before prior completed?
    acc: Any = None                 # spec: accepted-count future
    prop: Any = None                # spec: proposed-count future
    n_active: Any = None            # (steps,) stepping-slot counts future
    stage_entries: list | None = None  # continuous: stage table snapshot


@dataclasses.dataclass
class StagedWave:
    """One admission wave prepared off the dispatch path: prompts
    prefilled and first tokens sampled as device futures, awaiting its
    main-thread merge (slot placement or stage-row scatter).  ``merged``
    counts the leading requests already consumed — a wave larger than
    the free slots (or page budget) merges across several boundaries,
    head-of-line FIFO throughout."""

    reqs: list                      # policy-ordered run of staged Requests
    first_lens: list                # wave-prefill coverage per request
    specs: list                     # resolved SamplingParams per request
    keys0: Any                      # (n, 2) uint32 base PRNG keys (host)
    eos: Any                        # (n,) int32 eos ids (host)
    full: Any                       # (n,) bool whole-prompt-prefilled
    ks: Any                         # (W, 2, 2) split keys (device)
    first: Any                      # (W,) first sampled tokens (device)
    new_cache: Any                  # slot-major prefill cache (device)
    draft_new_cache: Any = None     # layer-draft twin (device)
    merged: int = 0                 # leading reqs already merged
    # prefill-skip (prefix-affinity): request i rides prefill row
    # rows[i] of the W-bucketed arrays, or rows[i] == -1 when its whole
    # first chunk is covered by resident registry pages and it admits
    # with ZERO prefill — cur starts at the shared coverage and the
    # prompt remainder streams through the decode loop's ingest buffer.
    # keys0/eos/full/specs are per-request (length n); ks/first/
    # new_cache are per-prefill-row (width W <= n).  None rows => all
    # requests prefill (one row each, pre-refactor layout).
    rows: list | None = None


@dataclasses.dataclass
class StagedEntry:
    """One request scattered into the device-side staging queue
    (continuous batching): the host-known carry row it was staged with,
    kept until a harvested window confirms the in-scan install so the
    mirror/scheduler can be updated retroactively."""

    req: Any
    host_row: dict                  # carry-leaf name -> per-slot row (np)
    pending: Any                    # un-ingested prompt tail (np) or None
    pages: list | None              # paged: physical pages already owned
    seq: int                        # staging sequence number (device key)
    keys0: Any                      # (2,) uint32 mirror placeholder
    full: bool                      # whole prompt covered by the prefill


@dataclasses.dataclass
class PreemptedRecord:
    """Everything needed to resurrect a preempted slot: the carry row as
    it stood at eviction (sampling state, cur, ring buffer columns), the
    un-ingested prompt tail, and the pages it held with their alloc
    stamps.  On re-admission, pages whose stamp is unchanged still hold
    the victim's content (resurrect/retain); recycled ones are rebuilt
    by re-prefilling the already-fed token history over just those
    pages."""

    req: Any
    host_row: dict                  # carry-leaf name -> per-slot row (np)
    pending: Any                    # un-ingested prompt tail (np) or None
    pages: list                     # physical pages held at eviction
    stamps: list                    # pool alloc stamp per page at eviction
    cur: int                        # fed-token count at eviction
    keys0: Any                      # (2,) uint32 base PRNG key (mirror)


class TokenBacklog:
    """A FIFO of host-side work items drained by one daemon thread.

    Items are zero-argument callables (closures over device futures).
    The thread is started lazily on the first ``put`` so a sync engine
    never spawns it.  ``flush`` blocks until every queued item has run;
    ``close`` flushes, stops the thread, and joins it — both re-raise
    the first exception a work item threw.
    """

    def __init__(self, name: str = "token-backlog"):
        self._name = name
        self._q: queue.Queue = queue.Queue()
        self._thread: threading.Thread | None = None
        self._err: BaseException | None = None
        self._closed = False

    @property
    def started(self) -> bool:
        return self._thread is not None

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _ensure_thread(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._drain, name=self._name, daemon=True)
            self._thread.start()

    def _drain(self):
        while True:
            item = self._q.get()
            try:
                if item is _STOP:
                    return
                if self._err is None:
                    item()
            except BaseException as e:  # noqa: BLE001 — repo rt on main thread
                self._err = e
            finally:
                self._q.task_done()

    def _reraise(self):
        if self._err is not None:
            err, self._err = self._err, None
            raise RuntimeError(
                f"{self._name} worker failed while draining") from err

    def put(self, item: Callable[[], None]):
        if self._closed:
            raise RuntimeError(f"{self._name} is closed")
        self._reraise()
        self._ensure_thread()
        self._q.put(item)

    def flush(self):
        """Block until every item queued so far has been processed."""
        if self._thread is not None:
            self._q.join()
        self._reraise()

    def close(self):
        """Flush, stop, and join the worker.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        if self._thread is not None:
            self._q.join()
            self._q.put(_STOP)
            self._thread.join()
            self._thread = None
        self._reraise()


class AdmissionWorker:
    """Admission-prefill worker: one daemon thread turning queue-head
    requests into ``StagedWave``s of device futures.

    Division of labor (the thread-safety contract):

      * ``take(max_n)`` — engine-provided, pops requests off the
        scheduler queue under the engine's admission lock (the only
        scheduler surface the worker touches).
      * ``prepare(reqs) -> StagedWave`` — engine-provided, DEVICE
        dispatch only: wave prefill + first-token sample.  jax dispatch
        is thread-safe; nothing host-visible is mutated.
      * the main thread drains prepared waves via ``poll()`` at window
        boundaries and owns all scheduler/pool/mirror mutation.

    ``capacity`` bounds look-ahead: the worker stages at most that many
    requests beyond what the main thread has merged, so prefilled-but-
    unmerged cache trees can't grow without bound.  Errors are captured
    and re-raised on the main thread at the next ``poll``/``close``."""

    def __init__(self, take: Callable[[int], list],
                 prepare: Callable[[list], Any],
                 name: str = "admission-prefill"):
        self._take = take
        self._prepare = prepare
        self._name = name
        self._cv = threading.Condition()
        self._out: list = []
        self._err: BaseException | None = None
        self._capacity = 0
        self._busy = False
        self._stop = False
        self._thread: threading.Thread | None = None
        self.waves_prepared = 0

    @property
    def started(self) -> bool:
        return self._thread is not None

    @property
    def busy(self) -> bool:
        """True while the worker holds un-polled output or is preparing."""
        with self._cv:
            return self._busy or bool(self._out)

    def _ensure_thread(self):
        if self._thread is None and not self._stop:
            self._thread = threading.Thread(
                target=self._run, name=self._name, daemon=True)
            self._thread.start()

    def kick(self, capacity: int):
        """Main thread: update the staging budget and wake the worker.
        Called at submit time and after each boundary merge."""
        with self._cv:
            self._capacity = max(0, capacity)
            if self._capacity > 0:
                self._ensure_thread()
            self._cv.notify_all()

    def _run(self):
        while True:
            with self._cv:
                while not self._stop and self._capacity <= 0:
                    self._cv.wait()
                if self._stop:
                    return
                cap = self._capacity
            try:
                reqs = self._take(cap)
                if not reqs:
                    with self._cv:
                        # nothing queued: sleep until the next kick
                        # (capacity will be re-announced then)
                        self._capacity = 0
                        self._busy = False
                        self._cv.notify_all()
                    continue
                with self._cv:
                    self._busy = True
                    self._capacity -= len(reqs)
                wave = self._prepare(reqs)
                with self._cv:
                    self._out.append(wave)
                    self.waves_prepared += 1
                    self._busy = False
                    self._cv.notify_all()
            except BaseException as e:  # noqa: BLE001 — reraised on main
                with self._cv:
                    self._err = e
                    self._busy = False
                    self._stop = True
                    self._cv.notify_all()
                return

    def poll(self) -> list:
        """Main thread: drain every prepared wave; re-raises a worker
        crash (once) so a failed prefill fails the run, not hangs it."""
        with self._cv:
            out, self._out = self._out, []
            err, self._err = self._err, None
        if err is not None:
            raise RuntimeError(
                f"{self._name} worker failed while staging") from err
        return out

    def wait(self, timeout: float | None = None) -> bool:
        """Main thread: block until a prepared wave (or a crash) is
        available, or the timeout lapses.  Returns True when ``poll()``
        would yield something.  Blocks through the kicked-but-not-yet-
        scheduled gap too — the caller checks there is genuinely work
        upstream before waiting."""
        with self._cv:
            self._cv.wait_for(
                lambda: bool(self._out) or self._err is not None,
                timeout=timeout)
            return bool(self._out) or self._err is not None

    def close(self):
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
