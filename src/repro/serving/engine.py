"""Executor: continuous batching over the latent KV cache with a fused,
device-resident multi-token decode loop.

The serving subsystem is split three ways:

  scheduler.py  admission policy, slot lifecycle, chunked prefill (host)
  sampler.py    on-device temperature / top-k / top-p / greedy sampling
  pipeline.py   overlapped-serving plumbing: in-flight window records +
                the backlog worker thread that drains token handling
  engine.py     this file — the executor.  One ``jax.lax.scan`` window
                runs ``sync_every`` decode steps entirely on device
                (feed -> decode_step -> sample -> append -> termination),
                carrying last-token, cur, active-mask, PRNG keys, ingest
                buffers and done-flags as device state.  The host is
                touched once per window: harvest emitted tokens, retire
                finished slots, refill prompt-ingest buffers, and run
                admission (batched, shape-bucketed wave prefill).  With
                ``overlap=True`` that boundary work pipelines against the
                NEXT window already running on device (double buffering),
                and with ``aot=True`` every executable is compiled at
                construction.

The engine is MESH-NATIVE: ``Engine(mesh=...)`` device-puts params via
``sharding.rules.param_specs`` and jits the window with explicit
``in_shardings``/``out_shardings`` — cache rings sharded slot x sequence
per ``CACHE_RULES`` (the softmax over the sharded S axis becomes a psum
LSE merge; the latent ``A @ z_v`` contraction psums only a tiny
``(B, H, r_v)``, the low-rank win compounding with tensor parallelism),
and the rest of the device carry (last-token, cur, active, per-slot PRNG
keys, ingest buffer) sharded on the slot axis per ``carry_specs``.
Without a mesh the engine runs on a degenerate (1, 1) mesh — the sharded
window IS the single-device path, not a branch.

Chunked prefill rides the same loop: a long prompt's first
``prefill_chunk`` tokens go through the wave prefill; the remainder sits
in a per-slot device buffer and is *fed* through decode steps (cache
writes at the token's true position, sampled outputs discarded until the
final prompt token), so decode-phase slots keep emitting between chunks.

Speculative decoding (``spec_depth > 0``) upgrades each window iteration
from one token to up to ``spec_depth + 1``: a draft (prompt-lookup
n-gram, or the target's own first K layers — see ``serving.draft``)
proposes ``spec_depth`` tokens, and ONE multi-token ``T.verify_step``
scores all proposals against target logits.  Acceptance is the
deterministic specialization of accept/reject-with-residual-resampling:
the per-slot sampler (policy + key stream) is a deterministic function,
so a proposal is accepted iff it equals the token the target would have
emitted, and the first rejection emits the target's own draw (the
residual collapses onto it).  Keys still advance once per *emitted*
token and rejected proposals never touch any ring, so token streams are
invariant to speculation depth — the draft buys step-count, never
changes output.  The accept mask and fed-token history ride the same
slot-sharded device carry (``rules.carry_specs``); no new collectives.

With ReCalKV enabled the resident cache is the *latent* ring — at 50%
compression the same HBM holds 2x the slots (the paper's serving win).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
import time
import warnings
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import single_device_mesh
from repro.models import kv_cache as KC
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving import draft as D
from repro.serving import sampler as S
from repro.serving.draft import DraftSpec
from repro.serving.pages import PagePool, PrefixRegistry, prefix_key
from repro.serving.pipeline import (AdmissionWorker, InflightWindow,
                                    PreemptedRecord, StagedEntry, StagedWave,
                                    TokenBacklog)
from repro.serving.policy import AdmissionPolicy, get_policy
from repro.serving.sampler import SamplingParams
from repro.serving.scheduler import Request, Scheduler
from repro.sharding import rules as R

__all__ = ["Engine", "Request", "SamplingParams", "DraftSpec"]

# Sentinel for an empty device-side staging row: the in-scan install
# picks argmin(seq), so the max int32 sorts every real (monotonically
# assigned) staging sequence number ahead of every free row.
STAGE_FREE = np.iinfo(np.int32).max

# Stage spans whose wall-clock also accrues to ``metrics()["profile"]``,
# under these keys; the others (engine.step, engine.finish, engine.merge,
# engine.flush) nest in or around them and appear on the profiler only.
PROFILE_KEYS = {"engine.dispatch": "dispatch", "engine.harvest": "harvest",
                "engine.bookkeep": "bookkeep",
                "engine.admission_stage": "admission_stage",
                "backlog.drain": "backlog_drain",
                "admission.prepare": "admission_worker"}

# slot refills kept for metrics() (see Engine._note_refills)
REFILLS_KEPT = 4096

# carry leaves an admission merge picks on device from the sampled first
# token; every other leaf's row is known to the host
DEVICE_ROWS = ("tok", "act", "keys")


def _ids(xs) -> str:
    """A span argument listing ints.  Space-joined: TraceMe's argument
    encoding splits on commas."""
    return " ".join(str(x) for x in xs)


def _merge_rows(pool_cache, new_cache, slots: jax.Array, rows: jax.Array):
    """Copy ``new_cache`` batch row ``rows[i]`` into ``pool_cache`` slot
    ``slots[i]``, one row at a time; an out-of-range (pad) slot drops.
    Traced inside the admission merge: with the pool donated every row
    lands in place, and no gathered copy of the rows is materialized (a
    batched scatter holds one as large as the rows, a temporary the
    allocator can make the host wait for).

    Batch is dim 0 for prefix/suffix caches but dim 1 under the scanned
    "blocks" subtree (leading dim = pattern periods)."""
    def row(i, pool):
        s = jax.lax.dynamic_slice_in_dim(slots, i, 1)
        r = jax.lax.dynamic_slice_in_dim(rows, i, 1)

        def one(path, dst, src):
            if getattr(path[0], "key", None) == "blocks":
                return dst.at[:, s].set(src[:, r].astype(dst.dtype),
                                        mode="drop")
            return dst.at[s].set(src[r].astype(dst.dtype), mode="drop")
        return jax.tree_util.tree_map_with_path(one, pool, new_cache)
    return jax.lax.fori_loop(0, slots.shape[0], row, pool_cache)


def _admission_merge(cache, dcache, carry, new, dnew, first, ks, ix):
    """Everything one admission merge puts on the device, as one program:
    prefill rows ``ix["rows"]`` of ``new`` into ``cache`` slots
    ``ix["slots"]``, the layer draft's rows (``ix["drows"]`` into
    ``ix["dslots"]``) into ``dcache``, and every carry leaf's row into
    ``carry``: the host-known ones from ``ix["host"]``, tok/act/keys
    picked from the first-token futures (``first``, ``ks``) by
    ``ix["full"]``, ``ix["eos"]``, ``ix["left"]`` and ``ix["keys0"]``, as
    the window's own first-token checks would.  A part left None is not
    merged; ``first`` is None for a wave that prefilled nothing (every
    row starts from its base key, feeding from the ingest buffer).  Row
    vectors are padded with an out-of-range slot, which drops."""
    if cache is not None:
        cache = _merge_rows(cache, new, ix["slots"], ix["rows"])
    if dcache is not None:
        dcache = _merge_rows(dcache, dnew, ix["dslots"], ix["drows"])
    if carry is not None:
        rows = dict(ix["host"])
        full = ix["full"]
        if first is None:
            rows["tok"] = jnp.zeros(full.shape, jnp.int32)
            rows["act"] = jnp.ones(full.shape, bool)
            rows["keys"] = ix["keys0"]
        else:
            f = first[ix["rows"]]
            rows["tok"] = jnp.where(full, f, 0)
            # a full-prompt row can die at its very first token (eos, or
            # an exhausted budget) — the checks the window applies
            rows["act"] = jnp.where(full, (f != ix["eos"]) & (ix["left"] > 0),
                                    True)
            rows["keys"] = jnp.where(full[:, None], ks[ix["rows"]][:, 0],
                                     ix["keys0"])
        carry = {k: v.at[ix["slots"]].set(rows[k].astype(v.dtype),
                                          mode="drop")
                 for k, v in carry.items()}
    return cache, dcache, carry


def _merge_slot_paged(pool_cache, new_cache, rows: jax.Array,
                      cols: jax.Array, phys: jax.Array, page_size: int):
    """Scatter prefill rows into the PAGED pool: ``new_cache`` is
    slot-major (W, Lr, ...); tile (rows[t], cols[t]) — slot row, logical
    page index — lands in physical page ``phys[t]`` of the page-major
    pool (n_pages, page_size, ...).  Shared prefix pages are simply
    absent from (rows, cols, phys): their content is already resident,
    so admission never rewrites them (copy-on-write by omission)."""
    def one(path, pool, new):
        key0 = getattr(path[0], "key", None)
        ps = page_size
        if key0 == "blocks":
            n_per, W = new.shape[0], new.shape[1]
            tiles = new.reshape((n_per, W, new.shape[2] // ps, ps)
                                + new.shape[3:])[:, rows, cols]
            return pool.at[:, phys].set(tiles.astype(pool.dtype))
        W = new.shape[0]
        tiles = new.reshape((W, new.shape[1] // ps, ps)
                            + new.shape[2:])[rows, cols]
        return pool.at[phys].set(tiles.astype(pool.dtype))
    return jax.tree_util.tree_map_with_path(one, pool_cache, new_cache)


def _bucket(n: int, cap: int) -> int:
    """Round up to a power of two, capped: the (wave, prompt-len) shapes a
    long-running engine sees collapse to O(log) values instead of one jit
    retrace per distinct admission wave."""
    return min(max(1, 1 << (n - 1).bit_length()), max(cap, n))


class Engine:
    """Slot-based continuous-batching executor.

    ``sync_every`` sets the decode window: tokens decoded per
    host round-trip.  Large windows amortize dispatch and host syncs
    (throughput); small windows tighten admission latency for queued
    requests and finished-slot turnaround (latency).
    ``prefill_chunk`` bounds how much prompt one admission wave prefills
    at once; the remainder streams through the decode loop.
    ``mesh`` is a ("data", "model") jax Mesh (see ``launch.mesh``); the
    slot axis shards over "data", the cache ring's sequence axis over
    "model".  Default: a (1, 1) single-device mesh.
    ``spec_depth`` turns on speculative decoding: up to that many draft
    tokens verified per window iteration (0 disables).  ``draft`` picks
    the proposer — "ngram" (default) or "layers:K" (self-draft from the
    target's first K layers); token streams are invariant to both knobs.
    ``overlap`` switches the step loop to the double-buffered pipeline:
    two windows in flight, the host blocking only on the *trailing*
    window's packed status, token handling on a backlog worker thread,
    and admission prefill dispatched concurrently with in-flight decode.
    Token streams are invariant to ``overlap`` (the async↔sync parity
    contract).  ``aot`` lowers + compiles the fused window, every
    reachable power-of-two (wave, prompt-len) prefill bucket and the
    admission merges at construction, so the first request pays load
    time, not trace time.
    ``pipeline_depth`` generalizes the double buffer to N windows in
    flight; ``continuous`` adds the device-side staging queue + in-scan
    slot swap; ``admission_thread`` moves wave prefill staging onto a
    worker thread (default: on whenever overlap is); ``adaptive_spec``
    degrades cold-draft slots to plain decode at window boundaries;
    ``pin_prefixes`` pins the K hottest registered prefix pages against
    pool recycling.

    The host's stages (``engine.*`` on the calling thread, ``admission.*``
    and ``backlog.*`` on the workers) are always spans on the profiler's
    clock (``jax.profiler.TraceAnnotation``); the main ones also sum into
    ``metrics()["profile"]``.
    """

    # adaptive speculation: degrade a slot once it has proposed at least
    # MIN_PROPOSED draft tokens with an accept rate below ACCEPT_FLOOR
    ADAPTIVE_MIN_PROPOSED = 8
    ADAPTIVE_ACCEPT_FLOOR = 0.25

    def __init__(self, cfg: ModelConfig, params, *, max_slots: int,
                 max_len: int, source: jax.Array | None = None,
                 backend: str | None = None,
                 sampling: SamplingParams | None = None,
                 sync_every: int = 8, prefill_chunk: int | None = None,
                 mesh: jax.sharding.Mesh | None = None,
                 spec_depth: int = 0,
                 draft: str | DraftSpec | None = None,
                 cache_layout: str = "ring",
                 page_size: int | None = None,
                 n_pages: int | None = None,
                 overlap: bool = False,
                 aot: bool = False,
                 pipeline_depth: int = 2,
                 continuous: bool = False,
                 admission_thread: bool | None = None,
                 pin_prefixes: int = 0,
                 adaptive_spec: bool = False,
                 policy: str | AdmissionPolicy | None = None,
                 lazy_pages: bool = False,
                 staging_depth: int | None = None):
        if backend is not None:
            cfg = dataclasses.replace(cfg, attn_backend=backend)
        if sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        if cache_layout not in ("ring", "paged"):
            raise ValueError(f"cache_layout={cache_layout!r}: expected "
                             f"'ring' or 'paged'")
        if cache_layout == "ring" and (page_size is not None
                                       or n_pages is not None):
            raise ValueError(
                "page_size/n_pages only apply to cache_layout='paged'")
        self.cache_layout = cache_layout
        self.page_size = self.n_pages = None
        self._pages: PagePool | None = None
        if cache_layout == "paged":
            kinds = set(cfg.expanded_layers())
            bad = sorted(k for k in kinds
                         if k in ("mamba", "rglru", "cross", "attn_cross"))
            if bad:
                raise ValueError(
                    f"cache_layout='paged' needs position-addressed "
                    f"self-attention rings; {cfg.name} has {bad} blocks")
            short = sorted(k for k in kinds
                           if cfg.cache_len(k, max_len) != max_len)
            if short:
                raise ValueError(
                    f"cache_layout='paged' needs full-length rings; "
                    f"{short} blocks keep ring length < max_len={max_len}")
            if page_size is None:
                page_size = next(p for p in (16, 8, 4, 2, 1)
                                 if max_len % p == 0)
            if page_size < 1 or max_len % page_size:
                raise ValueError(f"page_size={page_size} must be >= 1 and "
                                 f"divide max_len={max_len}")
            n_sp = max_len // page_size
            if n_pages is None:
                # ring-equivalent capacity plus the reserved null page;
                # smaller pools trade concurrency headroom for memory
                n_pages = max_slots * n_sp + 1
            if n_pages < n_sp + 1:
                raise ValueError(
                    f"n_pages={n_pages} cannot hold one full-length "
                    f"request ({n_sp} pages + the reserved null page)")
            self.page_size, self.n_pages = page_size, n_pages
            self._pages = PagePool(n_pages)
            self._prefixes = PrefixRegistry()
            self._slot_pages: list[list[int]] = [[] for _ in
                                                 range(max_slots)]
            # The pallas decode path tiles the ring at attn_block; pin it
            # to page_size so the paged kernel's page-per-tile walk is
            # bitwise-identical to the ring kernel's tile sequence (the
            # paged <-> ring parity contract).
            cfg = dataclasses.replace(cfg, attn_block=page_size)
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        if pipeline_depth != 2 and not overlap:
            raise ValueError("pipeline_depth is the overlapped engine's "
                             "in-flight window budget; set overlap=True")
        if continuous and not overlap:
            raise ValueError("continuous batching (in-window slot swap) "
                             "requires overlap=True")
        if admission_thread and not overlap:
            raise ValueError("admission_thread requires overlap=True (the "
                             "sync engine admits inline by definition)")
        if continuous:
            parsed = DraftSpec.parse(draft)
            if parsed is not None and parsed.kind == "layers":
                raise ValueError(
                    "continuous batching is incompatible with the layer-"
                    "fraction draft: its slot-major ring has no staged "
                    "twin for the in-scan install")
        if adaptive_spec and spec_depth == 0:
            raise ValueError("adaptive_spec degrades speculative depth "
                             "per slot; it needs spec_depth > 0")
        if pin_prefixes < 0:
            raise ValueError("pin_prefixes must be >= 0")
        if pin_prefixes > 0 and cache_layout != "paged":
            raise ValueError("pin_prefixes pins page-pool prefixes; it "
                             "needs cache_layout='paged'")
        resolved_policy = get_policy(policy)
        if resolved_policy.groups_by_prefix and cache_layout != "paged":
            raise ValueError(
                f"policy={resolved_policy.name!r} groups admissions by "
                f"shared prompt prefix; it needs cache_layout='paged' "
                f"(the prefix registry lives in the page pool)")
        if lazy_pages:
            if cache_layout != "paged":
                raise ValueError("lazy_pages defers page reservation; it "
                                 "needs cache_layout='paged'")
            if continuous:
                raise ValueError(
                    "lazy_pages is incompatible with continuous batching: "
                    "the in-scan installer hands slots over mid-window, so "
                    "boundary-granular page top-up/preemption cannot tell "
                    "whose reach it is covering")
            parsed = DraftSpec.parse(draft)
            if parsed is not None and parsed.kind == "layers":
                raise ValueError(
                    "lazy_pages is incompatible with the layer-fraction "
                    "draft: the draft's slot-major ring is not paged, so "
                    "a preempted slot's draft state cannot be rebuilt")
        if staging_depth is not None and staging_depth < 1:
            raise ValueError("staging_depth must be >= 1")
        if spec_depth < 0:
            raise ValueError("spec_depth must be >= 0")
        if spec_depth > 0:
            bad = [k for k in cfg.expanded_layers() if k in ("mamba",
                                                             "rglru")]
            if bad:
                raise ValueError(
                    f"spec_depth > 0 needs position-addressed caches; "
                    f"{cfg.name} has recurrent {sorted(set(bad))} blocks "
                    f"whose state cannot roll back a rejected token")
        self.cfg = cfg
        self.B, self.max_len = max_slots, max_len
        self.source = source
        self.sampling = sampling or S.GREEDY
        self.sync_every = sync_every
        self.spec_depth = spec_depth
        parsed_draft = DraftSpec.parse(draft)
        if parsed_draft is not None and spec_depth == 0:
            raise ValueError(
                f"draft={draft!r} requires spec_depth > 0 — a draft with "
                f"no speculation depth would be silently ignored")
        self.draft = (parsed_draft or DraftSpec("ngram")
                      if spec_depth > 0 else None)
        self.mesh = mesh if mesh is not None else single_device_mesh()
        # Backend telemetry + loud fallback: a requested pallas backend
        # still routes some layer kinds through einsum (absorbed-MLA
        # attention, cross-attention halves) — warn once so
        # backend="pallas" is never silently a no-op, and record what
        # the decode/verify steps will actually run for metrics().
        if cfg.attn_backend == "pallas":
            fallback = KC.pallas_fallback_kinds(cfg)
            if fallback:
                warnings.warn(
                    f"backend='pallas': layer kinds {fallback} have no "
                    f"pallas decode kernel and fall back to einsum",
                    RuntimeWarning, stacklevel=2)
        n_seq_shards = R.kernel_seq_shards(self.mesh)
        seq_cols = page_size if page_size is not None else max_len
        self._decode_kernel_sharded = bool(
            cfg.attn_backend == "pallas" and cfg.mla is None
            and n_seq_shards > 1 and seq_cols % n_seq_shards == 0)
        self._verify_backend = (
            None if spec_depth == 0
            else "pallas" if (cfg.attn_backend == "pallas"
                              and cfg.mla is None)
            else "einsum")
        # slots-per-shard admission locality: only meaningful when the
        # slot axis actually shards (divisible); else one logical shard
        n_slot_shards = math.prod(
            self.mesh.shape[a] for a in R.batch_axes(self.mesh))
        if n_slot_shards < 1 or max_slots % n_slot_shards:
            n_slot_shards = 1
        self.scheduler = Scheduler(max_slots, max_len,
                                   prefill_chunk=prefill_chunk,
                                   slot_shards=n_slot_shards,
                                   policy=resolved_policy)
        self.policy = self.scheduler.policy
        self.policy.configure(
            page_size=self.page_size,
            registry=self._prefixes if self._pages is not None else None)
        self.lazy_pages = bool(lazy_pages)
        # staging look-ahead: how many requests may sit prefilled-but-
        # unmerged ahead of free slots (satellite: decoupled from B)
        self.staging_depth = (int(staging_depth) if staging_depth is not None
                              else 2 * max_slots)
        # Mesh-native placement: params by PARAM_RULES (TP heads / FSDP),
        # the pooled cache rings by CACHE_RULES (slot x sequence).
        param_shardings = R.to_named(
            R.param_specs(params, self.mesh, grains=R.head_grains(cfg)),
            self.mesh)
        self.params = jax.device_put(params, param_shardings)
        cache = T.init_decode_cache(
            cfg, max_slots, max_len,
            pages=None if self._pages is None
            else (self.n_pages, self.page_size))
        self._cache_shardings = R.to_named(
            R.cache_specs(cache, self.mesh), self.mesh)
        self.cache = jax.device_put(cache, self._cache_shardings)
        # Layer-fraction draft: a VIEW over the target's first K layers
        # (no new weights) with its own — much smaller — ring cache,
        # sharded by the same rules and carried through the window.
        self.draft_params = self.draft_cache = None
        self._draft_cfg = self._draft_cache_shardings = None
        draft_param_shardings = None
        if self.draft is not None and self.draft.kind == "layers":
            dcfg, dparams = D.make_layer_draft(cfg, self.params,
                                               self.draft.layers)
            self._draft_cfg = dcfg
            draft_param_shardings = R.to_named(
                R.param_specs(dparams, self.mesh,
                              grains=R.head_grains(dcfg)), self.mesh)
            self.draft_params = jax.device_put(dparams,
                                               draft_param_shardings)
            dcache = T.init_decode_cache(dcfg, max_slots, max_len)
            self._draft_cache_shardings = R.to_named(
                R.cache_specs(dcache, self.mesh), self.mesh)
            self.draft_cache = jax.device_put(
                dcache, self._draft_cache_shardings)
        self.finished: list[Request] = []
        # per-slot host mirror of the device loop state (synced once per
        # window); the cache itself never leaves the device
        W = prefill_chunk or 1
        self._st: dict[str, np.ndarray] = {
            "tok": np.zeros(max_slots, np.int32),
            "cur": np.zeros(max_slots, np.int32),
            "act": np.zeros(max_slots, bool),
            "keys": np.zeros((max_slots, 2), np.uint32),
            "temp": np.zeros(max_slots, np.float32),
            "top_k": np.zeros(max_slots, np.int32),
            "top_p": np.ones(max_slots, np.float32),
            "eos": np.full(max_slots, -1, np.int32),
            "left": np.zeros(max_slots, np.int32),
            "buf": np.zeros((max_slots, W), np.int32),
            "avail": np.zeros(max_slots, np.int32),
            "bpos": np.zeros(max_slots, np.int32),
            "more": np.zeros(max_slots, bool),
        }
        if spec_depth > 0:
            # fed-token history: the n-gram draft's corpus, rebuilt from
            # the prompt at admission and extended on-device as tokens
            # are fed (a (B, max_len) carry leaf under carry_specs)
            self._st["hist"] = np.zeros((max_slots, max_len), np.int32)
        if adaptive_spec:
            # per-slot speculation gate: the window skips proposing for
            # slots degraded to plain decode (a cold draft's proposals
            # cost a wider verify for nothing).  Streams are invariant
            # to any spec_on schedule (deterministic accept/residual).
            self._st["spec_on"] = np.ones(max_slots, bool)
        if continuous:
            # per-slot generation counter, bumped by every in-scan
            # install: host scatters onto the live carry (refills,
            # degrades) are gen-guarded, so a scatter aimed at a slot
            # the device already handed to a NEW request drops instead
            # of clobbering it.
            self._st["gen"] = np.zeros(max_slots, np.int32)
        if self._pages is not None:
            # slot -> physical-page table: the device-side indirection the
            # paged readers/writers resolve through.  Unmapped logical
            # pages point at the reserved null page 0 (pos -1 there keeps
            # the bias masking them out); rides carry_specs on slot dim 0.
            self._st["ptab"] = np.zeros(
                (max_slots, max_len // self.page_size), np.int32)
        # metrics (sums and `windows` advance atomically at each window
        # boundary in _harvest, so metrics() mid-stream is consistent;
        # under overlap the backlog worker holds _mlock for its share)
        self.host_syncs = 0          # device->host harvest points
        self.admission_syncs = 0     # host_syncs spent on wave prefills
        self.windows = 0             # completed (harvested) windows
        self.windows_idle = 0        # harvested windows that emitted 0
        self.tokens_emitted = 0      # emitted by decode windows
        self._admit_tokens = 0       # first tokens emitted at admission
        self._occupancy_sum = 0
        self._queue_depth_sum = 0
        self._run_seconds = 0.0
        self.draft_proposed = 0      # draft tokens fed to verification
        self.draft_accepted = 0      # ... accepted (free extra tokens)
        self._mlock = threading.Lock()
        self._ttft_sum = 0.0         # summed submit -> first-token latency
        self._ttft_n = 0
        self.preemptions = 0         # slots evicted by lazy reservation
        self.prefill_calls = 0       # admission wave-prefill dispatches
        self.prefill_tokens = 0      # ... their real prompt tokens
        self.prefill_positions = 0   # ... their bucketed rows x length
        self.prefill_calls_saved = 0  # admissions served without a prefill
        # decode-window MoE routing sums (layers.MOE_COUNTERS), harvested
        # with the window's status; zero for a dense FFN
        self.moe_decode = dict.fromkeys(L.MOE_COUNTERS, 0)
        self._preempted: deque[PreemptedRecord] = deque()

        # -- overlapped-pipeline state (inert when overlap=False) --------
        self.overlap = bool(overlap)
        self.aot = bool(aot)
        self.pipeline_depth = pipeline_depth
        self.continuous = bool(continuous)
        self.adaptive_spec = bool(adaptive_spec)
        self.admission_thread = (bool(overlap) if admission_thread is None
                                 else bool(admission_thread))
        self.pin_prefixes = pin_prefixes
        self._inflight: deque[InflightWindow] = deque()
        self._st_dev: dict | None = None     # device-resident carry
        self._dispatch_index = 0             # windows dispatched so far
        self._overlapped_windows = 0         # dispatched before prior done
        # per-slot dispatch-index watermarks: a harvested window's status
        # is STALE for any slot (re)admitted or refilled at a later
        # boundary — without these, a fresh request would be "finished" by
        # its predecessor's death, and a refilled buffer re-refilled.
        self._slot_epoch = np.zeros(max_slots, np.int64)
        self._buf_epoch = np.zeros(max_slots, np.int64)
        self._backlog = TokenBacklog() if self.overlap else None
        self._repl = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
        # threaded-admission + continuous-batching state.  _sched_lock
        # guards the queue <-> staged handoff (the only scheduler surface
        # the admission worker touches); everything else scheduler-side
        # stays main-thread.
        self._sched_lock = threading.Lock()
        self._staged_waves: deque[StagedWave] = deque()
        self._stage_tab: list[StagedEntry | None] = [None] * max_slots
        self._stage_by_seq: dict[int, tuple[int, StagedEntry]] = {}
        self._stage_seq_next = 0
        self._stage_dev: dict | None = None
        self.slot_swaps = 0            # in-scan installs confirmed
        self._act_iters = 0            # sum of per-iteration stepping slots
        self.spec_degraded = 0         # slots degraded to plain decode
        self._spec_acc = np.zeros(max_slots, np.int64)
        self._spec_prop = np.zeros(max_slots, np.int64)
        self._prefix_hits: dict[int, int] = {}   # page -> registry hits
        self._admission: AdmissionWorker | None = None
        if self.overlap and self.admission_thread:
            self._admission = AdmissionWorker(self._take_staged_locked,
                                              self._prepare_wave)
        # per-stage wall-clock sums of the spans in PROFILE_KEYS (the
        # worker's stage only when there is a worker)
        self._prof = {k: 0.0 for k in PROFILE_KEYS.values()
                      if k != "admission_worker" or self._admission is not None}
        # slot -> when its last engine.finish ended; (merge start, seconds
        # the slot stood empty) of each refill that followed one
        self._freed_at: dict[int, float] = {}
        self._refills: deque[tuple[float, float]] = deque(
            maxlen=REFILLS_KEPT)

        # trace-count hooks: the counters bump inside the traced python
        # functions, so they advance exactly once per (re)trace — the AOT
        # smoke check asserts they stay flat while serving.
        self.trace_counts = {"window": 0, "prefill": 0, "draft_prefill": 0,
                             "merge": 0}

        def _prefill_fn(p, t, l):
            self.trace_counts["prefill"] += 1
            return T.prefill(cfg, p, t, l, max_len=max_len, mesh=self.mesh,
                             source=None if source is None
                             else source[: t.shape[0]])

        self._prefill_jit = jax.jit(_prefill_fn)
        self._prefill = self._prefill_jit
        self._prefill_exec: dict[tuple, Any] = {}
        if self.draft_cache is not None:
            dcfg = self._draft_cfg

            def _draft_prefill_fn(p, t, l):
                self.trace_counts["draft_prefill"] += 1
                return T.prefill(dcfg, p, t, l, max_len=max_len,
                                 mesh=self.mesh,
                                 source=None if source is None
                                 else source[: t.shape[0]])

            self._draft_prefill_jit = jax.jit(_draft_prefill_fn)
            self._draft_prefill = self._draft_prefill_jit
            self._draft_prefill_exec: dict[tuple, Any] = {}
        # Donate the cache buffer(s) into the window: self.cache is
        # rebound to the output, so XLA can update the ring in place
        # instead of holding two full caches live — the cache IS the HBM
        # footprint the paper halves.  (CPU ignores donation and would
        # warn, so only donate where it takes effect.)
        # continuous batching: a B-row device staging area — seq keys
        # (STAGE_FREE = empty), one carry row per staged request, and
        # (ring only) a stage cache tree the in-scan install copies a
        # slot row out of.  Paged mode needs no stage cache: a staged
        # request's pages are scattered straight into the shared pool at
        # stage time (they are freshly allocated, so no live reader can
        # see them until its ptab row installs).
        stage_tpl = None
        if self.continuous:
            stage_tpl = {
                "seq": np.full(max_slots, STAGE_FREE, np.int32),
                "rows": {k: np.zeros((max_slots,) + v.shape[1:], v.dtype)
                         for k, v in self._st.items()},
            }
            if self._pages is None:
                stage_tpl["cache"] = T.init_decode_cache(cfg, max_slots,
                                                         max_len)
        in_sh, out_sh = R.window_shardings(
            self.mesh, self.params, self.cache, self._st,
            param_shardings=param_shardings,
            cache_shardings=self._cache_shardings,
            draft_params=self.draft_params, draft_cache=self.draft_cache,
            draft_param_shardings=draft_param_shardings,
            draft_cache_shardings=self._draft_cache_shardings,
            spec_outputs=spec_depth > 0, stage=stage_tpl)
        logits_spec = jax.sharding.NamedSharding(
            self.mesh, R.slot_stacked_spec(max_slots, self.mesh,
                                           lead_dims=0))
        if spec_depth == 0:
            window_fn = self._make_window(
                cfg, max_len, sync_every,
                cache_shardings=self._cache_shardings,
                logits_spec=logits_spec, page_size=self.page_size,
                mesh=self.mesh, continuous=self.continuous)
            donate = (1,)
        else:
            window_fn = self._make_spec_window(
                cfg, max_len, sync_every, spec_depth, draft=self.draft,
                draft_cfg=self._draft_cfg,
                cache_shardings=self._cache_shardings,
                draft_cache_shardings=self._draft_cache_shardings,
                logits_spec=logits_spec, page_size=self.page_size,
                mesh=self.mesh, continuous=self.continuous,
                adaptive=self.adaptive_spec)
            donate = (2, 3) if self.draft_cache is not None else (1,)
        if jax.default_backend() == "cpu":
            donate = ()
        if self.draft_cache is not None:
            def counted_fn(params, dparams, cache, dcache, st):
                self.trace_counts["window"] += 1
                return window_fn(params, dparams, cache, dcache, st)
        elif self.continuous:
            def counted_fn(params, cache, st, stage):
                self.trace_counts["window"] += 1
                return window_fn(params, cache, st, stage)
        else:
            def counted_fn(params, cache, st):
                self.trace_counts["window"] += 1
                return window_fn(params, cache, st)
        self._window = jax.jit(counted_fn, donate_argnums=donate,
                               in_shardings=in_sh, out_shardings=out_sh)
        # the carry subtree of in_shardings, for committed state placement
        # (the overlapped pipeline and AOT executables both need inputs
        # that already sit where the compiled window expects them)
        if self.continuous:
            self._carry_sh = in_sh[-2]
            self._stage_sh = in_sh[-1]
            self._stage_dev = jax.device_put(stage_tpl, self._stage_sh)
        else:
            self._carry_sh = in_sh[-1]
        # one program per admission merge (_admission_merge), per target
        # and set of parts merged (see _merge_jit); it donates what it
        # merges into, on the window's rule
        self._merge_donate = () if jax.default_backend() == "cpu" else (0, 1, 2)
        self._merge_jits: dict[tuple, Any] = {}
        self._merge_exec: dict[tuple, Any] = {}
        if self.aot:
            self._aot_compile()

    # -- AOT warmup ----------------------------------------------------------

    def _aot_compile(self):
        """Lower + compile the window, every reachable prefill bucket and
        the admission merges now, so serving never traces: the (wave,
        prompt-len) shapes ``_bucket`` can produce form a small closed
        set, and the window's shapes are fixed at construction."""
        st = {k: jax.device_put(v, self._carry_sh[k])
              for k, v in self._st.items()}
        if self.draft_cache is not None:
            args = (self.params, self.draft_params, self.cache,
                    self.draft_cache, st)
        elif self.continuous:
            args = (self.params, self.cache, st, self._stage_dev)
        else:
            args = (self.params, self.cache, st)
        self._window = self._window.lower(*args).compile()
        cap = self.max_len - 1                  # submit() prompt cap
        if self.scheduler.prefill_chunk is not None:
            cap = min(cap, self.scheduler.prefill_chunk)
        wcap = max(self.B, self.staging_depth)
        waves = sorted({_bucket(n, wcap) for n in range(1, wcap + 1)})
        plens = sorted({_bucket(n, self.max_len) for n in range(1, cap + 1)})
        for w in waves:
            for p in plens:
                t = jax.ShapeDtypeStruct((w, p), jnp.int32,
                                         sharding=self._repl)
                ln = jax.ShapeDtypeStruct((w,), jnp.int32,
                                          sharding=self._repl)
                self._prefill_exec[(w, p)] = self._prefill_jit.lower(
                    self.params, t, ln).compile()
                if self.draft_cache is not None:
                    self._draft_prefill_exec[(w, p)] = \
                        self._draft_prefill_jit.lower(
                            self.draft_params, t, ln).compile()
        self._prefill = self._make_prefill_dispatch(
            self._prefill_jit, self._prefill_exec)
        if self.draft_cache is not None:
            self._draft_prefill = self._make_prefill_dispatch(
                self._draft_prefill_jit, self._draft_prefill_exec)
        self._aot_merges(waves, plens[0])

    def _aot_merges(self, waves, plen: int):
        """Compile the admission merges this engine's admission paths
        reach: per prefill wave bucket W, the row buckets a wave of W rows
        merges in (one row at a time into the continuous stage), plus a
        prefill-free merge (every row a resident prefix) when paged."""
        def spec(x, sharding):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

        def prefilled(execs, w):
            return jax.tree.map(lambda x: spec(x, x.sharding),
                                execs[(w, plen)].out_info[1])

        ring, draft = self._pages is None, self.draft_cache is not None
        rbs = sorted({_bucket(n, self.B) for n in range(1, self.B + 1)})
        runs = []                       # (part, W or None, row bucket)
        if not self.overlap:
            if ring or draft:
                runs += [((False, ring, draft, False), _bucket(n, waves[-1]),
                          _bucket(n, self.B)) for n in range(1, self.B + 1)]
        else:
            part = (self.continuous, ring, draft, True)
            runs += [(part, w, rb) for w in waves for rb in rbs
                     if rb <= w and (rb == 1 or not self.continuous)]
            if not ring:
                runs += [((self.continuous, False, False, True), None, rb)
                         for rb in ([1] if self.continuous else rbs)]
        for part, w, rb in runs:
            stage, with_cache, with_draft, with_carry = part
            cache, dcache, carry = self._merge_targets(part)
            if with_carry and carry is None:   # built at the first boundary
                carry = {k: spec(v, self._carry_sh[k])
                         for k, v in self._st.items()}
            targets = jax.tree.map(lambda x: spec(x, x.sharding),
                                   (cache, dcache, carry))
            new = (prefilled(self._prefill_exec, w) if with_cache else None)
            dnew = (prefilled(self._draft_prefill_exec, w) if with_draft
                    else None)
            first = ks = None
            if with_carry and w is not None:
                first = jax.ShapeDtypeStruct((w,), jnp.int32,
                                             sharding=self._repl)
                ks = jax.ShapeDtypeStruct((w, 2, 2), jnp.uint32,
                                          sharding=self._repl)
            host = None
            if with_carry:
                host = {k: np.zeros((rb,) + v.shape[1:], v.dtype)
                        for k, v in self._st.items() if k not in DEVICE_ROWS}
                if stage:
                    host["seq"] = np.zeros(rb, np.int32)
            ix = self._merge_ix([0] * rb, [0] * rb,
                                [(0, 0)] * rb if with_draft else None, host,
                                (np.zeros(rb, bool), np.zeros(rb, np.int32),
                                 np.zeros(rb, np.int32),
                                 np.zeros((rb, 2), np.uint32)))
            ix = jax.tree.map(lambda x: spec(x, self._repl), ix)
            key = self._merge_key(part, new, dnew, first, rb)
            self._merge_exec[key] = self._merge_jit(part).lower(
                *targets, new, dnew, first, ks, ix).compile()

    @staticmethod
    def _make_prefill_dispatch(jit_fn, executables):
        def dispatch(p, t, l):
            exe = executables.get(tuple(t.shape))
            return (jit_fn if exe is None else exe)(p, t, l)
        return dispatch

    def _prefill_args(self, toks: np.ndarray, lens: np.ndarray):
        """Device placement for wave-prefill inputs.  AOT executables
        require committed arrays matching the lowered shardings; the
        plain jit path keeps the cheaper uncommitted upload."""
        if self.aot:
            return (jax.device_put(toks, self._repl),
                    jax.device_put(lens, self._repl))
        return jnp.asarray(toks), jnp.asarray(lens)

    # -- fused decode window -------------------------------------------------

    @staticmethod
    def _stage_install(st, cache, seq, stage, max_len=None):
        """The device half of continuous batching: install (at most) the
        FIFO-head staged request into the lowest free slot.  ``seq`` is a
        scan CARRY — clearing the installed entry there makes the install
        exactly-once across any pipeline depth (later windows chain on
        this window's seq output).  Rows/cache are read-only inputs.  A
        full batch (or an empty stage) degenerates to an out-of-range
        scatter index, which ``mode="drop"`` turns into a no-op — no
        branch, so the window stays one trace."""
        B = st["act"].shape[0]
        q = jnp.argmin(seq).astype(jnp.int32)
        have = seq[q] != STAGE_FREE
        slot = jnp.argmax(~st["act"]).astype(jnp.int32)
        do = have & ~st["act"][slot]
        tgt = jnp.where(do, slot, B)
        st2 = {}
        for k, v in st.items():
            if k == "gen":
                # generation bump, NOT a copy: the host's gen-guarded
                # scatters key off this to drop writes aimed at the
                # slot's previous occupant
                st2[k] = v.at[tgt].add(1, mode="drop")
            else:
                st2[k] = v.at[tgt].set(
                    jnp.take(stage["rows"][k], q, axis=0), mode="drop")
        if "cache" in stage:
            cache = T.swap_cache_slot(cache, stage["cache"], tgt, q)
        seq2 = seq.at[jnp.where(do, q, B)].set(STAGE_FREE, mode="drop")
        sw_seq = jnp.where(do, seq[q], -1).astype(jnp.int32)
        sw_slot = jnp.where(do, slot, -1).astype(jnp.int32)
        return st2, cache, seq2, sw_seq, sw_slot

    @staticmethod
    def _make_window(cfg: ModelConfig, max_len: int, steps: int, *,
                     cache_shardings=None, logits_spec=None,
                     page_size: int | None = None, mesh=None,
                     continuous: bool = False):
        """Build the jitted window fn: ``steps`` fused decode iterations.

        Per iteration, per slot: pick the fed token (ingest buffer while
        prompt remains, else last sampled), run one batched decode_step
        (inactive/stalled rows masked from cache writes), sample, then
        update emit/termination flags — all under one lax.scan, so the
        only host sync is the caller harvesting the stacked outputs.

        ``continuous`` threads the device staging queue through the scan
        (see ``_stage_install``): each iteration may refill one freed
        slot from staged state before stepping, so a mid-window death
        costs idle iterations only until the next staged head, not until
        the boundary.

        ``cache_shardings``/``logits_spec`` pin the scan carry's ring
        layout and the sampler's slot-sharded logits so the loop body
        never reshards mid-scan (the mesh must not smuggle per-step
        transfers back in)."""

        def window(params, cache, st, stage=None):
            def body(carry, _):
                if continuous:
                    cache, st, seq = carry
                    st, cache, seq, sw_seq, sw_slot = Engine._stage_install(
                        st, cache, seq, stage)
                else:
                    cache, st = carry
                feeding = st["bpos"] < st["avail"]
                buf_tok = jnp.take_along_axis(
                    st["buf"],
                    jnp.minimum(st["bpos"], st["buf"].shape[1] - 1)[:, None],
                    axis=1)[:, 0]
                tok_in = jnp.where(feeding, buf_tok, st["tok"])
                # a slot whose ingest buffer drained but has prompt left on
                # the host stalls (no step) until the next refill
                stalled = st["more"] & ~feeding
                stepping = st["act"] & ~stalled
                pages = ((st["ptab"], page_size)
                         if page_size is not None else None)
                out = T.decode_step(
                    cfg, params, cache, tok_in, st["cur"], stepping,
                    cache_shardings=cache_shardings, pages=pages,
                    mesh=mesh, moe_counts=cfg.moe is not None)
                logits, cache = out[:2]
                ks = S.split_keys(st["keys"])
                sampled = S.sample_tokens(logits, st["temp"], st["top_k"],
                                          st["top_p"], ks[:, 1],
                                          spec=logits_spec)
                last_prompt = (feeding & ~st["more"]
                               & (st["bpos"] + 1 >= st["avail"]))
                emit = stepping & (~feeding | last_prompt)
                cur2 = st["cur"] + stepping.astype(st["cur"].dtype)
                left2 = st["left"] - emit.astype(st["left"].dtype)
                # ring-cap stop: cur2 == max_len means this step wrote the
                # last ring position — the NEXT write would wrap and evict
                # position 0.  (Not max_len - 1: that fired one step early
                # on the ingest path, costing cap-length chunked prompts
                # their final token vs unchunked admission.)
                done = (emit & ((sampled == st["eos"]) | (left2 <= 0))
                        | (stepping & (cur2 >= max_len)))
                st2 = {**st,
                       "tok": jnp.where(emit, sampled, st["tok"]),
                       "cur": cur2,
                       "act": st["act"] & ~done,
                       "keys": jnp.where(emit[:, None], ks[:, 0], st["keys"]),
                       "bpos": st["bpos"] + feeding.astype(st["bpos"].dtype),
                       "left": left2}
                n_act = stepping.astype(jnp.int32).sum()
                if cfg.moe is not None:
                    # the step's MoE routing counters ride beside n_act
                    # into the window's status
                    n_act = jnp.concatenate([n_act[None], out[2]])
                if continuous:
                    return ((cache, st2, seq),
                            (sampled, emit, sw_seq, sw_slot, n_act))
                return (cache, st2), (sampled, emit, n_act)

            if continuous:
                (cache, st, seq), (toks, emits, sw_seq, sw_slot, n_act) = \
                    jax.lax.scan(body, (cache, st, stage["seq"]), None,
                                 length=steps)
                return (cache, st, seq, sw_seq, sw_slot, toks, emits,
                        n_act)
            (cache, st), (toks, emits, n_act) = jax.lax.scan(
                body, (cache, st), None, length=steps)
            return cache, st, toks, emits, n_act

        return window

    # -- speculative decode window -------------------------------------------

    @staticmethod
    def _make_spec_window(cfg: ModelConfig, max_len: int, steps: int,
                          depth: int, *, draft: DraftSpec, draft_cfg=None,
                          cache_shardings=None, draft_cache_shardings=None,
                          logits_spec=None, page_size: int | None = None,
                          mesh=None, continuous: bool = False,
                          adaptive: bool = False):
        """Build the jitted speculative window: ``steps`` iterations, each
        verifying up to ``depth`` draft tokens in ONE target pass.

        Per iteration, per slot: propose ``depth`` tokens (n-gram lookup
        over the fed-token history, or greedy steps of the layer draft),
        run one S = depth + 1 token ``T.verify_step``, then walk the S
        positions in order: position j's target draw (the slot's policy
        with its j-th key split) is the token sequential decoding would
        emit there, so a proposal is accepted iff it matches; the first
        mismatch emits the draw itself (deterministic residual) and stops
        the round.  Only the accepted prefix is committed to the ring and
        keys advance exactly once per emitted token — the sequential body
        is the S = 1 special case, so streams are depth-invariant.
        Ingesting (chunked-prefill) slots keep their one-token-per-
        iteration behavior: their columns >= 1 are never candidates."""
        S_pos = depth + 1
        has_draft_model = draft.kind == "layers"

        def round_body(params, dparams, cache, dcache, st, seq=None,
                       stage=None):
            sw = ()
            if continuous:
                st, cache, seq, sw_seq, sw_slot = Engine._stage_install(
                    st, cache, seq, stage)
                sw = (sw_seq, sw_slot)
            feeding = st["bpos"] < st["avail"]
            buf_tok = jnp.take_along_axis(
                st["buf"],
                jnp.minimum(st["bpos"], st["buf"].shape[1] - 1)[:, None],
                axis=1)[:, 0]
            tok_in = jnp.where(feeding, buf_tok, st["tok"])
            stalled = st["more"] & ~feeding
            stepping = st["act"] & ~stalled
            speculating = stepping & ~feeding
            if adaptive:
                # degraded slots propose nothing — they ride the window
                # as plain decode (column 0 only).  Any spec_on schedule
                # leaves streams bitwise identical (deterministic
                # accept/residual), so this is purely a cost knob.
                speculating = speculating & st["spec_on"]
            cur = st["cur"]
            js = jnp.arange(S_pos, dtype=cur.dtype)
            cap_ok = (cur[:, None] + js[None, :]) < max_len      # (B, S)

            # --- proposals (B, depth)
            if has_draft_model:
                props = []
                d_tok, d_cur = tok_in, cur
                # S_pos draft steps: feeds [tok_in, d1..d_depth], so the
                # draft ring also covers the last (bonus) position on
                # full acceptance; rejected columns are struck from its
                # position index below.
                for j in range(S_pos):
                    act_j = (stepping if j == 0
                             else speculating & cap_ok[:, j])
                    dlogits, dcache = T.decode_step(
                        draft_cfg, dparams, dcache, d_tok, d_cur, act_j,
                        cache_shardings=draft_cache_shardings, mesh=mesh)
                    d_cur = d_cur + act_j.astype(d_cur.dtype)
                    if j < depth:
                        d_tok = jnp.argmax(dlogits, -1).astype(jnp.int32)
                        props.append(d_tok)
                props = jnp.stack(props, axis=1)
            else:
                props = D.ngram_propose(st["hist"], cur, tok_in, depth)

            # --- one multi-token target pass over [tok_in | proposals]
            fed = jnp.concatenate([tok_in[:, None], props], axis=1)
            cand = jnp.concatenate(
                [stepping[:, None], speculating[:, None] & cap_ok[:, 1:]],
                axis=1)                                          # (B, S)
            # the draft ring (layer draft) stays slot-major even in paged
            # mode — only the target cache resolves through the page table
            pages = ((st["ptab"], page_size)
                     if page_size is not None else None)
            logits, updates = T.verify_step(cfg, params, cache, fed, cur,
                                            cand, pages=pages, mesh=mesh)
            last_prompt = (feeding & ~st["more"]
                           & (st["bpos"] + 1 >= st["avail"]))

            # --- in-order accept / residual walk (j == emission index)
            keys_state = st["keys"]
            tok2 = st["tok"]
            done_any = jnp.zeros_like(st["act"])
            nemit = jnp.zeros_like(cur)
            cols = []
            emit_prev = s_prev = None
            for j in range(S_pos):
                if j == 0:
                    valid_j = stepping
                    emit_j = stepping & (~feeding | last_prompt)
                else:
                    valid_j = (emit_prev & ~done_any & cand[:, j]
                               & (fed[:, j] == s_prev))
                    emit_j = valid_j
                ks = S.split_keys(keys_state)
                s_j = S.sample_tokens(logits[:, j], st["temp"],
                                      st["top_k"], st["top_p"], ks[:, 1],
                                      spec=logits_spec)
                nemit = nemit + emit_j.astype(cur.dtype)
                left_j = st["left"] - nemit
                done_j = (emit_j & ((s_j == st["eos"]) | (left_j <= 0))
                          | (valid_j & (cur + j + 1 >= max_len)))
                done_any = done_any | done_j
                keys_state = jnp.where(emit_j[:, None], ks[:, 0],
                                       keys_state)
                tok2 = jnp.where(emit_j, s_j, tok2)
                cols.append((valid_j, emit_j, s_j))
                emit_prev, s_prev = emit_j, s_j
            valid = jnp.stack([c[0] for c in cols], axis=1)      # (B, S)
            emits_r = jnp.stack([c[1] for c in cols], axis=1)
            toks_r = jnp.stack([c[2] for c in cols], axis=1)

            # --- commit the accepted prefix (rejected tokens never wrote)
            cache = T.commit_verify_writes(cache, updates, cur, valid,
                                           cache_shardings=cache_shardings,
                                           pages=pages)
            if has_draft_model:
                # the draft wrote as it proposed; strike rejected columns
                # from its position index so they can't shadow the slot
                for j in range(1, S_pos):
                    dcache = KC.invalidate_positions(
                        dcache, cur + j, cand[:, j] & ~valid[:, j])
            hist = st["hist"]
            iota = jnp.arange(hist.shape[1], dtype=cur.dtype)[None, :]
            for j in range(S_pos):
                hit = (iota == (cur + j)[:, None]) & valid[:, j][:, None]
                hist = jnp.where(hit, fed[:, j][:, None], hist)

            st2 = {**st,
                   "tok": tok2,
                   "cur": cur + valid.astype(cur.dtype).sum(axis=1),
                   "act": st["act"] & ~done_any,
                   "keys": keys_state,
                   "bpos": st["bpos"] + feeding.astype(st["bpos"].dtype),
                   "left": st["left"] - nemit,
                   "hist": hist}
            accepted = valid[:, 1:].astype(jnp.int32).sum(axis=1)
            # count only REAL proposals: the n-gram draft pads unknown
            # positions with -1 (guaranteed rejects), which would deflate
            # accept_rate below what the draft actually achieves on the
            # positions it dared to predict
            proposed = ((cand[:, 1:] & (fed[:, 1:] >= 0))
                        .astype(jnp.int32).sum(axis=1))
            n_act = stepping.astype(jnp.int32).sum()
            return cache, dcache, st2, seq, (toks_r, emits_r, accepted,
                                             proposed, n_act) + sw

        if has_draft_model:
            def window(params, dparams, cache, dcache, st):
                def body(carry, _):
                    cache, dcache, st = carry
                    cache, dcache, st2, _, ys = round_body(
                        params, dparams, cache, dcache, st)
                    return (cache, dcache, st2), ys
                (cache, dcache, st), (toks, emits, acc, prop, n_act) = \
                    jax.lax.scan(body, (cache, dcache, st), None,
                                 length=steps)
                return cache, dcache, st, toks, emits, acc, prop, n_act
        elif continuous:
            def window(params, cache, st, stage):
                def body(carry, _):
                    cache, st, seq = carry
                    cache, _, st2, seq, ys = round_body(
                        params, None, cache, None, st, seq, stage)
                    return (cache, st2, seq), ys
                ((cache, st, seq),
                 (toks, emits, acc, prop, n_act, sw_seq, sw_slot)) = \
                    jax.lax.scan(body, (cache, st, stage["seq"]), None,
                                 length=steps)
                return (cache, st, seq, sw_seq, sw_slot, toks, emits,
                        acc, prop, n_act)
        else:
            def window(params, cache, st):
                def body(carry, _):
                    cache, st = carry
                    cache, _, st2, _, ys = round_body(params, None, cache,
                                                      None, st)
                    return (cache, st2), ys
                (cache, st), (toks, emits, acc, prop, n_act) = jax.lax.scan(
                    body, (cache, st), None, length=steps)
                return cache, st, toks, emits, acc, prop, n_act

        return window

    @classmethod
    def from_artifact(cls, path: str, *, max_slots: int, max_len: int,
                      source: jax.Array | None = None,
                      backend: str | None = None,
                      sampling: SamplingParams | None = None,
                      sync_every: int = 8,
                      prefill_chunk: int | None = None,
                      mesh: jax.sharding.Mesh | None = None,
                      spec_depth: int = 0,
                      draft: str | DraftSpec | None = None,
                      cache_layout: str = "ring",
                      page_size: int | None = None,
                      n_pages: int | None = None,
                      overlap: bool = False,
                      aot: bool = False,
                      pipeline_depth: int = 2,
                      continuous: bool = False,
                      admission_thread: bool | None = None,
                      pin_prefixes: int = 0,
                      adaptive_spec: bool = False,
                      policy: str | AdmissionPolicy | None = None,
                      lazy_pages: bool = False,
                      staging_depth: int | None = None) -> "Engine":
        """Boot an engine straight from a saved compression artifact —
        the compress-offline / serve-forever workflow across processes.
        ``overlap``/``aot``/``pipeline_depth``/``continuous`` select the
        pipelined engine exactly as on the constructor."""
        from repro.api import load_artifact  # local: api imports models too

        art = load_artifact(path)
        return cls(art.cfg, art.params, max_slots=max_slots, max_len=max_len,
                   source=source, backend=backend, sampling=sampling,
                   sync_every=sync_every, prefill_chunk=prefill_chunk,
                   mesh=mesh, spec_depth=spec_depth, draft=draft,
                   cache_layout=cache_layout, page_size=page_size,
                   n_pages=n_pages, overlap=overlap, aot=aot,
                   pipeline_depth=pipeline_depth, continuous=continuous,
                   admission_thread=admission_thread,
                   pin_prefixes=pin_prefixes, adaptive_spec=adaptive_spec,
                   policy=policy, lazy_pages=lazy_pages,
                   staging_depth=staging_depth)

    # -- back-compat conveniences -------------------------------------------

    @property
    def slot_req(self) -> list[Request | None]:
        return self.scheduler.slot_req

    @property
    def queue(self):
        return self.scheduler.queue

    @property
    def unfinished(self) -> dict[str, int]:
        """Requests not yet finished: queued vs admitted-but-mid-flight."""
        return {"queued": self.scheduler.queue_depth,
                "in_flight": self.scheduler.occupancy}

    @property
    def mesh_str(self) -> str:
        """Mesh shape joined over ALL axes in mesh order (e.g. "1x1",
        "2x4", "2x16x16" for a multi-pod mesh)."""
        return "x".join(str(self.mesh.shape[a]) for a in self.mesh.axis_names)

    # -- admission ----------------------------------------------------------

    def submit(self, req: Request) -> Request:
        with self._sched_lock:
            self.scheduler.submit(req)
        if self._admission is not None:
            self._admission.kick(self._staging_capacity())
        return req

    def _staging_capacity(self) -> int:
        """How many MORE requests admission may pull off the queue right
        now: ``staging_depth`` bounds the prefilled-but-unmerged look-
        ahead (default 2x the slot count, decoupled from ``max_slots``).
        Pulling a deeper run per kick is what batches N staged prompts
        into ONE bucketed wave prefill instead of N separate calls; the
        stage-row / free-slot / page-budget bounds are enforced at the
        boundary merge, where prepared waves wait head-of-line."""
        with self._sched_lock:
            return max(0, self.staging_depth - len(self.scheduler.staged))

    def _take_staged_locked(self, max_n: int) -> list[Request]:
        with self._sched_lock:
            return self.scheduler.take_staged(max_n)

    def _count_prefill(self, toks: np.ndarray, lens: np.ndarray):
        """One admission wave-prefill dispatch (any thread): its real
        prompt tokens and its bucketed (rows, length) positions."""
        with self._mlock:
            self.prefill_calls += 1
            self.prefill_tokens += int(lens.sum())
            self.prefill_positions += toks.size

    def _record_token(self, req: Request, tok: int):
        """Credit one emitted token to a request: append, stamp ttft on
        the first, fire the stream callback.  Runs on the main thread
        (sync engine) or the backlog worker (overlapped engine) — never
        both for the same engine, so out_tokens needs no lock; the ttft
        sums are shared with metrics() and do."""
        req.out_tokens.append(tok)
        if req.first_token_at is None:
            req.first_token_at = time.perf_counter()
            if req.submitted_at is not None:
                with self._mlock:
                    self._ttft_sum += req.first_token_at - req.submitted_at
                    self._ttft_n += 1
        if req.on_token is not None:
            req.on_token(req, tok)

    def _finish(self, slot: int):
        self.finished.append(self.scheduler.slot_req[slot])
        self.scheduler.release(slot)
        st = self._st
        st["act"][slot] = False
        st["avail"][slot] = 0
        st["bpos"][slot] = 0
        st["more"][slot] = False
        st["left"][slot] = 0
        if self._pages is not None:
            for pg in self._slot_pages[slot]:
                # refcount-0 pages keep their prefix key: the content is
                # resident until the LRU free list recycles the page, so
                # a recurring prompt can resurrect it (see _assign_pages)
                self._pages.free(pg)
            self._slot_pages[slot] = []
            st["ptab"][slot] = 0

    # -- paged admission helpers ---------------------------------------------

    def _pages_needed(self, req: Request) -> int:
        """Worst-case page count for ``req``: its write reach is known at
        admission (prompt + generation budget, capped by the ring), so
        admission can reserve up front and the device loop never faults.
        Conservative — ignores prefix sharing, so a fitting wave always
        has real pages even if every registry lookup misses."""
        reach = min(len(req.prompt) + req.max_new_tokens, self.max_len)
        return -(-reach // self.page_size)

    @property
    def _headroom(self) -> int:
        """Tokens a slot can advance past its last boundary-visible
        ``cur`` before the next lazy top-up runs: every window that can
        be in flight when one is dispatched (the trailing harvest lags
        by up to ``pipeline_depth`` windows, plus the new one) times the
        worst per-iteration advance (one fed/sampled token plus up to
        ``spec_depth`` accepted draft tokens)."""
        per_window = self.sync_every * (self.spec_depth + 1)
        depth = (self.pipeline_depth + 1) if self.overlap else 1
        return per_window * depth

    def _admit_need(self, req: Request, first_len: int) -> int:
        """Pages admission must map up front.  Eager (default): the full
        worst-case reach, so the device loop can never fault.  Lazy:
        just the admitted coverage plus one top-up interval's headroom —
        ``_lazy_topup`` grows the mapping at window boundaries as
        ``cur`` approaches it, preempting a victim when the pool runs
        dry."""
        if not self.lazy_pages:
            return self._pages_needed(req)
        reach = min(len(req.prompt) + req.max_new_tokens, self.max_len)
        return -(-min(first_len + self._headroom, reach) // self.page_size)

    def _probe_prefix_len(self, req: Request) -> int:
        """READ-ONLY registry probe: how many leading prompt tokens are
        covered by resident pages right now, capped one page short of
        the whole prompt (at least one prompt token must flow through
        the decode loop so the first generated token has a step to be
        sampled in when the prefill is skipped).  Heuristic — worker-
        thread safe; the authoritative lookup+retain happens at the
        boundary merge (``_map_shared_pages``), which may see more or
        fewer resident pages and is correct either way."""
        ps = self.page_size
        hit = 0
        for j in range((len(req.prompt) - 1) // ps):
            if self._prefixes.lookup(prefix_key(req.prompt, j, ps)) is None:
                break
            hit += 1
        return hit * ps

    def _skip_prefill(self, req: Request) -> bool:
        """Prefill-skip gate (prefix-affinity): admit with NO prefill
        row when resident registry pages cover all but at most one
        page's worth of the prompt — the remainder streams through the
        ingest buffer, which is cheap only when it is short."""
        if self._pages is None or not self.policy.groups_by_prefix:
            return False
        hit = self._probe_prefix_len(req)
        return hit > 0 and len(req.prompt) - hit <= self.page_size

    def _map_shared_pages(self, req: Request):
        """Skip-path page mapping (no prefill row): the longest resident
        registry prefix is retained/resurrected; the remainder is
        freshly allocated and POS-WIPED on device, because no prefill
        scatter covers it and a recycled page's stale positions would
        otherwise read as valid cache (position masking is the paged
        reader's validity mechanism).  Content for the wiped pages
        arrives via the decode loop's ingest/generation writes, so they
        are never registered.  Returns (mapping, hit_len)."""
        ps = self.page_size
        shared: list[int] = []
        for j in range((len(req.prompt) - 1) // ps):
            pg = self._prefixes.lookup(prefix_key(req.prompt, j, ps))
            if pg is None:
                break
            shared.append(pg)
        for pg in shared:
            self._note_prefix_hit(pg)
            if self._pages.refcount(pg) == 0:
                self._pages.resurrect(pg)
            else:
                self._pages.retain(pg)
        hit_len = len(shared) * ps
        n_need = max(self._admit_need(req, hit_len), len(shared))
        own = self._pages.alloc(n_need - len(shared))
        for pg in own:
            self._prefixes.drop_page(pg)
            self._prefix_hits.pop(pg, None)
        if own:
            self.cache = T.wipe_pages(self.cache,
                                      jnp.asarray(own, jnp.int32))
        self._update_pins()
        return shared + own, hit_len

    def _assign_shared_pages(self, slot: int, req: Request) -> int:
        """_map_shared_pages plus the slot bindings (page list + ptab
        row); returns the shared coverage — the admitted ``cur``."""
        mapping, hit_len = self._map_shared_pages(req)
        self._slot_pages[slot] = list(mapping)
        row = self._st["ptab"][slot]
        row[:] = 0
        row[: len(mapping)] = mapping
        with self._mlock:
            self.prefill_calls_saved += 1
        return hit_len

    def _map_pages(self, req: Request, first_len: int):
        """Map ``req``'s logical pages to physical ones: longest
        registry-hit prefix is *retained* (refcount++, no copy), the rest
        freshly allocated.  Returns (mapping, scatter_cols): the full
        physical mapping for a ptab row, and which logical pages the
        wave prefill must scatter (the non-shared ones).  Main-thread
        only (mutates the pool/registry) — the staging path calls this
        at the boundary merge, never on the admission worker.

        Copy-on-write resolves at admission: only prefix pages FULLY
        covered by this wave's prefill are shareable, and the first
        logical page past the shared run is by definition divergent —
        its content comes from this request's own prefill scatter, so
        the "copy" is free.  Generation never touches shared pages
        (writes start at first_len >= shared run end)."""
        ps = self.page_size
        n_need = self._admit_need(req, first_len)
        shared: list[int] = []
        lim = min(n_need, first_len // ps)
        for j in range(lim):
            pg = self._prefixes.lookup(prefix_key(req.prompt, j, ps))
            if pg is None:
                break
            shared.append(pg)
        for pg in shared:
            self._note_prefix_hit(pg)
            if self._pages.refcount(pg) == 0:
                # every holder retired but the page was never recycled:
                # its latent content is still resident, so the recurring
                # prefix skips the prefill (registry keys outlive holders)
                self._pages.resurrect(pg)
            else:
                self._pages.retain(pg)
        if shared and n_need > len(shared):
            # first divergent page: a fork in COW terms, but the new
            # content arrives via this request's own prefill scatter —
            # no device copy needed, just a fresh page
            self._pages.cow_forks += 1
        own = self._pages.alloc(n_need - len(shared))
        for pg in own:
            # a recycled page's old prefix key (if any) is dead now —
            # the registry must never map a prefix to rewritten content
            self._prefixes.drop_page(pg)
            self._prefix_hits.pop(pg, None)
        mapping = shared + own
        for j in range(len(shared), n_need):
            # register pages whose content this wave's prefill fully
            # determines (complete, never-rewritten prompt prefixes)
            if (j + 1) * ps <= first_len:
                self._prefixes.register(prefix_key(req.prompt, j, ps),
                                        mapping[j])
        self._update_pins()
        return mapping, list(range(len(shared), n_need))

    def _assign_pages(self, slot: int, req: Request, first_len: int):
        """_map_pages plus the slot bindings (page list + ptab row)."""
        mapping, scat = self._map_pages(req, first_len)
        self._slot_pages[slot] = list(mapping)
        row = self._st["ptab"][slot]
        row[:] = 0
        row[: len(mapping)] = mapping
        return mapping, scat

    def _note_prefix_hit(self, page: int):
        if self.pin_prefixes:
            self._prefix_hits[page] = self._prefix_hits.get(page, 0) + 1

    def _update_pins(self):
        """Keep the ``pin_prefixes`` hottest still-registered prefix
        pages pinned (exempt from LRU recycling, parked at refcount 0).
        Hit counts die with their page's registry entry, so a recycled
        page can't haunt the ranking."""
        if not self.pin_prefixes:
            return
        registered = self._prefixes.pages()
        alive = {pg: h for pg, h in self._prefix_hits.items()
                 if pg in registered}
        want = set(sorted(alive, key=lambda p: (-alive[p], p))
                   [: self.pin_prefixes])
        for pg in range(1, self.n_pages):
            if pg in want:
                self._pages.pin(pg)
            elif self._pages.is_pinned(pg):
                self._pages.unpin(pg)

    def _page_fits(self):
        """Page-budget admission gate: reserve each request's admission
        need up front against a running budget (head-of-line under fifo;
        other policies document their own skipping).  Conservative —
        ignores prefix sharing, so a fitting wave always has real pages
        even if every registry lookup misses.  Under lazy reservation
        the need shrinks to coverage + headroom, but a request whose
        worst-case reach exceeds the whole pool never fits: the top-up
        path must be able to finish it once the pool is all hers."""
        budget = self._pages.free_count

        def fits(req: Request) -> bool:
            nonlocal budget
            if (self.lazy_pages
                    and self._pages_needed(req) > self.n_pages - 1):
                return False
            need = self._admit_need(req, self.scheduler.first_chunk_len(req))
            if need > budget:
                return False
            budget -= need
            return True

        return fits

    def _skip_rows(self, reqs) -> tuple[list[int], int]:
        """Prefill-row assignment for an admission run: request i rides
        prefill row rows[i], or -1 when its prefill is skipped (resident
        prefix pages).  Returns (rows, prefill-row count)."""
        rows, w = [], 0
        for r in reqs:
            if self._skip_prefill(r):
                rows.append(-1)
            else:
                rows.append(w)
                w += 1
        return rows, w

    def _bucket_prompts(self, reqs, first_lens, rows, w):
        """Pack the prefill members of an admission run into one
        power-of-two (rows, prompt-len) bucket so a stream of ragged
        admissions reuses O(log) jit traces.  The row cap is the staging
        look-ahead (staged runs batch past the slot count); the length
        cap is max_len (padding past the ring would silently drop a
        fittable prompt prefix)."""
        pf = [fl for fl, ri in zip(first_lens, rows) if ri >= 0]
        # row cap: staged runs may batch up to staging_depth prompts in
        # one wave — past the cap _bucket degenerates to the raw count,
        # which would mint a fresh shape (and an AOT retrace) per run
        W = _bucket(w, max(self.B, self.staging_depth))
        P = _bucket(max(pf), self.max_len)
        toks = np.zeros((W, P), np.int32)
        lens = np.zeros((W,), np.int32)
        for i, r in enumerate(reqs):
            if rows[i] < 0:
                continue
            toks[rows[i], : first_lens[i]] = r.prompt[: first_lens[i]]
            lens[rows[i]] = first_lens[i]
        return toks, lens

    def _admission_wave(self):
        """Host half of admission: take a wave off the queue (policy
        order) and build its shape-bucketed prefill inputs.  Shared by
        the sync and the overlapped paths — the scheduler bookkeeping
        must be identical for the parity contract to hold."""
        if self._pages is None:
            wave = self.scheduler.take_wave()
        else:
            wave = self.scheduler.take_wave(self._page_fits())
        if not wave:
            return None
        first_lens = [self.scheduler.first_chunk_len(r) for _, r in wave]
        rows, w = self._skip_rows([r for _, r in wave])
        if w == 0:
            return wave, first_lens, rows, None, None
        toks, lens = self._bucket_prompts([r for _, r in wave],
                                          first_lens, rows, w)
        return wave, first_lens, rows, toks, lens

    def _admit_prefill(self, wave, first_lens, rows, toks, lens):
        """Dispatch the wave prefill (when any row needs one) and chain
        the slot merges onto the current cache futures.  Skip members
        (rows[i] == -1) bind resident registry pages instead, mutating
        ``first_lens`` in place to their shared coverage.  Never blocks:
        the returned logits are a (W, V) device future, or None for an
        all-skip wave."""
        logits = new_cache = None
        if toks is not None:
            self._count_prefill(toks, lens)
            tj, lj = self._prefill_args(toks, lens)
            logits, new_cache = self._prefill(self.params, tj, lj)
        if self._pages is not None:
            rws, cols, phys = [], [], []
            for i, (slot, r) in enumerate(wave):
                if rows[i] < 0:
                    first_lens[i] = self._assign_shared_pages(slot, r)
                    continue
                mapping, scat = self._assign_pages(slot, r, first_lens[i])
                for j in scat:
                    rws.append(rows[i])
                    cols.append(j)
                    phys.append(mapping[j])
            if phys:
                # non-shared pages only: shared prefixes are already
                # resident and must not be rewritten (their tail slots in
                # new_cache hold pos=-1 filler, same as fresh pages get)
                self.cache = _merge_slot_paged(
                    self.cache, new_cache, jnp.asarray(rws),
                    jnp.asarray(cols), jnp.asarray(phys), self.page_size)
        dnew = None
        if self.draft_cache is not None and toks is not None:
            # the layer draft consumes the same wave so its ring tracks
            # the target's (its logits here are irrelevant).  A skip
            # member's draft ring keeps stale content: its proposals are
            # garbage until overwritten, which costs acceptance rate but
            # never correctness (streams are invariant to proposals).
            _, dnew = self._draft_prefill(self.draft_params, tj, lj)
        # ring layout never skips (the gate needs the page registry): the
        # wave's rows merge in order
        self._merge([s for s, _ in wave], range(len(wave)),
                    new=new_cache if self._pages is None else None,
                    dnew=dnew, drafts=[(s, ri) for (s, _), ri in
                                       zip(wave, rows) if ri >= 0])
        return logits

    def _admit_sample_first(self, reqs, first_lens, logits):
        """Sample every wave row's first token with the SAME policy + key
        split the decode window would use — a request's stream is then
        identical whether its first token comes from the wave prefill
        (whole prompt consumed) or from the loop's last ingest step
        (chunked).  At temperature=0 this is exact argmax, matching the
        seed engine.  Knobs are padded to the full (W,) bucket and the
        sampler is the shared jitted entry point, so the value is bitwise
        identical under sync and overlapped admission (sample_tokens is
        batch-invariant per row).  Returns device futures.  Thread-safe
        (pure numpy + jax dispatch), so the admission worker can run it
        off-thread."""
        W = logits.shape[0]
        specs = [r.sampling or self.sampling for r in reqs]
        keys0 = np.zeros((W, 2), np.uint32)
        temp = np.zeros(W, np.float32)
        top_k = np.zeros(W, np.int32)
        top_p = np.ones(W, np.float32)
        eos = np.full(W, -1, np.int32)
        full = np.zeros(W, bool)
        for i, (sp, r) in enumerate(zip(specs, reqs)):
            keys0[i] = sp.slot_key(r.uid)
            temp[i] = sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
            eos[i] = -1 if r.eos_id is None else r.eos_id
            full[i] = first_lens[i] == len(r.prompt)
        ks = S.split_keys(jnp.asarray(keys0))
        first = S.sample_tokens_jit(logits, jnp.asarray(temp),
                                    jnp.asarray(top_k), jnp.asarray(top_p),
                                    ks[:, 1])
        return specs, keys0, eos, full, ks, first

    def _admit_bookkeep(self, slot, r, sp, first_len, eos_id):
        """Mirror writes common to both admission paths (everything the
        host knows without touching the device)."""
        st = self._st
        st["cur"][slot] = first_len
        st["keys"][slot] = 0              # real keys land per-path
        st["temp"][slot] = sp.temperature
        st["top_k"][slot] = sp.top_k
        st["top_p"][slot] = sp.top_p
        st["eos"][slot] = eos_id
        st["bpos"][slot] = 0
        st["act"][slot] = True
        if "spec_on" in st:
            # adaptive degradation is per REQUEST: a fresh admission gets
            # the draft back, with clean accept/propose accumulators
            st["spec_on"][slot] = True
        self._spec_acc[slot] = 0
        self._spec_prop[slot] = 0
        if "hist" in st:
            # the WHOLE prompt is known at admission (even the not-
            # yet-ingested tail): seed the n-gram corpus up front
            st["hist"][slot] = 0
            st["hist"][slot, : len(r.prompt)] = r.prompt
        rest = r.prompt[first_len:]
        if rest.size == 0:
            st["tok"][slot] = 0           # real first token lands per-path
            st["left"][slot] = r.max_new_tokens - 1
            st["avail"][slot] = 0
            st["more"][slot] = False
        else:
            # chunked prefill: stream the remainder through the
            # decode loop's ingest buffer
            self.scheduler.set_pending(slot, rest)
            self._load_chunk(slot)
            st["tok"][slot] = 0
            st["left"][slot] = r.max_new_tokens

    def _admit(self):
        """Synchronous admission: wave prefill (skip members ride
        resident registry pages instead), first-token sample for the
        prefill rows, at most one host sync, mirror writes.  An all-skip
        wave admits with ZERO device syncs — its first tokens come from
        the decode loop's ingest steps."""
        taken = self._admission_wave()
        if taken is None:
            return
        wave, first_lens, rows, toks, lens = taken
        logits = self._admit_prefill(wave, first_lens, rows, toks, lens)
        full = ks = first = None
        if logits is not None:
            preqs = [r for (_, r), ri in zip(wave, rows) if ri >= 0]
            pflens = [fl for fl, ri in zip(first_lens, rows) if ri >= 0]
            _, _, _, full, ks, first_dev = self._admit_sample_first(
                preqs, pflens, logits)
            first = np.asarray(first_dev)
            ks = np.asarray(ks)
            self.host_syncs += 1
            self.admission_syncs += 1
        st = self._st
        for i, (slot, r) in enumerate(wave):
            sp = r.sampling or self.sampling
            eos_id = -1 if r.eos_id is None else r.eos_id
            self._admit_bookkeep(slot, r, sp, first_lens[i], eos_id)
            st["keys"][slot] = sp.slot_key(r.uid)
            ri = rows[i]
            if ri >= 0 and full[ri]:
                # whole prompt prefilled: emit the first generated token
                # right away (as the seed engine did) and advance the key
                st["keys"][slot] = ks[ri, 0]
                st["tok"][slot] = first[ri]
                self._admit_tokens += 1
                self._record_token(r, int(first[ri]))
                if r.done:
                    self._finish(slot)

    def _load_chunk(self, slot: int):
        chunk = self.scheduler.next_chunk(slot)
        st = self._st
        w = chunk.shape[0]
        st["buf"][slot, :w] = chunk
        st["avail"][slot] = w
        st["bpos"][slot] = 0
        st["more"][slot] = self.scheduler.pending_len(slot) > 0

    def _refill(self):
        st = self._st
        for slot, r in enumerate(self.scheduler.slot_req):
            if (r is not None and st["act"][slot]
                    and st["bpos"][slot] >= st["avail"][slot]
                    and self.scheduler.pending_len(slot) > 0):
                self._load_chunk(slot)

    # -- lazy page reservation + preemption -----------------------------------
    #
    # With lazy_pages=True admission maps only the admitted coverage plus
    # one top-up interval's headroom instead of the worst-case reach; at
    # every window boundary _lazy_topup extends each active slot's
    # mapping to stay ahead of ``cur``.  When the pool runs dry the
    # admission policy picks a running victim to PREEMPT: its carry row,
    # pages, and alloc stamps are snapshotted, its fully-written prompt
    # pages are registered (so sharers or its own resurrection can find
    # them), and its pages are freed.  Re-admission (_readmit_preempted,
    # boundary priority over fresh admissions) resurrects surviving
    # pages and rebuilds recycled ones by re-prefilling the fed history
    # over just the lost page columns — streams are token-for-token
    # identical to an un-preempted run because the carry row (keys, cur,
    # left, ingest buffer) is restored verbatim.

    def _lazy_topup(self):
        """Boundary half of lazy reservation: extend every active slot's
        page mapping to cover ``cur + headroom`` (capped at its reach),
        position-wiping the fresh pages on device — nothing prefills
        them, and a recycled page's stale positions would otherwise read
        as valid cache."""
        if not self.lazy_pages:
            return
        st = self._st
        ps = self.page_size
        H = self._headroom
        for slot, r in enumerate(list(self.scheduler.slot_req)):
            if r is None or not st["act"][slot]:
                continue
            reach = min(len(r.prompt) + r.max_new_tokens, self.max_len)
            tgt = -(-min(int(st["cur"][slot]) + H, reach) // ps)
            have = len(self._slot_pages[slot])
            if tgt <= have:
                continue
            own = self._alloc_with_preemption(slot, tgt - have)
            if own is None:
                continue              # the slot itself was parked
            for pg in own:
                self._prefixes.drop_page(pg)
                self._prefix_hits.pop(pg, None)
            self._slot_pages[slot].extend(own)
            row = st["ptab"][slot]
            row[have: have + len(own)] = own
            self.cache = T.wipe_pages(self.cache,
                                      jnp.asarray(own, jnp.int32))
            if self.overlap:
                self._ensure_dev_state()
                self._scatter_rows(np.array([slot], np.int32),
                                   {"ptab": row[None]})
        self._update_pins()

    def _alloc_with_preemption(self, slot: int, need: int):
        """Allocate ``need`` pages for a running slot, preempting policy-
        chosen victims while the pool is short.  Returns the pages, or
        None when the slot itself had to be parked (no other victim
        could cover it — admission's solo-servability check guarantees
        it can be re-seated once the pool drains)."""
        tried = {slot}
        while not self._pages.can_alloc(need):
            cands = self._victim_candidates(tried)
            if not cands:
                self._preempt_slot(slot)
                return None
            victim = self.policy.pick_victim(cands)
            tried.add(victim)
            self._preempt_slot(victim)
        return self._pages.alloc(need)

    def _victim_candidates(self, exclude):
        """Active slots in admission order, oldest first — the universe
        ``policy.pick_victim`` chooses from (the default evicts the
        youngest, minimizing wasted work)."""
        pos = {}
        for i, uid in enumerate(self.scheduler.admitted_uids):
            pos[uid] = i
        cands = sorted(
            (pos.get(r.uid, -1), slot)
            for slot, r in enumerate(self.scheduler.slot_req)
            if (r is not None and slot not in exclude
                and self._st["act"][slot]))
        return [(slot, self.scheduler.slot_req[slot])
                for _, slot in cands]

    def _preempt_slot(self, slot: int) -> bool:
        """Evict a running slot: snapshot its carry row, register its
        fully-written prompt pages, free everything, park the request.
        Under overlap this is the pipeline's one deliberate full sync —
        reading the leading carry's row waits for every dispatched
        window, so the snapshot includes all their effects (their token
        emissions still reach the stream in dispatch order through the
        backlog).  Returns False when the slot turns out to have
        finished on device already (its retirement settles at harvest;
        its pages free there)."""
        if self.overlap:
            self._ensure_dev_state()
            row = {k: np.asarray(v[slot])
                   for k, v in self._st_dev.items()}
        else:
            row = {k: np.array(v[slot]) for k, v in self._st.items()}
        if not bool(row["act"]):
            return False
        if self.overlap:
            # deactivate on the leading carry so windows dispatched from
            # here on ignore the slot; epoch-gate in-flight statuses
            self._st_dev = T.preempt_slot(self._st_dev, slot)
            self._slot_epoch[slot] = self._dispatch_index
            self._buf_epoch[slot] = self._dispatch_index
        st = self._st
        st["act"][slot] = False
        st["avail"][slot] = 0
        st["bpos"][slot] = 0
        st["more"][slot] = False
        st["left"][slot] = 0
        with self._sched_lock:
            req, pending = self.scheduler.preempt(slot)
        ps = self.page_size
        cur = int(row["cur"])
        pages = list(self._slot_pages[slot])
        stamps = [self._pages.alloc_stamp(pg) for pg in pages]
        for j, pg in enumerate(pages):
            # fully-written prompt pages stay discoverable: a prefix
            # sharer — or this request's own resurrection — can pull
            # them back while they survive in the free list
            if (j + 1) * ps <= min(cur, len(req.prompt)):
                self._prefixes.register(prefix_key(req.prompt, j, ps), pg)
        for pg in pages:
            self._pages.free(pg)
        self._slot_pages[slot] = []
        st["ptab"][slot] = 0
        self._preempted.append(PreemptedRecord(
            req=req, host_row=row, pending=pending, pages=pages,
            stamps=stamps, cur=cur, keys0=np.array(row["keys"])))
        with self._mlock:
            self.preemptions += 1
        return True

    def _readmit_preempted(self):
        """Re-seat parked requests, oldest first, when a slot and pages
        are available (boundary priority over fresh admissions).  Pages
        whose alloc stamp is unchanged still hold the victim's content:
        resurrect at refcount 0, retain when a prefix sharer took them.
        Recycled pages are rebuilt — content pages by re-prefilling the
        fed history over just those columns, ahead-of-cur pages by a
        position wipe.  A zero-rebuild resurrection costs no prefill."""
        pool = self._pages
        while self._preempted:
            rec = self._preempted[0]
            with self._sched_lock:
                free = self.scheduler._wave_slot_order(1)
            if not free:
                return
            surv = [pool.alloc_stamp(pg) == stp
                    for pg, stp in zip(rec.pages, rec.stamps)]
            lost = [j for j, s in enumerate(surv) if not s]
            # surviving refcount-0 unpinned pages leave the free list on
            # resurrect, so the lost replacements must fit AFTER them
            surv_free = sum(
                1 for pg, s in zip(rec.pages, surv)
                if s and pool.refcount(pg) == 0 and not pool.is_pinned(pg))
            if pool.free_count - surv_free < len(lost):
                return
            slot = free[0]
            with self._sched_lock:
                self.scheduler.place(slot, rec.req)
            # claim every survivor FIRST (pulling it off the free list)
            # — allocating a lost page's replacement earlier could
            # recycle a survivor out from under its stale surv flag
            mapping = [None] * len(rec.pages)
            for j, (pg, ok_) in enumerate(zip(rec.pages, surv)):
                if ok_:
                    if pool.refcount(pg) == 0:
                        pool.resurrect(pg)
                    else:
                        pool.retain(pg)
                    mapping[j] = pg
            wipe, rebuild = [], []
            for j in lost:
                npg = pool.alloc(1)[0]
                self._prefixes.drop_page(npg)
                self._prefix_hits.pop(npg, None)
                mapping[j] = npg
                if j * self.page_size < rec.cur:
                    rebuild.append(j)
                else:
                    wipe.append(npg)
            if wipe:
                self.cache = T.wipe_pages(self.cache,
                                          jnp.asarray(wipe, jnp.int32))
            if rebuild:
                self._rebuild_pages(rec, mapping, rebuild)
            else:
                with self._mlock:
                    self.prefill_calls_saved += 1
            self._slot_pages[slot] = list(mapping)
            st = self._st
            for k, v in rec.host_row.items():
                st[k][slot] = v
            row = st["ptab"][slot]
            row[:] = 0
            row[: len(mapping)] = mapping
            with self._sched_lock:
                self.scheduler.set_pending(
                    slot, np.asarray(rec.pending, np.int32))
            if self.overlap:
                self._ensure_dev_state()
                rows_all = {k: np.asarray(st[k][slot])[None] for k in st}
                self._scatter_rows(np.array([slot], np.int32), rows_all)
                self._slot_epoch[slot] = self._dispatch_index
                self._buf_epoch[slot] = self._dispatch_index
            self._update_pins()
            self._preempted.popleft()

    def _rebuild_pages(self, rec: PreemptedRecord, mapping, rebuild):
        """Recompute recycled pages' cache content: prefill the tokens
        the victim had FED (cache content at position t is a pure
        function of the token fed at t) and scatter just the lost page
        columns.  Uses the spec hist leaf when present — it IS the fed
        history — else the prompt plus the settled out_tokens (the
        backlog is flushed first so the generated history is whole)."""
        cur = rec.cur
        if "hist" in rec.host_row:
            fed = np.asarray(rec.host_row["hist"][:cur], np.int32)
        else:
            if self._backlog is not None and self._backlog.started:
                self._backlog.flush()
            prompt = np.asarray(rec.req.prompt, np.int32)
            P = len(prompt)
            gen = (np.asarray(rec.req.out_tokens[: cur - P], np.int32)
                   if cur > P else np.zeros((0,), np.int32))
            fed = np.concatenate([prompt[: min(cur, P)], gen])
        toks = np.zeros((1, _bucket(cur, self.max_len)), np.int32)
        toks[0, :cur] = fed
        lens = np.array([cur], np.int32)
        self._count_prefill(toks, lens)
        tj, lj = self._prefill_args(toks, lens)
        _, new_cache = self._prefill(self.params, tj, lj)
        self.cache = _merge_slot_paged(
            self.cache, new_cache, jnp.asarray([0] * len(rebuild)),
            jnp.asarray(rebuild),
            jnp.asarray([mapping[j] for j in rebuild]), self.page_size)

    # -- overlapped pipeline --------------------------------------------------
    #
    # The double-buffered loop keeps the carry ON DEVICE (self._st_dev)
    # and up to two windows in flight.  At each boundary the host:
    #   1. blocks on the TRAILING window's packed (act, bpos) status —
    #      the pipeline's single device sync — retires finished slots,
    #      and hands its token futures to the backlog worker;
    #   2. applies its admission/refill decisions to the LEADING window's
    #      *output* futures via eager scatters (functional updates chain
    #      by dataflow, so no device round-trip is needed);
    #   3. dispatches the next window on the merged carry.
    # The numpy mirror self._st stays authoritative for host-owned leaves
    # and is refreshed for act/bpos at harvests, gated by per-slot epochs
    # (a harvested status is stale for slots touched at later boundaries).

    def _ensure_dev_state(self):
        if self._st_dev is None:
            # from a copy: on the CPU a device_put of an aligned host
            # buffer is zero-copy (may_alias=False too), and the host keeps
            # writing its mirror while programs that read this carry queue
            self._st_dev = {k: jax.device_put(v.copy(), self._carry_sh[k])
                            for k, v in self._st.items()}

    def _scatter_rows(self, slots_pad: np.ndarray, host_rows: dict,
                      guard_gen=None):
        """Scatter per-slot rows into the device carry.  ``slots_pad`` is
        bucket-padded with out-of-range index B; mode="drop" discards the
        pad rows, so bucketing never writes a real slot.

        ``guard_gen`` (continuous batching): the host's per-slot
        generation counters at decision time.  An in-scan install may
        have repopulated a slot since — the device compares its ``gen``
        leaf against the guard and redirects mismatched rows to the drop
        index, so a stale host decision can never clobber a freshly
        installed request."""
        sl = jnp.asarray(slots_pad)
        st = dict(self._st_dev)
        if guard_gen is not None:
            ok = st["gen"][sl] == jnp.asarray(guard_gen)
            sl = jnp.where(ok, sl, self.B)
        for k, rows in host_rows.items():
            # a copy: rows may be views of the mirror (see _ensure_dev_state)
            st[k] = st[k].at[sl].set(jnp.array(rows, st[k].dtype),
                                     mode="drop")
        self._st_dev = st

    def _merge(self, slots, rows, *, new=None, dnew=None, drafts=None,
               host=None, pick=None, first=None, ks=None, stage=False):
        """One admission merge, as one compiled program (_admission_merge):
        prefill rows ``rows`` of ``new`` into ring slots ``slots`` — of
        the resident pool, or of the continuous stage with ``stage`` —
        the layer draft's (slot, row) pairs ``drafts`` of ``dnew``, and,
        with ``host`` (host-known carry rows) and ``pick`` (full, eos,
        left, keys0 per row), every carry row.  The pool, draft ring and
        carry are donated (where donation takes effect) and come back with
        the window's shardings, so the next window neither reshards nor
        retraces."""
        part = (stage, new is not None, dnew is not None, host is not None)
        if not any(part[1:]):
            return
        if host is None:
            first = ks = None
        elif self.aot and first is not None:
            first, ks = jax.device_put((first, ks), self._repl)
        ix = self._merge_ix(slots, rows, drafts if dnew is not None else None,
                            host, pick)
        fn = self._merge_exec.get(
            self._merge_key(part, new, dnew, first, len(ix["slots"])))
        if fn is None:
            fn = self._merge_jit(part)
        cache, dcache, carry = fn(*self._merge_targets(part), new, dnew,
                                  first, ks, ix)
        if stage:
            self._stage_dev = {**self._stage_dev, "seq": carry.pop("seq"),
                               "rows": carry}
            if cache is not None:
                self._stage_dev["cache"] = cache
            return
        if cache is not None:
            self.cache = cache
        if dcache is not None:
            self.draft_cache = dcache
        if carry is not None:
            self._st_dev = carry

    def _merge_targets(self, part):
        """(cache, draft cache, carry) a merge of ``part`` writes into;
        the stage's carry is its rows plus its seq keys."""
        stage, with_cache, with_draft, with_carry = part
        if stage:
            return (self._stage_dev.get("cache") if with_cache else None,
                    None, {**self._stage_dev["rows"],
                           "seq": self._stage_dev["seq"]})
        return (self.cache if with_cache else None,
                self.draft_cache if with_draft else None,
                self._st_dev if with_carry else None)

    def _merge_jit(self, part):
        """The jitted merge for ``part`` = (stage, cache, draft, carry),
        its outputs pinned to where the window reads them."""
        fn = self._merge_jits.get(part)
        if fn is None:
            stage = part[0]
            if stage:
                sh = (self._stage_sh.get("cache"), None,
                      {**self._stage_sh["rows"], "seq": self._stage_sh["seq"]})
            else:
                sh = (self._cache_shardings, self._draft_cache_shardings,
                      self._carry_sh)
            out = tuple(s if on else None for s, on in zip(sh, part[1:]))

            def counted(*args):
                self.trace_counts["merge"] += 1
                return _admission_merge(*args)

            fn = self._merge_jits[part] = jax.jit(
                counted, donate_argnums=self._merge_donate, out_shardings=out)
        return fn

    @staticmethod
    def _merge_key(part, new, dnew, first, rb: int):
        """What selects a merge program: its parts, row bucket, and the
        shapes of the wave it merges from."""
        return part, rb, tuple((x.shape, x.dtype)
                               for x in jax.tree.leaves((new, dnew, first)))

    def _merge_ix(self, slots, rows, drafts=None, host=None, pick=None):
        """A merge's row vectors (host numpy), padded to the slot-count
        bucket: a pad slot is B (dropped), a pad row 0."""
        rb = _bucket(len(slots), self.B)

        def pad(a, dtype, fill=0):
            a = np.asarray(a, dtype)
            out = np.full((rb,) + a.shape[1:], fill, dtype)
            out[: a.shape[0]] = a
            return out

        ix = {"slots": pad(slots, np.int32, self.B),
              "rows": pad(rows, np.int32)}
        if drafts is not None:
            ix["dslots"] = pad([s for s, _ in drafts], np.int32, self.B)
            ix["drows"] = pad([r for _, r in drafts], np.int32)
        if host is not None:
            ix["host"] = {k: pad(v, v.dtype) for k, v in host.items()}
            full, eos, left, keys0 = pick
            ix |= {"full": pad(full, bool), "eos": pad(eos, np.int32),
                   "left": pad(left, np.int32),
                   "keys0": pad(keys0, np.uint32)}
        return ix

    def _prepare_wave(self, reqs) -> StagedWave:
        """Stage a wave OFF the admission path: bucket the prompts,
        dispatch the prefill into a FRESH per-wave cache, and sample each
        row's first token.  Pure device dispatch against immutable engine
        state — no scheduler, pool, or mirror mutation — so the admission
        worker thread can run it concurrently with boundary work.  All
        merging happens later, on the main thread, at a boundary."""
        first_lens = [self.scheduler.first_chunk_len(r) for r in reqs]
        # skip decision from a read-only registry probe (worker-thread
        # safe); the boundary merge re-resolves pages authoritatively,
        # and either direction of drift is correct (the remainder just
        # streams through ingest from wherever coverage actually ends)
        rows, w = self._skip_rows(reqs)
        specs = [r.sampling or self.sampling for r in reqs]
        keys0 = np.zeros((len(reqs), 2), np.uint32)
        eos = np.full(len(reqs), -1, np.int32)
        full = np.zeros(len(reqs), bool)
        for i, (sp, r) in enumerate(zip(specs, reqs)):
            keys0[i] = sp.slot_key(r.uid)
            if r.eos_id is not None:
                eos[i] = r.eos_id
            full[i] = rows[i] >= 0 and first_lens[i] == len(r.prompt)
        ks = first = new_cache = draft_new = None
        toks = np.zeros((0, 0), np.int32)
        if w:
            toks, lens = self._bucket_prompts(reqs, first_lens, rows, w)
        with self._stage("admission.prepare", uids=_ids(r.uid for r in reqs),
                         rows=toks.shape[0], length=toks.shape[1]):
            if w:
                self._count_prefill(toks, lens)
                tj, lj = self._prefill_args(toks, lens)
                logits, new_cache = self._prefill(self.params, tj, lj)
                if self.draft_cache is not None:
                    _, draft_new = self._draft_prefill(self.draft_params,
                                                       tj, lj)
                preqs = [r for r, ri in zip(reqs, rows) if ri >= 0]
                pflens = [fl for fl, ri in zip(first_lens, rows) if ri >= 0]
                _, _, _, _, ks, first = self._admit_sample_first(
                    preqs, pflens, logits)
        return StagedWave(reqs=list(reqs), first_lens=first_lens,
                          specs=specs, keys0=keys0, eos=eos, full=full,
                          ks=ks, first=first, new_cache=new_cache,
                          draft_new_cache=draft_new, rows=rows)

    def _admit_overlap(self):
        """Boundary admission for the overlapped engine: collect prepared
        waves (from the worker, or prepared inline), then merge them —
        straight into free slots, or into the device staging queue under
        continuous batching.  ``host_syncs``/``admission_syncs`` tick once
        per wave at its FIRST merge, however many boundaries the merge
        spans, preserving the host_syncs == windows + admission_syncs
        identity."""
        if self._admission is not None:
            self._staged_waves.extend(self._admission.poll())
        else:
            cap = self._staging_capacity()
            if cap > 0:
                reqs = self._take_staged_locked(cap)
                if reqs:
                    self._staged_waves.append(self._prepare_wave(reqs))
        if self.continuous:
            self._stage_from_waves()
        else:
            self._place_from_waves()
        if self._admission is not None:
            self._admission.kick(self._staging_capacity())

    def _place_from_waves(self):
        """Merge prepared waves into free slots (non-continuous overlap).
        Head-of-line FIFO like every admission path: a wave that doesn't
        fully fit (slots or pages) blocks the ones behind it and resumes
        at the next boundary."""
        while self._staged_waves:
            wv = self._staged_waves[0]
            todo = wv.reqs[wv.merged:]
            if not todo:
                self._staged_waves.popleft()
                continue
            with self._sched_lock:
                free = len(self.scheduler.free_slots())
            n = min(len(todo), free)
            if self._pages is not None:
                fits = self._page_fits()
                fit = 0
                for r in todo[:n]:
                    if not fits(r):
                        break
                    fit += 1
                n = fit
            if n == 0:
                return
            if wv.merged == 0:
                self.host_syncs += 1
                self.admission_syncs += 1
            with self._sched_lock:
                placed = self.scheduler.place_wave(todo[:n])
            idx = list(range(wv.merged, wv.merged + n))
            slots = [s for s, _ in placed]
            with self._stage("engine.merge",
                             uids=_ids(r.uid for _, r in placed),
                             slots=_ids(slots)) as t_merge:
                self._note_refills(slots, t_merge)
                self._merge_wave_rows(wv, placed, idx)
            wv.merged += n
            if wv.merged < len(wv.reqs):
                return
            self._staged_waves.popleft()

    def _merge_wave_rows(self, wv: StagedWave, placed, idx):
        """Merge wave rows ``idx`` into their placed slots: cache
        scatter, mirror bookkeeping, carry-row scatter, and the deferred
        first-token emission — the device half of what _admit does
        synchronously, expressed as dataflow on the leading carry."""
        st = self._st
        prow = ((lambda i: i) if wv.rows is None
                else (lambda i: wv.rows[i]))
        if self._pages is not None:
            rws, cols, phys = [], [], []
            for i, (slot, r) in zip(idx, placed):
                if prow(i) < 0:
                    # authoritative skip-path binding; the probe's guess
                    # is replaced by the coverage actually resident now
                    wv.first_lens[i] = self._assign_shared_pages(slot, r)
                    continue
                mapping, scat = self._assign_pages(slot, r,
                                                   wv.first_lens[i])
                for j in scat:
                    rws.append(prow(i))
                    cols.append(j)
                    phys.append(mapping[j])
            if phys:
                self.cache = _merge_slot_paged(
                    self.cache, wv.new_cache, jnp.asarray(rws),
                    jnp.asarray(cols), jnp.asarray(phys), self.page_size)
        for i, (slot, r) in zip(idx, placed):
            self._admit_bookkeep(slot, r, wv.specs[i], wv.first_lens[i],
                                 wv.eos[i])
            st["keys"][slot] = wv.keys0[i]   # placeholder: device = truth
            if wv.full[i]:
                self._admit_tokens += 1
            self._slot_epoch[slot] = self._dispatch_index
            self._buf_epoch[slot] = self._dispatch_index
        # host-known carry rows from the mirror the bookkeeping just
        # wrote; tok/keys/act depend on the sampled first token and are
        # picked on device.  A skip member (no prefill row) picks row 0,
        # which its full=False never reads.
        slots = [s for s, _ in placed]
        self._merge(
            slots, [max(prow(i), 0) for i in idx],
            new=wv.new_cache if self._pages is None else None,
            dnew=wv.draft_new_cache,
            drafts=[(s, prow(i)) for i, s in zip(idx, slots)
                    if prow(i) >= 0],
            host={k: arr[slots] for k, arr in st.items()
                  if k not in DEVICE_ROWS},
            pick=(wv.full[idx], wv.eos[idx],
                  [wv.reqs[i].max_new_tokens - 1 for i in idx],
                  wv.keys0[idx]),
            first=wv.first, ks=wv.ks)
        entries = [(r, prow(i)) for i, (_, r) in zip(idx, placed)
                   if wv.full[i]]
        if entries:
            self._backlog.put(self._drained(
                self._admit_item(wv.first, entries), self._dispatch_index))

    def _stage_bookkeep(self, r: Request, sp, first_len: int, eos_id):
        """Host-known carry ROW for a staged request — everything
        _admit_bookkeep writes to the mirror, built standalone so the
        install can land it on whichever slot the device picks.  Returns
        (row dict over every carry leaf, pending prompt tail)."""
        st = self._st
        row = {k: np.zeros(v.shape[1:], v.dtype) for k, v in st.items()}
        row["cur"][...] = first_len
        row["temp"][...] = sp.temperature
        row["top_k"][...] = sp.top_k
        row["top_p"][...] = sp.top_p
        row["eos"][...] = eos_id
        row["act"][...] = True
        if "hist" in row:
            row["hist"][: len(r.prompt)] = r.prompt
        if "spec_on" in row:
            row["spec_on"][...] = True
        rest = r.prompt[first_len:]
        if rest.size == 0:
            row["left"][...] = r.max_new_tokens - 1
            pending = np.zeros((0,), np.int32)
        else:
            # the ingest buffer row is W = prefill_chunk-or-1 wide; a
            # pending tail with no configured chunk (prefill-skip) must
            # stream one token per iteration like the sync path does
            width = self.scheduler.prefill_chunk or 1
            chunk, pending = rest[:width], rest[width:]
            row["buf"][: chunk.shape[0]] = chunk
            row["avail"][...] = chunk.shape[0]
            row["more"][...] = pending.size > 0
            row["left"][...] = r.max_new_tokens
        return row, pending

    def _stage_from_waves(self):
        """Continuous batching: move prepared wave rows into the device
        staging queue (carry rows + FIFO seq keys + cache content),
        bounded by free stage rows and — paged — the page budget.
        Head-of-line FIFO, like every admission path.  Requests stay in
        ``scheduler.staged`` until their install is confirmed at a
        harvest; the scan itself picks the slot."""
        free_rows = [q for q, e in enumerate(self._stage_tab) if e is None]
        while self._staged_waves and free_rows:
            wv = self._staged_waves[0]
            if wv.merged >= len(wv.reqs):
                self._staged_waves.popleft()
                continue
            i = wv.merged
            r = wv.reqs[i]
            if (self._pages is not None
                    and self._pages_needed(r) > self._pages.free_count):
                return
            if wv.merged == 0:
                self.host_syncs += 1
                self.admission_syncs += 1
            self._stage_one(wv, i, free_rows.pop(0))
            wv.merged += 1
        while (self._staged_waves
               and self._staged_waves[0].merged
                   >= len(self._staged_waves[0].reqs)):
            self._staged_waves.popleft()

    def _stage_one(self, wv: StagedWave, i: int, q: int):
        """Scatter wave row ``i`` into stage row ``q``: the host-known
        carry row, the device first-token pieces, the monotone seq key
        the scan's installer FIFOs on, and the prefilled cache content
        (stage cache row for ring, pool pages for paged)."""
        r = wv.reqs[i]
        ri = i if wv.rows is None else wv.rows[i]
        pages = mapping = None
        if self._pages is not None:
            if ri < 0:
                # prefill-skip: bind resident registry pages now (the
                # authoritative walk) and stage from their coverage
                mapping, hit_len = self._map_shared_pages(r)
                wv.first_lens[i] = hit_len
                with self._mlock:
                    self.prefill_calls_saved += 1
            else:
                mapping, scat = self._map_pages(r, wv.first_lens[i])
                rws = [ri] * len(scat)
                cols = list(scat)
                phys = [mapping[j] for j in scat]
                if phys:
                    # freshly-allocated (refcount-1) pages only, chained
                    # on the LATEST cache future: no in-flight window
                    # reads them, and the window that can see this seq
                    # key sees the pages
                    self.cache = _merge_slot_paged(
                        self.cache, wv.new_cache, jnp.asarray(rws),
                        jnp.asarray(cols), jnp.asarray(phys),
                        self.page_size)
            pages = list(mapping)
        row, pending = self._stage_bookkeep(r, wv.specs[i],
                                            wv.first_lens[i], wv.eos[i])
        if mapping is not None:
            row["ptab"][: len(mapping)] = mapping
        seq_val = self._stage_seq_next
        self._stage_seq_next += 1
        ent = StagedEntry(req=r, host_row=row, pending=pending,
                          pages=pages, seq=seq_val, keys0=wv.keys0[i],
                          full=bool(wv.full[i]))
        # a skip member (ri < 0) is never full: no sampled first token, it
        # starts feeding from the ingest buffer with its base key
        host = {k: v[None] for k, v in row.items() if k not in DEVICE_ROWS}
        host["seq"] = np.array([seq_val], np.int32)
        self._merge([q], [max(ri, 0)],
                    new=wv.new_cache if self._pages is None else None,
                    host=host,
                    pick=([wv.full[i]], [wv.eos[i]], [r.max_new_tokens - 1],
                          wv.keys0[i][None]),
                    first=wv.first, ks=wv.ks, stage=True)
        self._stage_tab[q] = ent
        self._stage_by_seq[seq_val] = (q, ent)
        if ent.full:
            # first token was emitted at STAGE time (parity with direct
            # admission); it must reach the stream before any window item
            # carrying this request's later tokens — backlog FIFO does it
            self._admit_tokens += 1
            self._backlog.put(self._drained(
                self._admit_item(wv.first, [(r, ri)]), self._dispatch_index))

    def _admit_item(self, first, entries):
        def item():
            arr = np.asarray(first)
            for r, i in entries:
                self._record_token(r, int(arr[i]))
        return item

    def _refill_async(self):
        """Refill drained ingest buffers and scatter them into the
        leading carry.  The mirror's (bpos, avail) pair is epoch-gated at
        harvest, so a chunk loaded at boundary d cannot be double-loaded
        off a pre-d status."""
        st = self._st
        slots = [slot for slot, r in enumerate(self.scheduler.slot_req)
                 if (r is not None and st["act"][slot]
                     and st["bpos"][slot] >= st["avail"][slot]
                     and self.scheduler.pending_len(slot) > 0)]
        if not slots:
            return
        for slot in slots:
            self._load_chunk(slot)
            self._buf_epoch[slot] = self._dispatch_index
        n = len(slots)
        R_ = _bucket(n, self.B)
        slots_pad = np.full(R_, self.B, np.int32)
        slots_pad[:n] = slots
        host_rows = {}
        for k in ("buf", "avail", "bpos", "more"):
            arr = st[k]
            rows = np.zeros((R_,) + arr.shape[1:], arr.dtype)
            for i, slot in enumerate(slots):
                rows[i] = arr[slot]
            host_rows[k] = rows
        gg = None
        if self.continuous:
            gg = np.zeros(R_, np.int32)
            gg[:n] = st["gen"][slots]
        self._scatter_rows(slots_pad, host_rows, guard_gen=gg)

    def _dispatch_window(self) -> bool:
        """One pipeline boundary's front half: launch the next window on
        the merged leading carry.  Returns False when nothing is active
        to decode AND (under continuous batching) nothing is staged for
        an in-scan install."""
        staged_pending = (self.continuous
                          and any(e is not None for e in self._stage_tab))
        if not (self._st["act"].any() or staged_pending):
            return False
        occ, qd = self.scheduler.occupancy, self.scheduler.queue_depth
        prior = self._inflight[-1] if self._inflight else None
        overlapped = prior is not None and not prior.status.is_ready()
        acc = prop = sw_seq = sw_slot = None
        if self.draft_cache is not None:
            (self.cache, self.draft_cache, st2, toks, emits, acc,
             prop, n_act) = self._window(self.params, self.draft_params,
                                         self.cache, self.draft_cache,
                                         self._st_dev)
        elif self.continuous and self.spec_depth > 0:
            (self.cache, st2, seq, sw_seq, sw_slot, toks, emits, acc,
             prop, n_act) = self._window(self.params, self.cache,
                                         self._st_dev, self._stage_dev)
            self._stage_dev = {**self._stage_dev, "seq": seq}
        elif self.continuous:
            (self.cache, st2, seq, sw_seq, sw_slot, toks, emits,
             n_act) = self._window(self.params, self.cache,
                                   self._st_dev, self._stage_dev)
            self._stage_dev = {**self._stage_dev, "seq": seq}
        elif self.spec_depth > 0:
            self.cache, st2, toks, emits, acc, prop, n_act = self._window(
                self.params, self.cache, self._st_dev)
        else:
            self.cache, st2, toks, emits, n_act = self._window(
                self.params, self.cache, self._st_dev)
        self._st_dev = st2
        # pack the harvest-critical pieces into ONE 1-D array at dispatch
        # so the trailing-boundary block is a single small transfer; the
        # harvest parses it positionally by the same layout
        parts = [st2["act"].astype(jnp.int32), st2["bpos"].astype(jnp.int32),
                 st2["cur"].astype(jnp.int32)]
        if self.continuous:
            parts.append(st2["gen"])
        if self.adaptive_spec:
            parts.append(acc.sum(axis=0))
            parts.append(prop.sum(axis=0))
        if self.continuous:
            parts.append(sw_seq)
            parts.append(sw_slot)
        parts.append(n_act.sum(axis=0).reshape(-1))
        status = jnp.concatenate(parts)
        self._inflight.append(InflightWindow(
            index=self._dispatch_index, status=status, toks=toks,
            emits=emits, slot_reqs=list(self.scheduler.slot_req),
            occ=occ, qd=qd, overlapped=overlapped, acc=acc, prop=prop))
        self._dispatch_index += 1
        if overlapped:
            self._overlapped_windows += 1
        return True

    def _harvest_trailing(self):
        """Block on the trailing window's status (the pipeline's one
        device sync), process confirmed in-scan installs, refresh the
        epoch-eligible mirror slots, retire finished requests, and hand
        token work to the backlog."""
        w = self._inflight.popleft()
        with self._stage("engine.harvest", window=w.index):
            status = np.asarray(w.status)
        with self._stage("engine.bookkeep"):
            self._bookkeep(w, status)

    def _bookkeep(self, w: InflightWindow, status: np.ndarray):
        """The host half of a harvest, on the trailing window's status."""
        self.host_syncs += 1
        self.windows += 1
        self._occupancy_sum += w.occ
        self._queue_depth_sum += w.qd
        B = self.B
        act = status[:B].astype(bool)
        bpos = status[B: 2 * B]
        cur = status[2 * B: 3 * B]
        off = 3 * B
        accs = props = sw_seq = sw_slot = None
        if self.continuous:
            off += B                      # gen leaf: mirrored per install
        if self.adaptive_spec:
            accs = status[off: off + B]
            props = status[off + B: off + 2 * B]
            off += 2 * B
        if self.continuous:
            K = self.sync_every
            sw_seq = status[off: off + K]
            sw_slot = status[off + K: off + 2 * K]
            off += 2 * K
        self._act_iters += int(status[off])
        self._count_moe(status[off + 1:])
        # snapshot the PRE-install slot->request map and the in-window
        # swap list BEFORE bookkeeping mutates them: the backlog item
        # credits each iteration's tokens to whoever held the slot then
        base = list(w.slot_reqs)
        installs, swaps = [], []
        if sw_seq is not None:
            for k in range(self.sync_every):
                sv = int(sw_seq[k])
                if sv < 0:
                    continue
                q, ent = self._stage_by_seq.pop(sv)
                installs.append((k, int(sw_slot[k]), q, ent))
                swaps.append((k, int(sw_slot[k]), ent.req))
        item = self._window_item(w, base, swaps)
        for k, s, q, ent in installs:
            with self._stage("engine.merge", uids=_ids([ent.req.uid]),
                             slots=_ids([s])) as t_merge:
                self._note_refills([s], t_merge)
                self._install_entry(w, s, q, ent)
        ok = self._slot_epoch <= w.index
        if accs is not None:
            self._adaptive_update(ok, act, accs, props,
                                  {s for _, s, _, _ in installs})
        self._st["act"][ok] = act[ok]
        self._st["cur"][ok] = cur[ok]
        bok = ok & (self._buf_epoch <= w.index)
        self._st["bpos"][bok] = bpos[bok]
        self._backlog.put(self._drained(item, w.index))
        done = [slot for slot, r in enumerate(w.slot_reqs)
                if (r is not None and ok[slot] and not act[slot]
                    and self.scheduler.slot_req[slot] is r)]
        if done:
            with self._stage("engine.finish",
                             uids=_ids(w.slot_reqs[s].uid for s in done),
                             slots=_ids(done)):
                for slot in done:
                    self._finish(slot)
            t_free = time.perf_counter()
            for slot in done:
                self._freed_at[slot] = t_free
        self.slot_swaps += len(installs)

    def _install_entry(self, w: InflightWindow, s: int, q: int,
                       ent: StagedEntry):
        """Main-thread bookkeeping for a CONFIRMED in-scan install: the
        device already owns slot ``s``'s carry row (the scan wrote it at
        iteration time); scheduler, mirror, pages, and epochs catch up
        here, retroactively."""
        if self.scheduler.slot_req[s] is not None:
            # the previous occupant died inside this window before the
            # install; its final tokens ride this window's backlog item
            self._finish(s)
        with self._sched_lock:
            self.scheduler.place(s, ent.req)
        st = self._st
        for k, v in ent.host_row.items():
            if k == "gen":
                continue
            st[k][s] = v
        st["gen"][s] += 1                 # mirror the scan's install bump
        self.scheduler.set_pending(s, np.asarray(ent.pending, np.int32))
        if self._pages is not None:
            self._slot_pages[s] = list(ent.pages)
        self._slot_epoch[s] = w.index
        self._buf_epoch[s] = w.index
        self._spec_acc[s] = 0
        self._spec_prop[s] = 0
        # windows dispatched before this install was known snapshot the
        # OLD occupant; patch them so their items credit the new one
        # from their own iteration 0 (the install predates them all)
        w.slot_reqs[s] = ent.req
        for wf in self._inflight:
            wf.slot_reqs[s] = ent.req
        self._stage_tab[q] = None

    def _adaptive_update(self, ok, act, accs, props, installed):
        """Fold a window's per-slot accept/propose counts into the
        running accumulators and degrade cold-draft slots to plain
        decode.  Sticky per request: spec_on resets at the next
        admission, not mid-request."""
        st = self._st
        degrade = []
        for s in range(self.B):
            if s in installed or not ok[s]:
                continue
            self._spec_acc[s] += int(accs[s])
            self._spec_prop[s] += int(props[s])
            if (st["spec_on"][s] and act[s]
                    and self._spec_prop[s] >= self.ADAPTIVE_MIN_PROPOSED
                    and self._spec_acc[s]
                        < self.ADAPTIVE_ACCEPT_FLOOR * self._spec_prop[s]):
                st["spec_on"][s] = False
                degrade.append(s)
        if not degrade:
            return
        self.spec_degraded += len(degrade)
        if self._st_dev is None:
            return                        # sync engine: mirror uploads
        n = len(degrade)
        Rb = _bucket(n, self.B)
        slots_pad = np.full(Rb, self.B, np.int32)
        slots_pad[:n] = degrade
        gg = None
        if self.continuous:
            gg = np.zeros(Rb, np.int32)
            gg[:n] = st["gen"][degrade]
        self._scatter_rows(slots_pad, {"spec_on": np.zeros(Rb, bool)},
                           guard_gen=gg)

    def _window_item(self, w: InflightWindow, base=None, swaps=None):
        slot_reqs = list(w.slot_reqs) if base is None else base
        swap_iter: dict[int, list] = {}
        for k, s, r in (swaps or ()):
            swap_iter.setdefault(k, []).append((s, r))

        def item():
            toks = np.asarray(w.toks)           # (K, B) or (K, B, S)
            emits = np.asarray(w.emits)
            if toks.ndim == 2:
                toks, emits = toks[:, :, None], emits[:, :, None]
            nemit = int(emits.sum())
            acc = 0 if w.acc is None else int(np.asarray(w.acc).sum())
            prop = 0 if w.prop is None else int(np.asarray(w.prop).sum())
            with self._mlock:
                self.tokens_emitted += nemit
                if nemit == 0:
                    # pipeline bubble: every host-believed-active slot
                    # died in the window in flight when this one launched
                    self.windows_idle += 1
                self.draft_accepted += acc
                self.draft_proposed += prop
            reqs = slot_reqs
            for k in range(toks.shape[0]):
                for s, r in swap_iter.get(k, ()):
                    reqs[s] = r
                for j in range(toks.shape[2]):
                    for i in np.nonzero(emits[k, :, j])[0]:
                        self._record_token(reqs[i], int(toks[k, i, j]))
        return item

    def _step_async(self):
        """One overlapped boundary: harvest the trailing window once the
        pipeline is full (``pipeline_depth`` windows in flight), merge
        staged admissions and refills into the leading carry, then
        dispatch the next window."""
        t0 = time.perf_counter()
        if len(self._inflight) >= self.pipeline_depth:
            self._harvest_trailing()
        self._ensure_dev_state()
        with self._stage("engine.admission_stage"):
            self._readmit_preempted()
            self._admit_overlap()
            self._refill_async()
            self._lazy_topup()
        with self._stage("engine.dispatch", window=self._dispatch_index):
            dispatched = self._dispatch_window()
        if not dispatched:
            if self._inflight:
                # nothing to decode by the host's (possibly stale) view:
                # drain a window — its harvest may retire slots and
                # unblock the queue for the next boundary
                self._harvest_trailing()
            elif (self._admission is not None
                  and (self._admission.busy
                       or self.scheduler.queue_depth > 0)):
                # nothing on device, but admission work is pending or
                # mid-prefill on the worker: block (bounded) for its
                # wave instead of spinning the idle guard down
                self._admission.wait(timeout=1.0)
        self._run_seconds += time.perf_counter() - t0

    @contextlib.contextmanager
    def _stage(self, name: str, **args):
        """One host stage: a profiler span ``name`` (with ``args``) on
        the calling thread, yielding its start on ``time.perf_counter``;
        a stage in PROFILE_KEYS also adds its wall-clock to
        ``metrics()["profile"]``.  Never per token."""
        with jax.profiler.TraceAnnotation(name, **args):
            t0 = time.perf_counter()
            try:
                yield t0
            finally:
                key = PROFILE_KEYS.get(name)
                if key in self._prof:
                    dt = time.perf_counter() - t0
                    with self._mlock:
                        self._prof[key] += dt

    def _drained(self, fn, window: int):
        """Wrap a backlog work item in its ``backlog.drain`` stage."""
        def run():
            with self._stage("backlog.drain", window=window):
                fn()
        return run

    def _note_refills(self, slots, t: float):
        """Slots filled at ``t``: each one an engine.finish emptied is a
        refill, kept as (t, seconds the slot stood empty)."""
        with self._mlock:
            for s in slots:
                freed = self._freed_at.pop(s, None)
                if freed is not None:
                    self._refills.append((t, t - freed))

    def flush(self):
        """Drain the pipeline: harvest every in-flight window and block
        until the backlog worker has processed all queued token work.
        No-op on a sync engine."""
        t0 = time.perf_counter()
        with self._stage("engine.flush"):
            while self._inflight:
                self._harvest_trailing()
            if self._backlog is not None and self._backlog.started:
                self._backlog.flush()
        self._run_seconds += time.perf_counter() - t0

    def close(self):
        """Flush and join the worker threads.  Idempotent; the engine
        remains usable for sync inspection (metrics, finished) after."""
        if self._admission is not None:
            self._admission.close()
        self.flush()
        if self._backlog is not None:
            self._backlog.close()

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, *exc):
        self.close()

    # -- one engine step (= one decode window) -------------------------------

    def step(self):
        """Admit + refill, then run one ``sync_every``-token fused decode
        window.  Sync mode harvests it immediately (the single host sync
        of the step); overlap mode harvests the *trailing* window and
        leaves this one in flight.

        Wall-clock accrues HERE (not in run()), so callers driving
        ``step()`` directly — benches, external event loops — still get a
        meaningful ``tokens_per_s`` out of :meth:`metrics`.  Idle no-op
        calls (nothing active, nothing admitted) accrue nothing: an
        event loop polling an empty engine must not dilute the rate."""
        with self._stage("engine.step", window=(self._dispatch_index
                                                if self.overlap
                                                else self.windows)):
            if self.overlap:
                self._step_async()
            else:
                self._step_sync()

    def _step_sync(self):
        t0 = time.perf_counter()
        self._readmit_preempted()
        self._admit()
        self._refill()
        self._lazy_topup()
        st = self._st
        if not st["act"].any():
            return
        # window-boundary snapshot: the load THIS window runs with —
        # folded into the means in _harvest, atomically with `windows`
        occ, qd = self.scheduler.occupancy, self.scheduler.queue_depth
        if self.aot:
            # AOT executables skip jit's implicit placement: the carry
            # must arrive committed to the lowered shardings
            state = {k: jax.device_put(v, self._carry_sh[k])
                     for k, v in st.items()}
        else:
            state = {k: jnp.asarray(v) for k, v in st.items()}
        acc = prop = None
        if self.draft_cache is not None:
            (self.cache, self.draft_cache, state, toks, emits, acc,
             prop, n_act) = self._window(self.params, self.draft_params,
                                         self.cache, self.draft_cache,
                                         state)
        elif self.spec_depth > 0:
            (self.cache, state, toks, emits, acc, prop,
             n_act) = self._window(self.params, self.cache, state)
        else:
            self.cache, state, toks, emits, n_act = self._window(
                self.params, self.cache, state)
        self._harvest(state, toks, emits, occ, qd, acc, prop, n_act)
        self._run_seconds += time.perf_counter() - t0

    def _harvest(self, state, toks, emits, occ: int, qd: int,
                 acc=None, prop=None, n_act=None):
        toks = np.asarray(toks)                 # (K, B) or (K, B, S)
        emits = np.asarray(emits)
        if toks.ndim == 2:                      # non-speculative window
            toks, emits = toks[:, :, None], emits[:, :, None]
        self._st = {k: np.array(v) for k, v in state.items()}
        # every window-scoped counter advances together, here and only
        # here — a mid-stream metrics() call never sees sums from one
        # window paired with counts from another
        self.host_syncs += 1
        self.windows += 1
        self.tokens_emitted += int(emits.sum())
        self._occupancy_sum += occ
        self._queue_depth_sum += qd
        if n_act is not None:
            n_act = np.asarray(n_act).reshape(toks.shape[0], -1)
            self._act_iters += int(n_act[:, 0].sum())
            self._count_moe(n_act[:, 1:].sum(axis=0))
        if acc is not None:
            self.draft_accepted += int(np.asarray(acc).sum())
            self.draft_proposed += int(np.asarray(prop).sum())
            if self.adaptive_spec:
                self._adaptive_update(
                    np.ones(self.B, bool), self._st["act"],
                    np.asarray(acc).sum(axis=0).reshape(-1),
                    np.asarray(prop).sum(axis=0).reshape(-1), set())
        slot_req = self.scheduler.slot_req
        for k in range(toks.shape[0]):
            for j in range(toks.shape[2]):
                for i in np.nonzero(emits[k, :, j])[0]:
                    self._record_token(slot_req[i], int(toks[k, i, j]))
        for slot, r in enumerate(slot_req):
            if r is not None and not self._st["act"][slot]:
                self._finish(slot)

    def _count_moe(self, sums):
        """Fold a harvested window's MoE routing sums (empty for a dense
        FFN) into ``moe_decode``."""
        for name, v in zip(L.MOE_COUNTERS, sums):
            self.moe_decode[name] += int(v)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Drive until drained or ``max_steps`` COMPLETED windows, then
        flush the pipeline + backlog (so ``finished`` streams are whole
        and ``metrics()`` is settled even on timeout).  The bound counts
        harvested windows — under overlap, dispatched-but-unharvested
        windows don't tick it, so the timeout means what it says.  On
        timeout the engine warns and leaves the backlog inspectable via
        ``engine.unfinished`` (callers distinguish drain from timeout).
        A stuck load (a request that can never admit) exits via the idle
        guard instead of spinning to max_steps.  A wave still being
        prepared on the admission worker is progress: a cold prefill
        compile outlasts the guard's bounded waits."""
        idle = 0
        while self.scheduler.has_work or self._inflight:
            if self.windows >= max_steps:
                break
            before = (self.windows, self.host_syncs, self._dispatch_index)
            self.step()
            made_progress = ((self.windows, self.host_syncs,
                              self._dispatch_index) != before
                             or (self._admission is not None
                                 and self._admission.busy))
            idle = 0 if made_progress else idle + 1
            if idle > self.B + 2:
                break
        self.flush()
        if self.scheduler.has_work:
            u = self.unfinished
            warnings.warn(
                f"Engine.run stopped after {self.windows} completed "
                f"windows (max_steps={max_steps}) with {u['queued']} "
                f"queued and {u['in_flight']} in-flight requests "
                f"unfinished (not a drain)", RuntimeWarning,
                stacklevel=2)
        return self.finished

    # -- metrics -------------------------------------------------------------

    def metrics(self) -> dict[str, Any]:
        """Serving counters since construction (host_syncs counts one per
        decode-window harvest plus one per admission wave).

        Safe to call mid-stream: window-scoped sums and ``windows``
        advance atomically at each harvest, and the instantaneous
        ``occupancy``/``queue_depth`` read the scheduler — the host-side
        truth at every window boundary — never the device mirror's
        active flags (which are stale between harvests).  Under overlap,
        ``tokens_per_s`` is true pipeline wall-clock: ``_run_seconds``
        accrues across boundary work AND the final flush, while the
        token counts settle on the backlog worker (flush/close first for
        exact totals).  ``ttft_s`` averages submit -> first-token wall
        latency; ``window_overlap`` is the fraction of windows that were
        dispatched before the prior one had finished on device — the
        direct measure of how often the double buffer actually hid the
        host; ``windows_idle`` counts harvested windows that emitted
        nothing (pipeline bubbles after a drain).  ``prefill_tokens`` /
        ``prefill_positions`` count the real prompt tokens and the
        bucketed positions of the prefills dispatched; ``slot_refills``
        lists the latest overlapped refills as (``time.perf_counter`` at
        the start of the ``engine.merge`` that filled the slot, seconds
        since the ``engine.finish`` that emptied it).  The
        ``moe_decode_*`` counters sum, over the decode windows harvested
        and every MoE layer, the live slots' (token, expert) pairs routed
        over all experts, the pairs whose expert is held here, and the
        held experts with at least one pair (prefill is not counted)."""
        with self._mlock:
            tokens = self.tokens_emitted + self._admit_tokens
            windows_idle = self.windows_idle
            ttft = self._ttft_sum / self._ttft_n if self._ttft_n else 0.0
            draft_proposed = self.draft_proposed
            draft_accepted = self.draft_accepted
            preemptions = self.preemptions
            prefill_calls = self.prefill_calls
            prefill_tokens = self.prefill_tokens
            prefill_positions = self.prefill_positions
            prefill_calls_saved = self.prefill_calls_saved
            prof = dict(self._prof)
            refills = [list(r) for r in self._refills]
        w = max(self.windows, 1)
        pool = self._pages
        ptotal = sum(prof.values())
        profile = {"seconds": prof,
                   "shares": {k: (v / ptotal if ptotal else 0.0)
                              for k, v in prof.items()}}
        return {
            "tokens": tokens,
            "windows": self.windows,
            "sync_every": self.sync_every,
            "cache_layout": self.cache_layout,
            "page_size": self.page_size or 0,
            "pages_total": 0 if pool is None else self.n_pages,
            # pages_free counts the allocatable free list only; pinned
            # pages at refcount 0 are PARKED (resident, not allocatable)
            # and reported separately so free + held + parked + null
            # partitions pages_total
            "pages_free": 0 if pool is None else pool.free_count,
            "pages_parked": 0 if pool is None else pool.parked,
            "pages_shared": 0 if pool is None else pool.share_events,
            "pages_peak": 0 if pool is None else pool.peak_used,
            "cow_forks": 0 if pool is None else pool.cow_forks,
            "mesh": self.mesh_str,
            "backend": self.cfg.attn_backend,
            "verify_backend": self._verify_backend,
            "decode_kernel_sharded": self._decode_kernel_sharded,
            "spec_depth": self.spec_depth,
            "draft": (None if self.draft is None else
                      (self.draft.kind if self.draft.kind == "ngram"
                       else f"layers:{self.draft.layers}")),
            "draft_proposed": draft_proposed,
            "draft_accepted": draft_accepted,
            "accept_rate": (draft_accepted / draft_proposed
                            if draft_proposed else 0.0),
            "host_syncs": self.host_syncs,
            "admission_syncs": self.admission_syncs,
            "host_syncs_per_token": self.host_syncs / max(tokens, 1),
            "decode_syncs_per_token":
                self.windows / max(tokens - self._admit_tokens, 1),
            "occupancy": self.scheduler.occupancy,
            "queue_depth": self.scheduler.queue_depth,
            "occupancy_mean": self._occupancy_sum / w,
            "queue_depth_mean": self._queue_depth_sum / w,
            "overlap": self.overlap,
            "aot": self.aot,
            "pipeline_depth": self.pipeline_depth if self.overlap else 0,
            "continuous": self.continuous,
            "admission_thread": self.admission_thread,
            "window_overlap": (self._overlapped_windows
                               / max(self._dispatch_index, 1)
                               if self.overlap else 0.0),
            "windows_idle": windows_idle,
            "slot_swaps": self.slot_swaps,
            "occupancy_device_mean":
                self._act_iters / (w * self.sync_every),
            "adaptive_spec": self.adaptive_spec,
            "spec_degraded": self.spec_degraded,
            "pin_prefixes": self.pin_prefixes,
            "pages_pinned": 0 if pool is None else pool.pinned,
            "policy": self.policy.name,
            "lazy_pages": self.lazy_pages,
            "staging_depth": self.staging_depth,
            "preemptions": preemptions,
            "prefill_calls": prefill_calls,
            "prefill_tokens": prefill_tokens,
            "prefill_positions": prefill_positions,
            "prefill_calls_saved": prefill_calls_saved,
            **self.moe_decode,
            "slot_refills": refills,
            "profile": profile,
            "ttft_s": ttft,
            "prefix_resurrections": (0 if pool is None
                                     else pool.prefix_resurrections),
            "run_seconds": self._run_seconds,
            "tokens_per_s": tokens / self._run_seconds
                            if self._run_seconds else 0.0,
        }
