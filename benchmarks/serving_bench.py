"""End-to-end serving benchmark: the engine's throughput trajectory.

Cache compression papers win or lose on serving throughput, not per-layer
reconstruction error — this bench drives the scheduler/sampler/executor
engine over a fixed synthetic request load for every cache variant
(dense / latent / int8-latent) x attention backend (einsum / pallas) and
records tokens/s plus host-syncs-per-decoded-token (the executor's fused
``sync_every``-token window must cost <= 1 host round-trip per window,
vs 1 per token for the seed engine's loop).

Each run APPENDS one trajectory row to ``BENCH_serving.json`` so the
numbers are comparable across PRs.  On CPU the pallas rows run the
kernels in interpret mode — a correctness trace whose ratio becomes a
speed claim only on TPU.

Overlap rows: the latent/einsum load is re-run on the double-buffered
overlapped pipeline with AOT warmup (``overlap=True, aot=True``), ring
and paged — ``speedup_vs_sync`` records the throughput ratio against the
matching blocking row in the same entry.

Continuous rows: a saturating mixed-length load (``continuous_mix``)
drives the depth-3 window pipeline with device-side mid-window slot
swaps — ``occupancy_device_mean`` (mean active slots per fused-scan
iteration), ``slot_swaps``, client-observed inter-token latency
(``itl_p50_ms``/``itl_p95_ms``) and the host-boundary stage shares
(``profile_shares``) are the recorded trajectory; ``--profile PATH``
additionally dumps the continuous row's stage seconds and shares as JSON
(the per-event timeline is a profiler trace of the engine's spans).

Mesh rows: the latent load is re-run over engine mesh shapes (``1x1``
and ``2x4``) for BOTH backends — the pallas rows exercise the shard_map
kernel path (per-shard partial softmax + LSE merge over the "model"
axis) — so the sharded window's CPU overhead (collectives + forced host
devices) is a recorded trajectory, not an anecdote.  A shape needing
more devices than this process has is measured in a forced-host
subprocess (``--one-mesh-row``), since the device count must be fixed
before jax initializes.  The structural 1-sync-per-window assertion runs
on every row, mesh rows included.

Every pallas row records ``speedup_vs_einsum`` (its tokens/s over the
matching einsum row's): < 1 on CPU where the kernel runs in interpret
mode, the number to watch on TPU.

Off the chip path: this bench is a CPU trajectory.  Its mesh rows re-exec
the script as a forced-host CPU child after the parent has touched JAX,
and on a TPU host a parent holds the chip, so such a row would measure
the CPU under a mesh row's name.  On the chip, run ``chip_smoke.py``
(one process) instead.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import mesh_from_spec
from repro.models import transformer as T
from repro.serving import Engine, Request

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_serving.json")

VARIANTS = {
    "dense": ({}, {}),
    "latent": ({"recalkv_ratio": 0.5}, {}),
    "int8_latent": ({"recalkv_ratio": 0.5}, {"cache_quant_bits": 8}),
}

MESH_SHAPES = ("1x1", "2x4")


def bench_engine(arch: str, variant: str, backend: str, *, slots: int,
                 max_len: int, requests: int, new_tokens: int,
                 sync_every: int, mesh_spec: str | None = None,
                 spec_depth: int = 0, draft: str | None = None,
                 cache_layout: str = "ring", page_size: int | None = None,
                 n_pages: int | None = None, prompts=None,
                 workload: str | None = None, overlap: bool = False,
                 aot: bool = False, pipeline_depth: int = 2,
                 continuous: bool = False,
                 admission_thread: bool | None = None,
                 policy: str | None = None, lazy_pages: bool = False,
                 new_tokens_list=None,
                 stamp_tokens: bool = False,
                 profile_out: dict | None = None) -> dict:
    kw, extra = VARIANTS[variant]
    cfg = dataclasses.replace(get_config(arch, smoke=True, **kw),
                              dtype=jnp.float32, attn_backend=backend,
                              **extra)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, max_slots=slots, max_len=max_len,
                 sync_every=sync_every, mesh=mesh_from_spec(mesh_spec),
                 spec_depth=spec_depth, draft=draft,
                 cache_layout=cache_layout, page_size=page_size,
                 n_pages=n_pages, overlap=overlap, aot=aot,
                 pipeline_depth=pipeline_depth, continuous=continuous,
                 admission_thread=admission_thread, policy=policy,
                 lazy_pages=lazy_pages)
    if prompts is None:
        g = np.random.default_rng(1)
        prompts = [g.integers(0, cfg.vocab_size,
                              int(g.integers(4, max_len // 3))
                              ).astype(np.int32)
                   for _ in range(requests)]
    # inter-token latency as the CLIENT sees it: perf_counter stamps on
    # every on_token callback (backlog-thread domain under overlap), gaps
    # taken within each request's stream
    stamps: dict[int, list[float]] = {}
    for i, p in enumerate(prompts):
        nt = new_tokens if new_tokens_list is None else new_tokens_list[i]
        cb = None
        if stamp_tokens:
            cb = (lambda r, t, _u=i:
                  stamps.setdefault(_u, []).append(time.perf_counter()))
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=nt,
                           on_token=cb))
    finished = eng.run()
    eng.close()                      # settle backlog counters (no-op sync)
    m = eng.metrics()
    cache_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(eng.cache))
    assert len(finished) == len(prompts), "bench load did not drain"
    # the executor's structural contract: exactly one host sync per
    # sync_every-step decode window (plus one per admission wave) — syncs
    # no longer scale with decoded tokens as in the seed engine (and a
    # speculative window still costs ONE sync however many tokens it
    # verifies)
    assert m["host_syncs"] == m["windows"] + m["admission_syncs"], m
    assert m["host_syncs"] < m["tokens"], m
    row = {
        "variant": variant,
        "backend": backend,
        "mesh": m["mesh"],
        # per-row platform: the forced-host 2x4 row runs in a CPU
        # subprocess even when the parent entry's platform is tpu/gpu
        "platform": jax.default_backend(),
        "tokens": m["tokens"],
        "tokens_per_s": round(m["tokens_per_s"], 2),
        "host_syncs_per_token": round(m["host_syncs_per_token"], 4),
        "decode_syncs_per_token": round(m["decode_syncs_per_token"], 4),
        "occupancy_mean": round(m["occupancy_mean"], 2),
        "cache_bytes": cache_bytes,
    }
    if overlap:
        # overlap identity + pipeline health.  The sync_every bound is
        # asserted on BUSY windows: the overlapped drain dispatches a few
        # windows against a stale host view that harvest as empty bubbles
        # (windows_idle) — those cost a sync but emit nothing, so the raw
        # decode_syncs_per_token can exceed 1/sync_every without any
        # structural regression.
        decode_tokens = round(m["windows"]
                              / max(m["decode_syncs_per_token"], 1e-12))
        busy = (m["windows"] - m["windows_idle"]) / max(decode_tokens, 1)
        assert busy <= 1.0 / sync_every + 1e-9, m
        row["overlap"] = True
        row["aot"] = aot
        row["pipeline_depth"] = m["pipeline_depth"]
        row["window_overlap"] = round(m["window_overlap"], 4)
        row["windows_idle"] = m["windows_idle"]
        row["busy_decode_syncs_per_token"] = round(busy, 4)
        row["ttft_s"] = round(m["ttft_s"], 4)
        row["occupancy_device_mean"] = round(m["occupancy_device_mean"], 2)
        # host-boundary stage shares (always-on counters): where the
        # boundary wall-clock actually goes — dispatch / harvest /
        # admission_stage / backlog_drain / bookkeep (+ the admission
        # worker's off-thread prefill time)
        row["profile_shares"] = {k: round(v, 3)
                                 for k, v in m["profile"]["shares"].items()}
    if continuous:
        row["continuous"] = True
        row["slot_swaps"] = m["slot_swaps"]
    if stamp_tokens:
        gaps = [b - a for s in stamps.values()
                for a, b in zip(s, s[1:])]
        if gaps:
            row["itl_p50_ms"] = round(
                float(np.percentile(gaps, 50)) * 1e3, 2)
            row["itl_p95_ms"] = round(
                float(np.percentile(gaps, 95)) * 1e3, 2)
    if spec_depth:
        row["spec_depth"] = spec_depth
        row["draft"] = m["draft"]
        row["accept_rate"] = round(m["accept_rate"], 4)
    if cache_layout == "paged":
        # effective footprint = pages actually touched at peak, not the
        # pool reservation — the number prefix sharing shrinks
        per_page = cache_bytes / eng.n_pages
        row["cache_layout"] = "paged"
        row["page_size"] = eng.page_size
        row["pool_bytes"] = cache_bytes
        row["cache_bytes"] = int(round(per_page * m["pages_peak"]))
        row["pages_peak"] = m["pages_peak"]
        row["pages_shared"] = m["pages_shared"]
        row["cow_forks"] = m["cow_forks"]
        row["prefill_calls"] = m["prefill_calls"]
        row["prefill_calls_saved"] = m["prefill_calls_saved"]
    if policy is not None:
        row["policy"] = m["policy"]
    if lazy_pages:
        row["lazy_pages"] = True
        row["preemptions"] = m["preemptions"]
    if workload:
        row["workload"] = workload
    if profile_out is not None:
        # the aggregate stage seconds and shares, dumped by --profile
        profile_out["profile"] = m["profile"]
    return row


def bench_device_loop(arch: str, variant: str, *, slots: int, max_len: int,
                      new_tokens: int) -> dict:
    """Raw ``T.decode_loop`` throughput — the executor's upper bound: one
    fused scan, no scheduler, no sampler state, one harvest at the end."""
    kw, extra = VARIANTS[variant]
    cfg = dataclasses.replace(get_config(arch, smoke=True, **kw),
                              dtype=jnp.float32, **extra)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    g = np.random.default_rng(1)
    toks = jnp.asarray(g.integers(0, cfg.vocab_size, (slots, 8)), jnp.int32)
    lens = jnp.full((slots,), 8, jnp.int32)
    logits, caches = T.prefill(cfg, params, toks, lens, max_len)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    cur = lens.astype(jnp.int32)
    loop = jax.jit(lambda c, t, u: T.decode_loop(
        cfg, params, c, t, u, new_tokens))
    loop(caches, tok, cur)[3].block_until_ready()      # compile
    # best-of-3: the row is an UPPER bound and scheduler contention is
    # one-sided noise, so min-of-N is the right estimator (a single
    # ~10ms timed call swings >40% run-to-run on a busy host and flakes
    # the 20% perf gate)
    dt = float("inf")
    for _ in range(3):
        t0 = time.time()
        out = loop(caches, tok, cur)[3]
        out.block_until_ready()
        dt = min(dt, time.time() - t0)
    return {
        "variant": variant,
        "backend": "device_loop",
        "tokens": slots * new_tokens,
        "tokens_per_s": round(slots * new_tokens / dt, 2),
        "host_syncs_per_token": round(1.0 / (slots * new_tokens), 4),
    }


def _subprocess_mesh_row(arch: str, shape: str, *, backend: str = "einsum",
                         slots: int, max_len: int, requests: int,
                         new_tokens: int, sync_every: int) -> dict:
    """Measure a mesh shape needing more devices than this process has:
    re-exec this script with forced host devices (XLA device count is
    fixed at jax init, so it cannot change in-process)."""
    need = math.prod(int(v) for v in shape.split("x"))
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    xla_flags = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"),
        f"--xla_force_host_platform_device_count={need}"]))
    env = {**os.environ,
           "XLA_FLAGS": xla_flags,
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": src + os.pathsep + os.environ.get("PYTHONPATH", "")}
    cmd = [sys.executable, os.path.abspath(__file__),
           "--one-mesh-row", shape, "--arch", arch, "--backend", backend,
           "--slots", str(slots), "--max-len", str(max_len),
           "--requests", str(requests), "--new-tokens", str(new_tokens),
           "--sync-every", str(sync_every)]
    res = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=1800)
    if res.returncode != 0:
        raise RuntimeError(f"mesh row {shape} subprocess failed:\n"
                           f"{res.stderr[-3000:]}")
    line = [l for l in res.stdout.splitlines() if l.startswith("MESHROW ")][0]
    return json.loads(line[len("MESHROW "):])


def bench_mesh_rows(arch: str, *, slots: int, max_len: int, requests: int,
                    new_tokens: int, sync_every: int,
                    have_rows: list[dict] | None = None) -> list[dict]:
    """Latent load over engine mesh shapes x backends (in-process when
    the devices exist, forced-host subprocess otherwise).  The pallas
    rows run the shard_map kernel path (ring slices sharded over the
    "model" axis, LSE-merged partial softmax) and record
    ``speedup_vs_einsum`` against their einsum twin.  Rows already
    covered by ``have_rows`` are skipped — the variant matrix's own
    latent rows ARE the 1x1 measurement (the engine's default mesh is
    (1, 1)), so they are not re-run."""
    rows = []
    kw = dict(slots=slots, max_len=max_len, requests=requests,
              new_tokens=new_tokens, sync_every=sync_every)

    def have(shape, backend):
        for r in (have_rows or []) + rows:
            if (r.get("mesh") == shape and r["variant"] == "latent"
                    and r["backend"] == backend and not r.get("spec_depth")
                    and r.get("cache_layout", "ring") == "ring"
                    and not r.get("overlap") and not r.get("workload")):
                return r
        return None

    for shape in MESH_SHAPES:
        for backend in ("einsum", "pallas"):
            if have(shape, backend) is not None:
                continue
            need = math.prod(int(v) for v in shape.split("x"))
            t0 = time.time()
            if need <= jax.local_device_count():
                row = bench_engine(arch, "latent", backend, mesh_spec=shape,
                                   **kw)
            else:
                row = _subprocess_mesh_row(arch, shape, backend=backend,
                                           **kw)
            row["bench_seconds"] = round(time.time() - t0, 1)
            if backend == "pallas":
                base = have(shape, "einsum")
                if base is not None and base["tokens_per_s"] > 0:
                    row["speedup_vs_einsum"] = round(
                        row["tokens_per_s"] / base["tokens_per_s"], 2)
            rows.append(row)
            print(f"serving/latent/{backend}/mesh={shape}: "
                  f"{row['tokens_per_s']:.1f} tok/s, "
                  f"{row['host_syncs_per_token']:.3f} syncs/tok")
    return rows


def bench_paged_rows(arch: str, *, slots: int, max_len: int, requests: int,
                     new_tokens: int, sync_every: int) -> list[dict]:
    """Paged-layout rows: the standard load over the pooled cache (einsum
    and pallas), then a shared- vs unshared-system-prompt pair whose
    effective cache_bytes demonstrates prefix sharing — the shared row's
    peak footprint must be strictly below the unshared run's."""
    rows = []
    common = dict(slots=slots, max_len=max_len, requests=requests,
                  new_tokens=new_tokens, sync_every=sync_every)
    base = None
    for backend in ("einsum", "pallas"):
        t0 = time.time()
        row = bench_engine(arch, "latent", backend, cache_layout="paged",
                           **common)
        row["bench_seconds"] = round(time.time() - t0, 1)
        if backend == "einsum":
            base = row
        elif base["tokens_per_s"] > 0:
            row["speedup_vs_einsum"] = round(
                row["tokens_per_s"] / base["tokens_per_s"], 2)
        rows.append(row)
        print(f"serving/latent/{backend}/paged: "
              f"{row['tokens_per_s']:.1f} tok/s, "
              f"cache {row['cache_bytes']/2**20:.2f} MiB effective "
              f"(peak {row['pages_peak']} pages)")
    # shared-prefix pair: same lengths, one load shares a 3-page system
    # prompt across all requests, the other uses disjoint prompts
    ps = next(p for p in (8, 4, 2, 1) if max_len % p == 0)
    vocab = get_config(arch, smoke=True).vocab_size
    g = np.random.default_rng(2)
    sysp = g.integers(0, vocab, 3 * ps).astype(np.int32)
    shared = [np.concatenate([sysp,
                              g.integers(0, vocab, 4).astype(np.int32)])
              for _ in range(requests)]
    unshared = [g.integers(0, vocab, 3 * ps + 4).astype(np.int32)
                for _ in range(requests)]
    pair = {}
    for name, load in (("shared_prefix", shared),
                       ("unshared_prefix", unshared)):
        t0 = time.time()
        row = bench_engine(arch, "latent", "einsum", cache_layout="paged",
                           page_size=ps, prompts=load, workload=name,
                           **common)
        row["bench_seconds"] = round(time.time() - t0, 1)
        pair[name] = row
        rows.append(row)
        print(f"serving/latent/einsum/paged/{name}: "
              f"cache {row['cache_bytes']/2**20:.2f} MiB effective, "
              f"{row['pages_shared']} shares, {row['cow_forks']} forks")
    assert (pair["shared_prefix"]["cache_bytes"]
            < pair["unshared_prefix"]["cache_bytes"]), pair
    assert pair["shared_prefix"]["pages_shared"] > 0, pair
    rows.append(bench_mixed_length(arch, max_len=max_len,
                                   sync_every=sync_every))
    return rows


def bench_mixed_length(arch: str, *, max_len: int,
                       sync_every: int) -> dict:
    """Mixed-length admission: under the SAME pool budget a 4-slot ring
    engine reserves (4 full-length rings), the paged engine's
    reach-based page accounting admits more concurrent requests when the
    load mixes one near-cap prompt with many short ones."""
    kw, extra = VARIANTS["latent"]
    cfg = dataclasses.replace(get_config(arch, smoke=True, **kw),
                              dtype=jnp.float32, **extra)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ps = next(p for p in (8, 4, 2, 1) if max_len % p == 0)
    n_sp = max_len // ps
    budget = 4 * n_sp + 1                      # 4-slot ring equivalent
    new_tokens = 2 * sync_every
    g = np.random.default_rng(3)
    prompts = ([g.integers(0, cfg.vocab_size,
                           max_len - new_tokens - 1).astype(np.int32)]
               + [g.integers(0, cfg.vocab_size, 4).astype(np.int32)
                  for _ in range(7)])

    def drive(eng):
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=p.copy(),
                               max_new_tokens=new_tokens))
        eng.step()                 # first admission wave + one window
        conc = eng.scheduler.occupancy
        eng.run()
        return conc, eng.metrics()

    t0 = time.time()
    ring_conc, _ = drive(Engine(cfg, params, max_slots=4, max_len=max_len,
                                sync_every=sync_every))
    paged_conc, m = drive(Engine(cfg, params, max_slots=8, max_len=max_len,
                                 sync_every=sync_every,
                                 cache_layout="paged", page_size=ps,
                                 n_pages=budget))
    assert paged_conc > ring_conc, (paged_conc, ring_conc)
    print(f"serving/latent/einsum/paged/mixed_length: {paged_conc} "
          f"concurrent slots vs {ring_conc} ring under {budget - 1} pages")
    return {
        "variant": "latent", "backend": "einsum", "mesh": m["mesh"],
        "platform": jax.default_backend(),
        "workload": "mixed_length",
        "cache_layout": "paged", "page_size": ps,
        "pool_pages": budget,
        "concurrent_slots": paged_conc,
        "ring_concurrent_slots": ring_conc,
        "tokens": m["tokens"],
        "tokens_per_s": round(m["tokens_per_s"], 2),
        "host_syncs_per_token": round(m["host_syncs_per_token"], 4),
        "decode_syncs_per_token": round(m["decode_syncs_per_token"], 4),
        "occupancy_mean": round(m["occupancy_mean"], 2),
        "pages_peak": m["pages_peak"],
        "bench_seconds": round(time.time() - t0, 1),
    }


def bench_overlap_rows(arch: str, *, slots: int, max_len: int,
                       requests: int, new_tokens: int, sync_every: int,
                       have_rows: list[dict]) -> list[dict]:
    """Overlapped-pipeline rows: the double-buffered engine, AOT-warmed,
    over the standard load (ring and paged).  AOT moves trace time out of
    the serving window and the double buffer overlaps host boundary work
    with device compute, so ``tokens_per_s`` here measures steady-state
    serving throughput; ``speedup_vs_sync`` records the ratio against the
    matching sync row from this same entry — the number the pipeline
    refactor exists to move.  Streams stay token-for-token identical to
    the sync rows (asserted in tests/test_async_serving.py)."""
    rows = []
    common = dict(slots=slots, max_len=max_len, requests=requests,
                  new_tokens=new_tokens, sync_every=sync_every)
    for cache_layout in ("ring", "paged"):
        t0 = time.time()
        row = bench_engine(arch, "latent", "einsum", overlap=True, aot=True,
                           cache_layout=cache_layout, **common)
        row["bench_seconds"] = round(time.time() - t0, 1)
        base = next((r for r in have_rows
                     if r["variant"] == "latent" and r["backend"] == "einsum"
                     and not r.get("overlap") and not r.get("spec_depth")
                     and r.get("cache_layout", "ring") == cache_layout
                     and not r.get("workload")), None)
        if base is not None and base["tokens_per_s"] > 0:
            row["speedup_vs_sync"] = round(
                row["tokens_per_s"] / base["tokens_per_s"], 2)
        rows.append(row)
        print(f"serving/latent/einsum/{cache_layout}/overlap+aot: "
              f"{row['tokens_per_s']:.1f} tok/s "
              f"({row.get('speedup_vs_sync', '?')}x sync), "
              f"overlap {row['window_overlap']:.2f}, "
              f"ttft {row['ttft_s'] * 1e3:.0f}ms")
    # the pipeline must WIN somewhere: at least one overlapped row
    # beats its sync baseline (the measured margin — 7-8x on this load,
    # AOT keeping trace time out of the serving window — lives in the
    # trajectory; asserting the full margin would gate CI on shared-
    # runner noise)
    assert any(r.get("speedup_vs_sync", 0) > 1.0 for r in rows), rows
    return rows


def bench_continuous_rows(arch: str, *, slots: int, max_len: int,
                          new_tokens: int, sync_every: int,
                          profile_out: dict | None = None) -> list[dict]:
    """Continuous-batching rows: a saturating mixed-length load (4x the
    slot count, short and long prompts, staggered ``max_new_tokens``) so
    slots free mid-window constantly — the load the device-side slot
    swap exists for.  Three rows on the IDENTICAL load: the blocking
    engine (baseline for ``speedup_vs_sync``), the depth-3 pipeline
    without continuous batching (its ``occupancy_device_mean`` shows
    slots idling until the next boundary), and depth-3 + continuous
    (staged requests install INSIDE the fused scan).  The continuous
    row must swap in-scan and lift device occupancy over the boundary-
    only pipeline — that ordering is structural (a freed slot stays
    empty for the rest of the window without the swap), not timing, so
    it is asserted.  ``itl_p50_ms``/``itl_p95_ms`` record client-
    observed inter-token latency from on_token stamps."""
    g = np.random.default_rng(7)
    vocab = get_config(arch, smoke=True).vocab_size
    n = 6 * slots
    prompts, new_list = [], []
    for i in range(n):
        # mostly short prompts (admission keeps pace with decode) with a
        # long one per wave-ish group, and decode lengths long and
        # staggered enough that slots free MID-window while staged
        # successors are already waiting to be swapped in
        plen = max_len // 3 if i % 8 == 0 else int(g.integers(4, 8))
        nt = (new_tokens + sync_every, new_tokens + 2 * sync_every,
              max_len - sync_every - 1)[i % 3]
        nt = min(nt, max_len - plen - 1)
        prompts.append(g.integers(0, vocab, plen).astype(np.int32))
        new_list.append(nt)
    common = dict(slots=slots, max_len=max_len, requests=n,
                  new_tokens=new_tokens, sync_every=sync_every,
                  prompts=prompts, new_tokens_list=new_list,
                  workload="continuous_mix")
    rows = []
    t0 = time.time()
    sync_row = bench_engine(arch, "latent", "einsum", stamp_tokens=True,
                            **common)
    sync_row["bench_seconds"] = round(time.time() - t0, 1)
    rows.append(sync_row)
    print(f"serving/latent/einsum/continuous_mix/sync: "
          f"{sync_row['tokens_per_s']:.1f} tok/s, "
          f"itl p50 {sync_row.get('itl_p50_ms', 0):.1f}ms")
    t0 = time.time()
    over_row = bench_engine(arch, "latent", "einsum", overlap=True,
                            aot=True, pipeline_depth=3, **common)
    over_row["bench_seconds"] = round(time.time() - t0, 1)
    rows.append(over_row)
    print(f"serving/latent/einsum/continuous_mix/overlap-d3: "
          f"{over_row['tokens_per_s']:.1f} tok/s, "
          f"device occupancy {over_row['occupancy_device_mean']:.2f}")
    t0 = time.time()
    cont_row = bench_engine(arch, "latent", "einsum", overlap=True,
                            aot=True, pipeline_depth=3, continuous=True,
                            stamp_tokens=True,
                            profile_out=profile_out, **common)
    cont_row["bench_seconds"] = round(time.time() - t0, 1)
    if sync_row["tokens_per_s"] > 0:
        cont_row["speedup_vs_sync"] = round(
            cont_row["tokens_per_s"] / sync_row["tokens_per_s"], 2)
    rows.append(cont_row)
    print(f"serving/latent/einsum/continuous_mix/continuous-d3: "
          f"{cont_row['tokens_per_s']:.1f} tok/s "
          f"({cont_row.get('speedup_vs_sync', '?')}x sync), "
          f"{cont_row['slot_swaps']} in-scan swaps, "
          f"device occupancy {cont_row['occupancy_device_mean']:.2f} "
          f"vs {over_row['occupancy_device_mean']:.2f} boundary-only, "
          f"itl p95 {cont_row.get('itl_p95_ms', 0):.1f}ms")
    assert cont_row["slot_swaps"] > 0, cont_row
    assert (cont_row["occupancy_device_mean"]
            > over_row["occupancy_device_mean"]), (cont_row, over_row)
    assert cont_row.get("speedup_vs_sync", 0) > 1.0, (cont_row, sync_row)
    assert cont_row["tokens_per_s"] > over_row["tokens_per_s"], (cont_row,
                                                                over_row)
    return rows


def bench_policy_rows(arch: str, *, slots: int, max_len: int,
                      sync_every: int) -> list[dict]:
    """Admission-policy rows: the ``prefix_storm`` workload — 24 requests
    sharing 3 long system prompts (8 sharers each, interleaved arrival)
    with short unique tails — on the identical load under ``fifo`` and
    ``prefix-affinity``.  FIFO admits arrival-order waves, so every wave
    mixes prompt groups and every request rides a prefill row;
    prefix-affinity groups sharers into waves and, once a group's prompt
    pages are resident, later sharers admit with ZERO prefill (the tail
    streams through the decode loop's ingest buffer).  The policy must
    win on this load — strictly fewer admission prefill calls AND >=
    1.3x tokens/s — and the margin is asserted, not just recorded:
    prefill compute dominates the workload by construction (system
    prompt ~10x the decode budget), so the ordering is structural."""
    vocab = get_config(arch, smoke=True).vocab_size
    ps = next(p for p in (8, 4, 2, 1) if max_len % p == 0)
    sys_pages = max(1, (max_len - ps) // ps - 1)
    g = np.random.default_rng(11)
    sysps = [g.integers(0, vocab, sys_pages * ps).astype(np.int32)
             for _ in range(3)]
    prompts = []
    for i in range(24):
        # 1-token tails: the un-chunked ingest buffer is one column
        # wide, so a skip-admitted tail feeds in a single boundary
        tail = g.integers(0, vocab, 1).astype(np.int32)
        prompts.append(np.concatenate([sysps[i % 3], tail]))
    common = dict(slots=slots, max_len=max_len, requests=len(prompts),
                  new_tokens=4, sync_every=sync_every, prompts=prompts,
                  cache_layout="paged", page_size=ps,
                  workload="prefix_storm")
    rows, by = [], {}
    for policy in ("fifo", "prefix-affinity"):
        t0 = time.time()
        row = bench_engine(arch, "latent", "einsum", policy=policy,
                           **common)
        row["bench_seconds"] = round(time.time() - t0, 1)
        by[policy] = row
        rows.append(row)
        print(f"serving/latent/einsum/paged/prefix_storm/{policy}: "
              f"{row['tokens_per_s']:.1f} tok/s, "
              f"{row['prefill_calls']} prefill calls "
              f"({row['prefill_calls_saved']} saved)")
    fifo, aff = by["fifo"], by["prefix-affinity"]
    if fifo["tokens_per_s"] > 0:
        aff["speedup_vs_fifo"] = round(
            aff["tokens_per_s"] / fifo["tokens_per_s"], 2)
    assert aff["prefill_calls"] < fifo["prefill_calls"], (aff, fifo)
    assert aff["prefill_calls_saved"] > 0, aff
    assert aff.get("speedup_vs_fifo", 0) >= 1.3, (aff, fifo)
    return rows


SPEC_CONFIGS = ((2, "ngram"), (2, "layers:2"))


def run(arch: str = "qwen3-4b", *, slots: int = 4, max_len: int = 48,
        requests: int = 6, new_tokens: int = 16,
        sync_every: int = 8, mesh_rows: bool = True,
        profile_out: dict | None = None) -> dict:
    rows = []
    for variant in VARIANTS:
        base = None
        for backend in ("einsum", "pallas"):
            t0 = time.time()
            row = bench_engine(arch, variant, backend, slots=slots,
                               max_len=max_len, requests=requests,
                               new_tokens=new_tokens, sync_every=sync_every)
            row["bench_seconds"] = round(time.time() - t0, 1)
            if backend == "einsum":
                base = row
            elif base["tokens_per_s"] > 0:
                row["speedup_vs_einsum"] = round(
                    row["tokens_per_s"] / base["tokens_per_s"], 2)
            rows.append(row)
            print(f"serving/{variant}/{backend}: "
                  f"{row['tokens_per_s']:.1f} tok/s, "
                  f"{row['host_syncs_per_token']:.3f} syncs/tok, "
                  f"cache {row['cache_bytes']/2**20:.2f} MiB")
    # speculative rows: the latent cache's halved footprint buys slots;
    # speculation spends them on step count — accept rate is the recorded
    # trajectory.  Both backends run: the pallas rows drive the
    # multi-query verify kernel (streams are token-identical to einsum,
    # asserted in tests/test_verify_kernel.py); tokens/s on CPU interpret
    # mode is a correctness trace whose ratio becomes a speed claim on
    # real accelerators.
    for spec_depth, draft in SPEC_CONFIGS:
        base = None
        for backend in ("einsum", "pallas"):
            t0 = time.time()
            row = bench_engine(arch, "latent", backend, slots=slots,
                               max_len=max_len, requests=requests,
                               new_tokens=new_tokens, sync_every=sync_every,
                               spec_depth=spec_depth, draft=draft)
            row["bench_seconds"] = round(time.time() - t0, 1)
            if backend == "einsum":
                base = row
            elif base["tokens_per_s"] > 0:
                row["speedup_vs_einsum"] = round(
                    row["tokens_per_s"] / base["tokens_per_s"], 2)
            rows.append(row)
            print(f"serving/latent/{backend}/spec={spec_depth}/{draft}: "
                  f"{row['tokens_per_s']:.1f} tok/s, "
                  f"accept rate {row['accept_rate']:.2f}")
    rows += bench_paged_rows(arch, slots=slots, max_len=max_len,
                             requests=requests, new_tokens=new_tokens,
                             sync_every=sync_every)
    rows += bench_overlap_rows(arch, slots=slots, max_len=max_len,
                               requests=requests, new_tokens=new_tokens,
                               sync_every=sync_every, have_rows=rows)
    rows += bench_continuous_rows(arch, slots=slots, max_len=max_len,
                                  new_tokens=new_tokens,
                                  sync_every=sync_every,
                                  profile_out=profile_out)
    rows += bench_policy_rows(arch, slots=slots, max_len=max_len,
                              sync_every=sync_every)
    if mesh_rows:
        rows += bench_mesh_rows(arch, slots=slots, max_len=max_len,
                                requests=requests, new_tokens=new_tokens,
                                sync_every=sync_every, have_rows=rows)
    # saturating multi-slot load -> the acceptance bound is demonstrated:
    # <= 1 host sync per sync_every decoded tokens (mesh rows included;
    # overlap rows bound their BUSY windows — drain bubbles cost a sync
    # but emit nothing, see bench_engine)
    if requests >= slots >= 2 and new_tokens >= 2 * sync_every:
        for row in rows:
            bound = row.get("busy_decode_syncs_per_token",
                            row["decode_syncs_per_token"])
            assert bound <= 1.0 / sync_every + 1e-9, row
    row = bench_device_loop(arch, "latent", slots=slots, max_len=max_len,
                            new_tokens=new_tokens)
    rows.append(row)
    print(f"serving/latent/device_loop: {row['tokens_per_s']:.1f} tok/s "
          f"(raw fused-scan upper bound)")
    return {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "arch": arch,
        "platform": jax.default_backend(),
        "config": {"slots": slots, "max_len": max_len, "requests": requests,
                   "new_tokens": new_tokens, "sync_every": sync_every},
        "rows": rows,
    }


def append_trajectory(entry: dict, out_path: str):
    """Append this run's entry to the BENCH_serving.json trajectory."""
    traj = []
    if os.path.exists(out_path):
        with open(out_path) as f:
            traj = json.load(f)
    traj.append(entry)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(traj, f, indent=1)
    os.replace(tmp, out_path)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--backend", default="einsum",
                    choices=("einsum", "pallas"),
                    help="attention backend for --one-mesh-row")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=48)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--sync-every", type=int, default=8)
    ap.add_argument("--mesh-rows", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="append mesh-shape rows (1x1, 2x4 forced-host)")
    ap.add_argument("--one-mesh-row", default=None, metavar="SHAPE",
                    help="internal: print one mesh row as MESHROW json "
                         "(run in a forced-host subprocess) and exit")
    ap.add_argument("--out", default=DEFAULT_OUT)
    ap.add_argument("--profile", default=None, metavar="PATH",
                    help="dump the continuous row's host-boundary stage "
                         "seconds and shares as JSON to PATH")
    args = ap.parse_args(argv)
    if args.one_mesh_row:
        row = bench_engine(args.arch, "latent", args.backend,
                           slots=args.slots, max_len=args.max_len,
                           requests=args.requests,
                           new_tokens=args.new_tokens,
                           sync_every=args.sync_every,
                           mesh_spec=args.one_mesh_row)
        print("MESHROW " + json.dumps(row))
        return
    profile_out = {} if args.profile else None
    entry = run(args.arch, slots=args.slots, max_len=args.max_len,
                requests=args.requests, new_tokens=args.new_tokens,
                sync_every=args.sync_every, mesh_rows=args.mesh_rows,
                profile_out=profile_out)
    append_trajectory(entry, args.out)
    print(f"trajectory row appended to {os.path.abspath(args.out)}")
    if args.profile:
        with open(args.profile, "w") as f:
            json.dump(profile_out, f, indent=1)
        print(f"host-boundary stage profile written to "
              f"{os.path.abspath(args.profile)}")


if __name__ == "__main__":
    main()
