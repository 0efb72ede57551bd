"""Overlapped-pipeline parity and lifecycle.

The overlapped engine (``overlap=True``) keeps ``pipeline_depth``
decode windows in flight, admits concurrently with in-flight decode
(staging prefill on a worker thread), hands token harvesting to a
backlog worker thread, and — with ``continuous=True`` — installs staged
requests into freed slots INSIDE the fused scan — none of which may
change a single emitted token.  Every test here pins the async engine's streams
TOKEN-FOR-TOKEN to the blocking engine's across cache variants,
backends, layouts, speculation, and (in the `mesh` CI job) a forced
(2, 4) host mesh, and checks the structural contracts the pipeline adds:
one device sync per *trailing* window, flat trace counts under AOT, and
a backlog thread that drains and joins on ``close``.
"""

import dataclasses
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import transformer as T
from repro.serving import Engine, Request, SamplingParams

KEY = jax.random.PRNGKey(0)

CASES = {
    "dense": {},
    "latent": {"recalkv_ratio": 0.5},
    "int8_latent": {"recalkv_ratio": 0.5, "cache_quant_bits": 8},
}

SAMPLED = SamplingParams(temperature=0.9, top_k=32, top_p=0.9, seed=11)

_MODELS = {}


def _model(case):
    """Config + params, cached per case — every test reuses one model."""
    if case not in _MODELS:
        extra = CASES[case]
        kw = {k: extra[k] for k in ("recalkv_ratio",) if k in extra}
        cfg = get_config("qwen3-4b", smoke=True, **kw)
        cfg = dataclasses.replace(
            cfg, dtype=jnp.float32,
            **{k: v for k, v in extra.items() if k == "cache_quant_bits"})
        _MODELS[case] = (cfg, T.init_params(cfg, KEY))
    return _MODELS[case]


def _prompts(cfg, n=6, seed=3):
    g = np.random.default_rng(seed)
    return [g.integers(0, cfg.vocab_size, 5 + 2 * i).astype(np.int32)
            for i in range(n)]


def _serve(cfg, params, prompts, *, sampling=None, max_new=6, **kw):
    eng = Engine(cfg, params, max_slots=4, max_len=40, sampling=sampling,
                 **kw)
    for i, pr in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=pr.copy(), max_new_tokens=max_new))
    done = eng.run()
    eng.close()
    return {r.uid: r.out_tokens for r in done}, eng


class TestAsyncStreamParity:
    """overlap=True must be stream-invariant: same tokens, same order,
    per request, as the blocking engine — greedy and sampled."""

    @pytest.mark.parametrize("case,backend", [
        ("dense", "einsum"), ("latent", "einsum"),
        ("int8_latent", "einsum"), ("latent", "pallas"),
    ])
    def test_greedy_streams_match_sync(self, case, backend):
        cfg, params = _model(case)
        cfg = dataclasses.replace(cfg, attn_backend=backend)
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts)
        got, eng = _serve(cfg, params, prompts, overlap=True)
        assert eng.overlap
        assert got == ref, (case, backend)

    def test_sampled_mixed_load_matches_sync(self):
        """Mixed greedy/sampled requests through chunked prefill: the
        shared jitted admission sampler + per-slot key chains make the
        async first tokens (and everything after) bitwise equal."""
        cfg, params = _model("latent")
        g = np.random.default_rng(21)
        reqs = [(g.integers(0, cfg.vocab_size,
                            int(g.integers(3, 30))).astype(np.int32),
                 SAMPLED if i % 2 else None) for i in range(6)]

        def serve(overlap):
            eng = Engine(cfg, params, max_slots=4, max_len=40,
                         prefill_chunk=6, sync_every=4, overlap=overlap)
            for i, (pr, sp) in enumerate(reqs):
                eng.submit(Request(uid=i, prompt=pr.copy(),
                                   max_new_tokens=6, sampling=sp))
            done = eng.run()
            eng.close()
            return {r.uid: r.out_tokens for r in done}

        assert serve(True) == serve(False)

    @pytest.mark.parametrize("spec_depth", [0, 2])
    def test_paged_streams_match_sync_ring(self, spec_depth):
        """Paged + overlap (+ speculation) still equals the sync ring
        engine — layout, pipeline, and speculation are all invisible in
        the streams."""
        cfg, params = _model("latent")
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts)
        got, eng = _serve(cfg, params, prompts, overlap=True,
                          cache_layout="paged", spec_depth=spec_depth,
                          draft="ngram" if spec_depth else None)
        assert got == ref, spec_depth
        if spec_depth:
            assert eng.metrics()["draft_proposed"] > 0

    def test_layer_draft_spec_matches_sync(self):
        """The self-draft (layers:K) speculative window under overlap:
        accept/residual bookkeeping rides the same packed-status harvest
        and must stay deterministic."""
        cfg, params = _model("latent")
        prompts = _prompts(cfg)
        for sp in (None, SAMPLED):
            ref, _ = _serve(cfg, params, prompts, sampling=sp)
            got, eng = _serve(cfg, params, prompts, sampling=sp,
                              overlap=True, spec_depth=2, draft="layers:2")
            assert got == ref
            assert eng.metrics()["draft_proposed"] > 0

    def test_one_sync_per_trailing_window(self):
        """The pipeline's structural contract: exactly one host sync per
        harvested (trailing) window plus one per admission wave — and the
        busy windows keep the 1-per-sync_every-token bound."""
        cfg, params = _model("latent")
        _, eng = _serve(cfg, params, _prompts(cfg), overlap=True,
                        max_new=16, sync_every=4)
        m = eng.metrics()
        assert m["host_syncs"] == m["windows"] + m["admission_syncs"], m
        assert m["host_syncs"] < m["tokens"], m
        decode_tokens = round(m["windows"] / m["decode_syncs_per_token"])
        busy = (m["windows"] - m["windows_idle"]) / max(decode_tokens, 1)
        assert busy <= 1.0 / 4 + 1e-9, m

    def test_overlap_metrics_shape(self):
        cfg, params = _model("latent")
        _, eng = _serve(cfg, params, _prompts(cfg), overlap=True)
        m = eng.metrics()
        assert m["overlap"] is True
        assert 0.0 <= m["window_overlap"] <= 1.0
        assert m["ttft_s"] > 0.0
        assert m["windows_idle"] >= 0
        assert m["tokens_per_s"] > 0.0


class TestContinuousBatching:
    """continuous=True: staged requests install into freed slots INSIDE
    the fused scan (device-side mid-window slot swap), at any pipeline
    depth, with admission prefill staged on a worker thread — all of it
    stream-invariant against the blocking engine."""

    @pytest.mark.parametrize("depth,continuous", [
        (2, False), (3, False), (2, True), (3, True),
    ])
    def test_depth_and_swap_parity(self, depth, continuous):
        cfg, params = _model("latent")
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts)
        got, eng = _serve(cfg, params, prompts, overlap=True,
                          pipeline_depth=depth, continuous=continuous)
        assert got == ref, (depth, continuous)
        m = eng.metrics()
        assert m["pipeline_depth"] == depth
        assert m["continuous"] is continuous
        assert m["host_syncs"] == m["windows"] + m["admission_syncs"], m

    @pytest.mark.parametrize("case,backend", [
        ("dense", "einsum"), ("int8_latent", "einsum"),
        ("latent", "pallas"),
    ])
    def test_variant_backend_parity(self, case, backend):
        cfg, params = _model(case)
        cfg = dataclasses.replace(cfg, attn_backend=backend)
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts)
        got, _ = _serve(cfg, params, prompts, overlap=True,
                        pipeline_depth=3, continuous=True)
        assert got == ref, (case, backend)

    @pytest.mark.parametrize("spec_depth", [0, 2])
    def test_paged_continuous_matches_sync_ring(self, spec_depth):
        cfg, params = _model("latent")
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts)
        got, eng = _serve(cfg, params, prompts, overlap=True,
                          pipeline_depth=3, continuous=True,
                          cache_layout="paged", spec_depth=spec_depth,
                          draft="ngram" if spec_depth else None)
        assert got == ref, spec_depth
        assert eng.metrics()["slot_swaps"] > 0

    def test_chunked_mixed_lengths_match_sync(self):
        """Chunked prefill + staggered budgets: slots free and refill
        mid-window while other slots are still ingesting prompt chunks."""
        cfg, params = _model("latent")
        g = np.random.default_rng(23)
        reqs = [(g.integers(0, cfg.vocab_size,
                            int(g.integers(3, 30))).astype(np.int32),
                 4 + i % 5) for i in range(8)]

        def serve(**kw):
            eng = Engine(cfg, params, max_slots=4, max_len=40,
                         prefill_chunk=6, sync_every=4, **kw)
            for i, (pr, mn) in enumerate(reqs):
                eng.submit(Request(uid=i, prompt=pr.copy(),
                                   max_new_tokens=mn))
            done = eng.run()
            eng.close()
            return {r.uid: r.out_tokens for r in done}

        assert serve(overlap=True, pipeline_depth=3,
                     continuous=True) == serve()

    def test_inline_admission_matches_threaded(self):
        """admission_thread=False stages on the dispatch loop instead of
        the worker — ordering (and therefore streams) cannot differ."""
        cfg, params = _model("latent")
        prompts = _prompts(cfg, n=8)
        ref, et = _serve(cfg, params, prompts, overlap=True,
                         pipeline_depth=3, continuous=True)
        got, ei = _serve(cfg, params, prompts, overlap=True,
                         pipeline_depth=3, continuous=True,
                         admission_thread=False)
        assert got == ref
        assert et.metrics()["admission_thread"] is True
        assert ei.metrics()["admission_thread"] is False
        for eng in (et, ei):
            m = eng.metrics()
            assert m["host_syncs"] == m["windows"] + m["admission_syncs"], m

    def test_saturating_load_swaps_in_scan(self):
        """More requests than slots: continuation requests install via
        the device-side staging queue, not boundary placement — the swap
        counter and the sampled-stream parity prove the install path."""
        cfg, params = _model("latent")
        prompts = _prompts(cfg, n=10)
        ref, _ = _serve(cfg, params, prompts, sampling=SAMPLED)
        got, eng = _serve(cfg, params, prompts, sampling=SAMPLED,
                          overlap=True, pipeline_depth=3, continuous=True)
        assert got == ref
        m = eng.metrics()
        assert m["slot_swaps"] > 0
        assert m["occupancy_device_mean"] > 0.0
        assert m["host_syncs"] == m["windows"] + m["admission_syncs"], m

    def test_profile_records_stage_timeline(self):
        cfg, params = _model("latent")
        _, eng = _serve(cfg, params, _prompts(cfg), overlap=True,
                        pipeline_depth=3, continuous=True)
        prof = eng.metrics()["profile"]
        for stage in ("dispatch", "harvest", "bookkeep",
                      "admission_stage", "backlog_drain",
                      "admission_worker"):
            assert stage in prof["seconds"], prof
            assert prof["seconds"][stage] >= 0.0
        assert sum(prof["shares"].values()) == pytest.approx(1.0)
        # the aggregate is always on
        _, eng2 = _serve(cfg, params, _prompts(cfg, n=2), overlap=True)
        assert eng2.metrics()["profile"]["seconds"]["dispatch"] >= 0.0

    def test_bad_configs_rejected(self):
        cfg, params = _model("latent")
        with pytest.raises(ValueError, match="pipeline_depth"):
            Engine(cfg, params, max_slots=2, max_len=40, overlap=True,
                   pipeline_depth=0)
        with pytest.raises(ValueError):
            Engine(cfg, params, max_slots=2, max_len=40,
                   pipeline_depth=3)            # depth needs overlap
        with pytest.raises(ValueError):
            Engine(cfg, params, max_slots=2, max_len=40, continuous=True)
        with pytest.raises(ValueError, match="layer"):
            Engine(cfg, params, max_slots=2, max_len=40, overlap=True,
                   continuous=True, spec_depth=2, draft="layers:2")


class TestAdaptiveSpec:
    """adaptive_spec=True: a slot whose draft acceptance stays under the
    floor after enough proposals is degraded to plain decode at a window
    boundary — output streams are invariant (verification would have
    rejected those drafts anyway)."""

    def test_streams_invariant_and_degrades_cold_drafts(self):
        cfg, params = _model("latent")
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts, max_new=16)
        # layers:2 over random-init weights: acceptance ~0, so every slot
        # crosses ADAPTIVE_MIN_PROPOSED with a sub-floor accept rate
        got, eng = _serve(cfg, params, prompts, max_new=16, spec_depth=2,
                          draft="layers:2", adaptive_spec=True)
        assert got == ref
        m = eng.metrics()
        assert m["adaptive_spec"] is True
        assert m["spec_degraded"] > 0, m

    def test_overlap_continuous_parity_and_metric(self):
        cfg, params = _model("latent")
        prompts = _prompts(cfg, n=8)
        ref, _ = _serve(cfg, params, prompts, max_new=12)
        got, eng = _serve(cfg, params, prompts, max_new=12, overlap=True,
                          pipeline_depth=3, continuous=True, spec_depth=2,
                          draft="ngram", adaptive_spec=True)
        assert got == ref
        assert eng.metrics()["spec_degraded"] >= 0

    def test_requires_speculation(self):
        cfg, params = _model("latent")
        with pytest.raises(ValueError, match="spec_depth"):
            Engine(cfg, params, max_slots=2, max_len=40,
                   adaptive_spec=True)


def _rand_like(g, x, lead=None):
    """Random array shaped like ``x`` (batch dim resized to ``lead``)."""
    shape = x.shape if lead is None else lead
    dt = np.dtype(x.dtype)
    if dt == bool:
        return g.random(shape) < 0.5
    if np.issubdtype(dt, np.integer):
        info = np.iinfo(dt)
        return g.integers(max(info.min, -1000), min(info.max, 1000), shape,
                          dtype=dt)
    return g.standard_normal(shape).astype(dt)


def _rand_wave(g, pool, w):
    """A prefill cache of ``w`` rows shaped like the pool ``pool``."""
    def one(path, x):
        d = 1 if getattr(path[0], "key", None) == "blocks" else 0
        lead = x.shape[:d] + (w,) + x.shape[d + 1:]
        return jnp.asarray(_rand_like(g, x, lead))
    return jax.tree_util.tree_map_with_path(one, pool)


def _eager_merge(cache, dcache, carry, new, dnew, first, ks, slots, rows,
                 host, full, eos, left, keys0, B):
    """The admission merge as eager per-leaf gathers and scatters: the
    reference the compiled program must reproduce bit for bit."""
    sl, rw = jnp.asarray(slots), jnp.asarray(rows)

    def merge_slot(pool_cache, new_cache):
        def one(path, pool, src):
            if getattr(path[0], "key", None) == "blocks":
                return pool.at[:, sl].set(src[:, rw].astype(pool.dtype))
            return pool.at[sl].set(src[rw].astype(pool.dtype))
        return jax.tree_util.tree_map_with_path(one, pool_cache, new_cache)

    cache = merge_slot(cache, new)
    if dcache is not None:
        dcache = merge_slot(dcache, dnew)
    n = len(slots)
    wb = 1 << (n - 1).bit_length()
    pad = wb - n
    slots_pad = jnp.asarray(list(slots) + [B] * pad)
    rsel = jnp.asarray(list(rows) + [0] * pad)
    full_d = jnp.asarray(np.concatenate([full, np.zeros(pad, bool)]))
    eos_d = jnp.asarray(np.concatenate([eos, np.zeros(pad, np.int32)]))
    left_d = jnp.asarray(np.concatenate([left, np.zeros(pad, np.int32)]))
    keys0_d = jnp.asarray(np.concatenate([keys0,
                                          np.zeros((pad, 2), np.uint32)]))
    first_sel = first[rsel]
    out = dict(carry)
    rows_all = {k: np.concatenate([v, np.zeros((pad,) + v.shape[1:],
                                               v.dtype)])
                for k, v in host.items()}
    rows_all |= {
        "tok": jnp.where(full_d, first_sel, 0),
        "act": jnp.where(full_d, (first_sel != eos_d) & (left_d > 0), True),
        "keys": jnp.where(full_d[:, None], ks[rsel][:, 0], keys0_d),
    }
    for k, v in rows_all.items():
        out[k] = out[k].at[slots_pad].set(
            jnp.asarray(v).astype(out[k].dtype), mode="drop")
    return cache, dcache, out


class TestCompiledMerge:
    """The admission merge is one compiled program (pool cache, layer-
    draft ring and carry rows together): bitwise what the eager per-leaf
    scatters gave, nothing outside the merged slots touched, and no
    retrace for a second round of merges of already-seen shapes."""

    @pytest.mark.parametrize("n", [1, 2, 3])          # row buckets 1, 2, 4
    @pytest.mark.parametrize("draft", [None, "layers:2"])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_matches_eager_merge(self, case, draft, n):
        cfg, params = _model(case)
        kw = {"spec_depth": 2, "draft": draft} if draft else {}
        B, W = 4, 4
        eng = Engine(cfg, params, max_slots=B, max_len=40, overlap=True,
                     **kw)
        g = np.random.default_rng(n + 7 * len(case))

        def fill(tree, shardings):           # placed as the engine's own
            return jax.device_put(jax.tree.map(lambda x: _rand_like(g, x),
                                               tree), shardings)
        eng.cache = fill(eng.cache, eng._cache_shardings)
        if draft:
            eng.draft_cache = fill(eng.draft_cache,
                                   eng._draft_cache_shardings)
        eng._st_dev = fill(eng._st, eng._carry_sh)
        traced = None
        for _ in range(2):                 # second round: seen shapes
            slots = [int(s) for s in g.permutation(B)[:n]]
            rows = [int(r) for r in g.permutation(W)[:n]]
            new = _rand_wave(g, eng.cache, W)
            dnew = _rand_wave(g, eng.draft_cache, W) if draft else None
            first = jnp.asarray(g.integers(0, cfg.vocab_size, W, np.int32))
            ks = jnp.asarray(g.integers(0, 2 ** 32, (W, 2, 2), np.uint32))
            host = {k: _rand_like(g, v, (n,) + v.shape[1:])
                    for k, v in eng._st.items()
                    if k not in ("tok", "act", "keys")}
            full = g.random(n) < 0.7
            eos = g.integers(0, cfg.vocab_size, n, np.int32)
            left = g.integers(0, 3, n).astype(np.int32)
            keys0 = g.integers(0, 2 ** 32, (n, 2), np.uint32)
            before = jax.tree.map(np.asarray, (eng.cache, eng.draft_cache,
                                               eng._st_dev))
            want = _eager_merge(eng.cache, eng.draft_cache, eng._st_dev,
                                new, dnew, first, ks, slots, rows, host,
                                full, eos, left, keys0, B)
            eng._merge(slots, rows, new=new, dnew=dnew,
                       drafts=list(zip(slots, rows)) if draft else None,
                       host=host, pick=(full, eos, left, keys0),
                       first=first, ks=ks)
            got = (eng.cache, eng.draft_cache, eng._st_dev)
            jax.tree.map(np.testing.assert_array_equal,
                         jax.tree.map(np.asarray, got),
                         jax.tree.map(np.asarray, want))
            others = [s for s in range(B) if s not in slots]

            def unchanged(path, a, b):
                d = 1 if getattr(path[0], "key", None) == "blocks" else 0
                np.testing.assert_array_equal(np.take(a, others, axis=d),
                                              np.take(b, others, axis=d))
            for a, b in zip(jax.tree.map(np.asarray, got[:2]), before[:2]):
                jax.tree_util.tree_map_with_path(unchanged, a, b)
            for k, v in got[2].items():
                np.testing.assert_array_equal(np.asarray(v)[others],
                                              before[2][k][others])
            if traced is None:
                traced = eng.trace_counts["merge"]
                assert traced == 1
        assert eng.trace_counts["merge"] == traced, "merge retraced"


def _aligned_copy(a, align=64):
    """``a`` copied into a buffer aligned to ``align`` bytes, the layout
    in which a device_put on the CPU aliases host memory."""
    raw = np.empty(a.nbytes + align, np.uint8)
    off = (-raw.ctypes.data) % align
    out = raw[off: off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


class TestHostMirror:
    def test_device_carry_never_aliases_mirror(self):
        """The overlapped engine keeps writing its host mirror while the
        programs that read the device carry may still be queued: the
        carry it uploads must be a copy, even where the backend could
        alias the mirror's memory."""
        cfg, params = _model("latent")
        eng = Engine(cfg, params, max_slots=4, max_len=40, overlap=True)
        eng._st = {k: _aligned_copy(v) for k, v in eng._st.items()}
        eng._ensure_dev_state()
        want = jax.tree.map(np.array, eng._st_dev)
        for v in eng._st.values():
            if v.dtype == bool:
                np.logical_not(v, out=v)
            else:
                v += 1
        jax.tree.map(np.testing.assert_array_equal,
                     jax.tree.map(np.asarray, eng._st_dev), want)
        eng.close()


class TestAOT:
    def test_aot_no_retrace_and_stream_parity(self):
        """AOT compiles the window exactly once and every prefill bucket
        at construction; serving must not trace anything new (the
        trace-count hook is the first-token-latency regression guard),
        and the streams still equal the sync engine's."""
        cfg, params = _model("latent")
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts)
        eng = Engine(cfg, params, max_slots=4, max_len=40, overlap=True,
                     aot=True)
        compiled = dict(eng.trace_counts)
        assert compiled["window"] == 1
        assert compiled["prefill"] > 0
        for i, pr in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=pr.copy(), max_new_tokens=6))
        done = eng.run()
        eng.close()
        assert {r.uid: r.out_tokens for r in done} == ref
        assert eng.trace_counts == compiled, "serving retraced an executable"

    def test_aot_continuous_depth3_no_retrace(self):
        """The continuous window (carry + staging-queue signature) AOT-
        compiles once; a full depth-3 continuous serve — staging
        scatters, in-scan installs, gen-guarded refills — must not trace
        anything new."""
        cfg, params = _model("latent")
        prompts = _prompts(cfg, n=8)
        ref, _ = _serve(cfg, params, prompts)
        eng = Engine(cfg, params, max_slots=4, max_len=40, overlap=True,
                     aot=True, pipeline_depth=3, continuous=True)
        compiled = dict(eng.trace_counts)
        assert compiled["window"] == 1
        for i, pr in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=pr.copy(), max_new_tokens=6))
        done = eng.run()
        eng.close()
        assert {r.uid: r.out_tokens for r in done} == ref
        assert eng.trace_counts == compiled, "serving retraced an executable"
        assert eng.metrics()["slot_swaps"] > 0

    def test_aot_sync_engine_matches(self):
        """aot is orthogonal to overlap: the blocking engine driven off
        AOT executables emits identical streams too."""
        cfg, params = _model("latent")
        prompts = _prompts(cfg, n=4)
        ref, _ = _serve(cfg, params, prompts)
        got, _ = _serve(cfg, params, prompts, aot=True)
        assert got == ref


class TestLifecycle:
    def test_backlog_thread_drains_and_joins_on_close(self):
        cfg, params = _model("latent")
        eng = Engine(cfg, params, max_slots=4, max_len=40, overlap=True)
        for i, pr in enumerate(_prompts(cfg, n=4)):
            eng.submit(Request(uid=i, prompt=pr.copy(), max_new_tokens=6))
        done = eng.run()
        assert eng._backlog.started          # pipeline actually used it
        eng.close()
        assert not eng._backlog.alive
        assert not [t for t in threading.enumerate()
                    if t.name == "token-backlog"]
        assert all(r.out_tokens for r in done)
        eng.close()                          # idempotent

    def test_context_manager_closes(self):
        cfg, params = _model("latent")
        with Engine(cfg, params, max_slots=4, max_len=40,
                    overlap=True) as eng:
            eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                               max_new_tokens=4))
            eng.run()
        assert not eng._backlog.alive

    def test_run_timeout_counts_completed_windows_and_flushes(self):
        """run(max_steps) under overlap: the bound ticks on HARVESTED
        windows (not dispatches), the warning reports completed windows,
        and the backlog is flushed so the partial streams are whole."""
        cfg, params = _model("latent")
        eng = Engine(cfg, params, max_slots=2, max_len=40, overlap=True,
                     sync_every=2)
        for i in range(4):
            eng.submit(Request(uid=i, prompt=np.arange(5, dtype=np.int32),
                               max_new_tokens=30))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            eng.run(max_steps=1)
        msgs = [str(x.message) for x in w
                if issubclass(x.category, RuntimeWarning)]
        assert any("completed windows" in m and "max_steps=1" in m
                   for m in msgs), msgs
        assert not eng._inflight              # flushed on timeout
        assert eng.windows >= 1
        u = eng.unfinished
        assert u["queued"] + u["in_flight"] > 0
        # the streams that did come out are settled (backlog drained)
        emitted = sum(len(r.out_tokens) for r in eng.scheduler.slot_req
                      if r is not None)
        assert emitted == eng.metrics()["tokens"]
        eng.close()

    def test_run_outlasts_a_slow_admission_prefill(self):
        """A wave whose prefill takes longer than the idle guard's
        bounded waits (a cold compile of a full-size model) is progress:
        run() must drain, not stop with every request still queued."""
        import time
        cfg, params = _model("latent")
        eng = Engine(cfg, params, max_slots=1, max_len=40, overlap=True)
        prepare = eng._admission._prepare

        def slow_prepare(reqs):
            time.sleep(5.0)        # > (max_slots + 3) waits of 1 s each
            return prepare(reqs)

        eng._admission._prepare = slow_prepare
        for i, pr in enumerate(_prompts(cfg, n=2)):
            eng.submit(Request(uid=i, prompt=pr.copy(), max_new_tokens=3))
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            done = eng.run()
        eng.close()
        assert eng.unfinished == {"queued": 0, "in_flight": 0}
        assert sorted(len(r.out_tokens) for r in done) == [3, 3]

    def test_on_token_streaming_order(self):
        """Request.on_token fires once per token, in stream order, on the
        backlog worker — the callback view equals out_tokens."""
        cfg, params = _model("latent")
        seen = {}

        def serve(overlap):
            seen.clear()
            eng = Engine(cfg, params, max_slots=4, max_len=40,
                         overlap=overlap)
            for i, pr in enumerate(_prompts(cfg, n=4)):
                eng.submit(Request(
                    uid=i, prompt=pr.copy(), max_new_tokens=6,
                    on_token=lambda r, t: seen.setdefault(r.uid,
                                                          []).append(t)))
            done = eng.run()
            eng.close()
            assert seen == {r.uid: r.out_tokens for r in done}
            return dict(seen)

        assert serve(True) == serve(False)

    def test_prefix_resurrection_across_generations(self):
        """Paged engine: after every holder of a shared prompt prefix
        retires, its pages sit refcount-0 on the LRU free list with their
        registry keys intact — a later request with the same prefix
        revives them instead of re-prefilling fresh pages."""
        cfg, params = _model("latent")
        g = np.random.default_rng(7)
        sysp = g.integers(0, cfg.vocab_size, 16).astype(np.int32)

        def load(uids):
            return [Request(uid=u, prompt=np.concatenate(
                [sysp, g.integers(0, cfg.vocab_size, 3).astype(np.int32)]),
                max_new_tokens=4) for u in uids]

        eng = Engine(cfg, params, max_slots=4, max_len=40,
                     cache_layout="paged", page_size=8, overlap=True)
        for r in load(range(2)):
            eng.submit(r)
        eng.run()                    # first generation retires fully
        for r in load(range(2, 4)):
            eng.submit(r)
        eng.run()
        eng.close()
        m = eng.metrics()
        assert m["prefix_resurrections"] > 0, m
        assert m["pages_shared"] > 0, m


class TestTokenBacklog:
    """The backlog primitive itself (repro.serving.pipeline)."""

    def test_fifo_order_and_lazy_start(self):
        from repro.serving.pipeline import TokenBacklog
        bl = TokenBacklog()
        assert not bl.started                # sync engines never spawn it
        out = []
        for i in range(100):
            bl.put(lambda i=i: out.append(i))
        bl.flush()
        assert out == list(range(100))       # strict put() order
        bl.close()
        assert not bl.alive

    def test_worker_error_reraises_on_main_thread(self):
        from repro.serving.pipeline import TokenBacklog
        bl = TokenBacklog(name="bl-err")
        bl.put(lambda: 1 / 0)
        with pytest.raises(RuntimeError, match="bl-err"):
            bl.flush()
        bl.close()

    def test_close_is_idempotent_and_put_after_close_raises(self):
        from repro.serving.pipeline import TokenBacklog
        bl = TokenBacklog()
        bl.put(lambda: None)
        bl.close()
        bl.close()
        with pytest.raises(RuntimeError, match="closed"):
            bl.put(lambda: None)

    def test_worker_error_reraises_on_put(self):
        """A crash surfaces on the NEXT put too, not only flush/close —
        the dispatch loop must fail fast instead of queueing into a dead
        worker forever."""
        from repro.serving.pipeline import TokenBacklog
        bl = TokenBacklog(name="bl-put-err")
        bl.put(lambda: 1 / 0)
        bl._q.join()                         # item processed, error latched
        with pytest.raises(RuntimeError, match="bl-put-err"):
            bl.put(lambda: None)
        bl.close()

    def test_worker_error_reraises_on_close(self):
        from repro.serving.pipeline import TokenBacklog
        bl = TokenBacklog(name="bl-close-err")
        bl.put(lambda: 1 / 0)
        with pytest.raises(RuntimeError, match="bl-close-err"):
            bl.close()
        bl.close()                           # still idempotent after raise

    def test_error_skips_rest_but_preserves_liveness(self):
        """Items queued after a crash are not executed (the drain guard),
        and the worker still joins cleanly."""
        from repro.serving.pipeline import TokenBacklog
        ran = []
        bl = TokenBacklog(name="bl-skip")
        bl.put(lambda: ran.append(0))
        bl.put(lambda: 1 / 0)
        bl.put(lambda: ran.append(1))        # enqueued before error latched
        with pytest.raises(RuntimeError, match="bl-skip"):
            bl.flush()
        assert ran == [0]                    # post-crash item skipped
        bl.close()
        assert not bl.alive

    def test_close_during_flush_from_another_thread(self):
        """flush() on one thread + close() on another: both return, every
        item runs exactly once, the worker joins."""
        import time
        from repro.serving.pipeline import TokenBacklog
        ran = []
        bl = TokenBacklog(name="bl-race")
        for i in range(20):
            bl.put(lambda i=i: (time.sleep(0.005), ran.append(i)))
        flusher = threading.Thread(target=bl.flush)
        flusher.start()
        bl.close()
        flusher.join(timeout=10)
        assert not flusher.is_alive()
        assert ran == list(range(20))
        assert not bl.alive

    def test_fifo_under_slow_consumers(self):
        """Strict put() order even when item durations vary wildly — the
        single-worker FIFO is what keeps overlapped streams identical to
        sync streams."""
        import time
        from repro.serving.pipeline import TokenBacklog
        g = np.random.default_rng(5)
        delays = g.uniform(0.0, 0.004, 50)
        out = []
        bl = TokenBacklog()
        for i, d in enumerate(delays):
            bl.put(lambda i=i, d=d: (time.sleep(d), out.append(i)))
        bl.flush()
        assert out == list(range(50))
        bl.close()


class TestAdmissionWorker:
    """The admission-prefill worker primitive (repro.serving.pipeline):
    capacity-gated take/prepare off-thread, crash re-raise on poll."""

    def test_prepares_waves_up_to_capacity(self):
        import collections
        from repro.serving.pipeline import AdmissionWorker
        queue = collections.deque(range(10))

        def take(n):
            return [queue.popleft() for _ in range(min(n, len(queue)))]

        w = AdmissionWorker(take, lambda reqs: ("wave", list(reqs)),
                            name="adm-test")
        w.kick(3)
        assert w.wait(timeout=5.0)
        waves = w.poll()
        assert waves == [("wave", [0, 1, 2])]
        assert len(queue) == 7               # capacity bounded the take
        w.close()

    def test_crash_reraises_on_poll_once(self):
        from repro.serving.pipeline import AdmissionWorker

        def boom(reqs):
            raise RuntimeError("prefill exploded")

        w = AdmissionWorker(lambda n: [1], boom, name="adm-crash")
        w.kick(1)
        assert w.wait(timeout=5.0)           # crash counts as "ready"
        with pytest.raises(RuntimeError, match="adm-crash"):
            w.poll()
        assert w.poll() == []                # raised once, then drained
        w.close()

    def test_wait_times_out_when_nothing_upstream(self):
        import time
        from repro.serving.pipeline import AdmissionWorker
        w = AdmissionWorker(lambda n: [], lambda reqs: reqs,
                            name="adm-idle")
        w.kick(4)
        t0 = time.perf_counter()
        assert not w.wait(timeout=0.2)       # empty take: no wave, no hang
        assert time.perf_counter() - t0 < 5.0
        w.close()


class TestAsyncMesh:
    """The overlapped pipeline over a (2, 4) mesh (runs in the `mesh` CI
    job under forced host devices; skips otherwise)."""

    @pytest.fixture(scope="class")
    def mesh24(self):
        return make_test_mesh(2, 4, skip=True)

    def test_greedy_streams_match_single_device_sync(self, mesh24):
        cfg, params = _model("latent")
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts)
        got, eng = _serve(cfg, params, prompts, overlap=True, mesh=mesh24)
        assert eng.mesh_str == "2x4"
        assert got == ref
        m = eng.metrics()
        assert m["host_syncs"] == m["windows"] + m["admission_syncs"], m

    def test_sampled_spec_streams_match_single_device_sync(self, mesh24):
        cfg, params = _model("latent")
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts, sampling=SAMPLED)
        got, _ = _serve(cfg, params, prompts, sampling=SAMPLED,
                        overlap=True, mesh=mesh24, spec_depth=2,
                        draft="ngram")
        assert got == ref

    def test_aot_overlap_on_mesh(self, mesh24):
        cfg, params = _model("latent")
        prompts = _prompts(cfg, n=4)
        ref, _ = _serve(cfg, params, prompts)
        got, eng = _serve(cfg, params, prompts, overlap=True, aot=True,
                          mesh=mesh24)
        assert got == ref
        assert eng.trace_counts["window"] == 1

    def test_continuous_depth3_on_mesh(self, mesh24):
        """The staging queue + in-scan install under shard_map: stage
        rows shard with the cache pool, the swap scatter stays mode="drop"
        dataflow — streams still equal the single-device sync engine."""
        cfg, params = _model("latent")
        prompts = _prompts(cfg)
        ref, _ = _serve(cfg, params, prompts)
        got, eng = _serve(cfg, params, prompts, overlap=True, mesh=mesh24,
                          pipeline_depth=3, continuous=True)
        assert got == ref
        m = eng.metrics()
        assert m["slot_swaps"] > 0
        assert m["host_syncs"] == m["windows"] + m["admission_syncs"], m
