"""The three public verbs: ``calibrate`` once, ``compress`` many times,
``serve`` anywhere.

    from repro.api import CompressionSpec, RankPolicy, calibrate, compress
    from repro.launch import make_production_mesh

    calib = calibrate(cfg, params, batches, fisher=True)
    art = compress(cfg, params,
                   CompressionSpec("recalkv",
                                   rank_policy=RankPolicy(keep_ratio=0.5)),
                   calib)
    art.save("experiments/qwen3_r50")      # later: serve(...)
    eng = serve(art, max_slots=128, max_len=32768,
                mesh=make_production_mesh())

``compress`` also accepts the raw calibration batches directly (it will
capture stats — and Fisher scores when the rank policy asks — itself) and
a bare method name instead of a full spec.  ``serve`` boots the
mesh-native continuous-batching engine from an artifact (in-memory or a
saved path) — the compress-offline / serve-forever workflow in one call.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import repro.models.compress as C
from repro.api.artifact import CompressionArtifact
from repro.api.registry import get_strategy
from repro.api.spec import CalibrationData, CompressionSpec
from repro.models.config import ModelConfig


def calibrate(cfg: ModelConfig, params: Any, batches: Sequence[dict], *,
              fisher: bool = False) -> CalibrationData:
    """Run the calibration forward passes once and summarize them.

    ``batches`` are dicts with "tokens" (and "labels" when ``fisher``,
    which additionally captures per-layer Fisher scores for the rank
    allocator).  The result is strategy-agnostic — capture once, reuse
    across every ``compress`` call.
    """
    batches = list(batches)
    stats = C.capture_calibration(cfg, params, batches)
    fk, fv = C.fisher_scores(cfg, params, batches) if fisher else (None, None)
    tokens = sum(int(b["tokens"].size) for b in batches)
    return CalibrationData(stats=stats, fisher_k=fk, fisher_v=fv,
                           token_count=tokens)


def _as_spec(spec) -> CompressionSpec:
    if isinstance(spec, CompressionSpec):
        return spec
    if isinstance(spec, str):
        return CompressionSpec(method=spec)
    raise TypeError(f"spec must be a CompressionSpec or method name, "
                    f"got {type(spec).__name__}")


def compress(cfg: ModelConfig, params: Any,
             spec: CompressionSpec | str = "recalkv",
             calib: CalibrationData | Sequence[dict] | None = None,
             ) -> CompressionArtifact:
    """Compress a dense checkpoint with a registered strategy.

    Returns a durable :class:`CompressionArtifact`; ``artifact.cfg`` /
    ``artifact.params`` plug into every forward/serving entry point, and
    ``save_artifact`` persists the bundle across process boundaries.
    """
    spec = _as_spec(spec)
    strategy = get_strategy(spec.method)
    if calib is None:
        calib = CalibrationData()
    elif not isinstance(calib, CalibrationData):
        calib = calibrate(cfg, params, calib,
                          fisher=spec.rank_policy.use_fisher)
    ccfg, cparams, info = strategy.compress(cfg, params, spec, calib)
    if spec.backend is not None:
        ccfg = dataclasses.replace(ccfg, attn_backend=spec.backend)
    provenance = {
        "method": spec.method,
        "spec": spec.to_dict(),
        "calib_tokens": calib.token_count,
        "fisher": calib.fisher_k is not None and spec.rank_policy.use_fisher,
        **info,
    }
    if ccfg.recalkv is not None:
        provenance["group_size"] = ccfg.recalkv.group_size
        provenance["ranks_by_layer"] = (
            None if ccfg.recalkv.ranks_by_layer is None
            else [list(r) for r in ccfg.recalkv.ranks_by_layer])
    return CompressionArtifact(cfg=ccfg, params=cparams,
                               provenance=provenance)


def serve(artifact: CompressionArtifact | str, *, max_slots: int,
          max_len: int, mesh=None, **engine_kw):
    """Boot a serving :class:`repro.serving.Engine` from a compression
    artifact — either the in-memory result of :func:`compress` or a path
    produced by ``save_artifact``.

    ``mesh`` (a ("data", "model") jax Mesh, see ``repro.launch.mesh``)
    makes the engine mesh-native: params placed by the sharding rules,
    the cache pool sharded slot x sequence, and the fused decode window
    jitted with explicit in/out shardings.  Omitted, the same code path
    runs on a degenerate single-device mesh.  Remaining ``engine_kw``
    (``sampling``, ``sync_every``, ``prefill_chunk``, ``backend``,
    ``source``, the speculative-decoding pair ``spec_depth`` /
    ``draft``, the paged-cache trio ``cache_layout`` / ``page_size`` /
    ``n_pages`` — ``cache_layout="paged"`` pools cache pages across
    slots with copy-on-write prompt-prefix sharing — the pipeline
    knobs ``overlap`` / ``aot`` / ``pipeline_depth`` / ``continuous`` /
    ``admission_thread`` (N-deep window pipeline, device-side mid-window
    slot swap, threaded admission prefill, AOT-compiled executables),
    the admission-policy trio ``policy`` / ``lazy_pages`` /
    ``staging_depth`` — ``policy`` picks the admission order ("fifo",
    "prefix-affinity", "reach-packing", or an ``AdmissionPolicy``
    instance), ``lazy_pages`` allocates cache pages as generation
    reaches them (preempting a policy-chosen victim on pool
    exhaustion), ``staging_depth`` bounds the admission worker's
    look-ahead — plus ``adaptive_spec`` and ``pin_prefixes``; token
    streams are invariant to all of these) pass
    through to the Engine."""
    from repro.serving.engine import Engine  # local: engine imports api too

    if isinstance(artifact, str):
        return Engine.from_artifact(artifact, max_slots=max_slots,
                                    max_len=max_len, mesh=mesh, **engine_kw)
    return Engine(artifact.cfg, artifact.params, max_slots=max_slots,
                  max_len=max_len, mesh=mesh, **engine_kw)
