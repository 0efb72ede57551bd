#!/usr/bin/env python3
"""Chip smoke run: calibrate, compress with ReCalKV, serve — qwen3-4b at
full width (36 layers, d_model 2560, bf16) on a TPU.

    python3 chip_smoke.py           # one chip
    python3 chip_smoke.py --mesh    # four chips: the sharded latent engine

One process, no child processes.  Weights and calibration data are made
from ``--seed``; nothing is downloaded or read from outside the checkout.
One chip runs these phases in order:

  device    fail unless jax.devices()[0] is a TPU;
  kernels   every ring Pallas kernel (dense, latent and int8-latent decode,
            their multi-query verify forms, flash prefill with dense and
            with latent values) compiled for the chip (interpret=False)
            against ``kernels/ref.py`` in float32;
  dense     bf16 init, then the same requests served by ``Engine`` with
            ``launch.serve``'s settings on einsum and on pallas;
  compress  ``calibrate`` (no Fisher) + ``compress("recalkv", keep 0.5)``,
            then the dense-only weights are released;
  latent    the compressed model on einsum, pallas, pallas with an int8
            cache, and pallas over the paged cache; prefill and first
            decode-step logits of pallas against einsum.

``--mesh`` runs only the four-chip phase: the latent engine on a (1, 4)
mesh (pallas and einsum) against a (1, 1) pallas engine on device 0, at
qwen3-4b widths with the depth cut to ``MESH_LAYERS``.

Every phase prints one line: what ran, compile and wall seconds, and the
device's bytes in use and peak.  Wall seconds are smoke timings of one
cold run, compilation included, not benchmark numbers.  Any failure
raises; the last line of stdout, printed only when every phase passed, is
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

GIB = 2 ** 30


@dataclasses.dataclass(frozen=True)
class Load:
    """The served requests.  The default prompts span two length buckets
    (128 and 1024), so each engine compiles its decode window and two
    prefill programs; compile time, not widths, is what a run can cut."""
    slots: int = 8
    max_len: int = 2048
    prompt_lens: tuple[int, ...] = (128, 1024, 128, 600, 100, 800, 128, 1000)
    new_tokens: int = 32

    def prompts(self, vocab: int, seed: int) -> list[np.ndarray]:
        g = np.random.default_rng(seed)
        return [g.integers(0, vocab, n).astype(np.int32)
                for n in self.prompt_lens]


# Bounds, both on max |error| over max |reference|.  Kernels run in
# float32 against a float32 reference at the highest matmul precision; the
# kernels' float32 dots are single-pass bf16 MXU products, which measured
# 4e-3 to 6e-3 on a v5e, while a wrong tile, mask or group is off by order
# 1.  Served logits are bf16 end to end through 36 layers whose two
# backends round in different orders: 0.1 is about 13 bf16 epsilons.
KERNEL_RTOL = 2e-2
LOGIT_RTOL = 0.1
MESH_LAYERS = 2


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------

class CompileClock:
    """Seconds spent in XLA backend compiles, from JAX's own event."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def memory(device) -> tuple[int, int]:
    stats = device.memory_stats() or {}
    return stats.get("bytes_in_use", 0), stats.get("peak_bytes_in_use", 0)


class Phase:
    """Prints the phase's line on success; an exception propagates."""

    def __init__(self, name: str, clock: CompileClock, device):
        self.name, self.clock, self.device = name, clock, device
        self.detail = ""

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        in_use, peak = memory(self.device)
        print(f"[phase {self.name}] {self.detail} | compile "
              f"{self.clock.seconds - self.c0:.1f} s, wall "
              f"{time.perf_counter() - self.t0:.1f} s (smoke timing, not "
              f"a benchmark) | bytes_in_use {in_use / GIB:.2f} GiB, "
              f"peak_bytes_in_use {peak / GIB:.2f} GiB", flush=True)
        return False


def check(cond: bool, msg: str):
    if not cond:
        raise RuntimeError(msg)


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Kernel parity
# ---------------------------------------------------------------------------

def kernel_parity(seed: int) -> dict[str, tuple[float, float]]:
    """(max |kernel - reference|, max |reference|) per ring kernel at
    qwen3-4b widths."""
    from repro.kernels import ops, ref
    from repro.kernels.flash_prefill import flash_prefill_attention
    from repro.kernels.latent_decode import (latent_decode_attention,
                                             latent_decode_attention_mq)
    from repro.kernels.latent_decode_q import (
        latent_decode_attention_mq_quant, latent_decode_attention_quant)
    ref_decode = jax.jit(ref.latent_decode_attention)
    ref_quant = jax.jit(ref.latent_decode_attention_quant)
    ref_prefill = jax.jit(ref.flash_prefill_attention)

    B, S, dh, nq, theta = 8, 2048 + 1, 128, 3, 1e6
    scale = dh ** -0.5
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    normal = lambda *shape: jax.random.normal(next(keys), shape, jnp.float32)
    cur = jax.random.randint(next(keys), (B,), S // 2, S - 1)
    pos = jnp.where(jnp.arange(S)[None] % 97 == 5, -1,
                    jnp.broadcast_to(jnp.arange(S), (B, S)))
    pos_q = cur[:, None] + jnp.arange(nq)
    pos_ext = jnp.concatenate([pos, pos_q], axis=1)
    feed = jnp.arange(nq)[None] <= jnp.arange(B)[:, None] % nq
    bias = ops.decode_bias(pos, cur, None)
    bias_mq = ops.verify_bias(pos_ext, pos_q, feed, None, S)

    def exact(fn, *a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)

    def gap(o, o_ref):
        return (float(jnp.max(jnp.abs(o - o_ref))),
                float(jnp.max(jnp.abs(o_ref))))

    def per_query(o, args, hg, ref_fn):
        """``gap`` of a multi-query output against one reference call per
        query (rows ordered (query, head) within each group)."""
        q, rest = args[0], args[1:-1]
        gaps = [gap(o[:, :, j * hg:(j + 1) * hg],
                    exact(ref_fn, q[:, :, j * hg:(j + 1) * hg], *rest,
                          bias_mq[:, j], scale)) for j in range(nq)]
        return max(e for e, _ in gaps), max(m for _, m in gaps)

    errs = {}
    kw = dict(scale=scale, interpret=False)
    for name, (G, r, hg, s) in {"dense": (8, dh, 4, 1),
                                "latent": (2, 256, 16, 4)}.items():
        if name == "dense":     # identity reconstruction, post-RoPE keys
            r_k = jnp.broadcast_to(jnp.eye(dh), (G, dh, dh))
            cos = jnp.ones((B, S + nq, dh // 2))
            sin = jnp.zeros_like(cos)
        else:
            r_k = normal(G, r, s * dh) * r ** -0.5
            cos, sin = ops.rope_tables_for(pos_ext, dh, theta)
        zk, zv = normal(B, S + nq, G, r), normal(B, S + nq, G, r)
        ref_fn = ref_decode
        args = (normal(B, G, hg, dh), zk[:, :S], zv[:, :S], r_k,
                cos[:, :S], sin[:, :S], bias)
        o = latent_decode_attention(*args, **kw)
        errs[f"{name}_decode"] = gap(o, exact(ref_fn, *args, scale))
        args = (normal(B, G, nq * hg, dh), zk, zv, r_k, cos, sin, bias_mq)
        errs[f"{name}_verify"] = per_query(
            latent_decode_attention_mq(*args, **kw), args, hg, ref_fn)
        if name == "latent":
            zq = jax.random.randint(next(keys), (2, B, S + nq, G, r), -127,
                                    128).astype(jnp.int8)
            zs = jax.random.uniform(next(keys), (2, B, S + nq, G),
                                    minval=0.004, maxval=0.012)
            ref_fn = ref_quant
            args = (normal(B, G, hg, dh), zq[0, :, :S], zs[0, :, :S],
                    zq[1, :, :S], zs[1, :, :S], r_k, cos[:, :S], sin[:, :S],
                    bias)
            o = latent_decode_attention_quant(*args, **kw)
            errs["int8_decode"] = gap(o, exact(ref_fn, *args, scale))
            args = (normal(B, G, nq * hg, dh), zq[0], zs[0], zq[1], zs[1],
                    r_k, cos, sin, bias_mq)
            errs["int8_verify"] = per_query(
                latent_decode_attention_mq_quant(*args, **kw), args, hg,
                ref_fn)

    T_ = 1000                                   # ragged: tail tile masked
    q, k = normal(2, T_, 32, dh), normal(2, T_, 8, dh)
    for name, v in {"dense": normal(2, T_, 8, dh),
                    "latent_values": normal(2, T_, 2, 256)}.items():
        o = flash_prefill_attention(q, k, v, interpret=False)
        v_ref = jnp.repeat(v, 8 // v.shape[2], axis=2)  # kv head -> group
        errs[f"prefill_{name}"] = gap(
            o, exact(ref_prefill, q, k, v_ref))
    return errs


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def serve(cfg, params, load: Load, prompts, *, backend: str, mesh=None,
          **engine_kw) -> tuple[dict[int, list[int]], dict, object]:
    """Serve every prompt through ``Engine`` with ``launch.serve``'s
    settings (overlapped, pipeline depth 3, admission thread, sync every
    8).  Fails unless every request finishes with all its tokens.
    Returns (tokens by uid, metrics, the engine's params)."""
    from repro.serving import Engine, Request
    eng = Engine(cfg, params, max_slots=load.slots, max_len=load.max_len,
                 backend=backend, mesh=mesh, overlap=True, pipeline_depth=3,
                 **engine_kw)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=load.new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    try:
        eng.run()
    finally:
        eng.close()
    unfinished = eng.unfinished
    check(not unfinished["queued"] and not unfinished["in_flight"],
          f"{backend}: unfinished requests {unfinished}")
    short = [r.uid for r in reqs if len(r.out_tokens) != load.new_tokens]
    check(not short, f"{backend}: requests {short} stopped short")
    tokens = {r.uid: list(r.out_tokens) for r in reqs}
    m, served_params = eng.metrics(), eng.params
    del eng
    gc.collect()
    return tokens, m, served_params


def agreement(a: dict[int, list[int]], b: dict[int, list[int]]) -> str:
    """Greedy-token agreement: requests identical end to end, and the
    mean share of each stream before its first differing token."""
    same = sum(a[u] == b[u] for u in a)
    prefix = []
    for u in a:
        n = next((i for i, (x, y) in enumerate(zip(a[u], b[u])) if x != y),
                 len(a[u]))
        prefix.append(n / len(a[u]))
    return f"{same}/{len(a)} identical, prefix agreement {np.mean(prefix):.3f}"


def first_logits(cfg, params, prompts, max_len: int, mesh=None):
    """Logits of the prefill and of the first greedy decode step."""
    from repro.models import transformer as T
    lens = np.array([len(p) for p in prompts], np.int32)
    toks = np.zeros((len(prompts), int(lens.max())), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p

    def f(p, t, n):
        lg0, cache = T.prefill(cfg, p, t, n, max_len=max_len, mesh=mesh)
        nxt = jnp.argmax(lg0, axis=-1).astype(jnp.int32)
        lg1, _ = T.decode_step(cfg, p, cache, nxt, n, mesh=mesh)
        return lg0.astype(jnp.float32), lg1.astype(jnp.float32)

    return jax.jit(f)(params, toks, lens)


def logit_gap(ref_logits, other_logits) -> tuple[float, str]:
    """Worst relative gap over (prefill, first decode step): max |diff|
    over max |reference logit|."""
    gaps = [float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a)))
            for a, b in zip(ref_logits, other_logits)]
    return max(gaps), f"prefill {gaps[0]:.2e}, decode {gaps[1]:.2e}"


# ---------------------------------------------------------------------------
# Runs
# ---------------------------------------------------------------------------

def run_one_chip(cfg, load: Load, seed: int, clock: CompileClock, device):
    """Every single-chip phase after the device check."""
    from repro.api import CompressionSpec, RankPolicy, calibrate, compress
    from repro.data import DataConfig, batch
    from repro.models import transformer as T

    with Phase("kernels", clock, device) as ph:
        errs = kernel_parity(seed)
        bad = {k: v for k, v in errs.items()
               if not v[0] <= KERNEL_RTOL * v[1]}
        ph.detail = ("max abs err / max abs ref: " + ", ".join(
            f"{k} {e:.2e}/{m:.2e}" for k, (e, m) in errs.items())
            + f" (bound: err <= {KERNEL_RTOL} x ref)")
        check(not bad, f"kernel parity outside {KERNEL_RTOL}: {bad}")

    prompts = load.prompts(cfg.vocab_size, seed)
    with Phase("init", clock, device) as ph:
        params = T.init_params(cfg, jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
        nbytes = tree_bytes(params)
        _, peak = memory(device)
        ph.detail = (f"{cfg.name} {cfg.num_layers} layers, {nbytes / GIB:.2f} "
                     f"GiB of {jnp.dtype(cfg.dtype).name} weights")
        # a whole float32 tree would be twice the bf16 bytes
        check(peak < 1.5 * nbytes, f"init peaked at {peak / GIB:.2f} GiB "
              f"for {nbytes / GIB:.2f} GiB of weights")

    served = {}
    for backend in ("einsum", "pallas"):
        with Phase(f"dense-{backend}", clock, device) as ph:
            served[backend], m = serve(cfg, params, load, prompts,
                                       backend=backend)[:2]
            ph.detail = (f"{len(prompts)}/{len(prompts)} requests finished, "
                         f"{m['tokens']} tokens, {m['tokens_per_s']:.1f} "
                         f"tok/s")
    print(f"[dense] pallas vs einsum greedy tokens: "
          f"{agreement(served['einsum'], served['pallas'])}", flush=True)

    with Phase("compress", clock, device) as ph:
        data = DataConfig(vocab_size=min(512, cfg.vocab_size), seq_len=512)
        batches = [batch(data, "calib", i, 4) for i in range(4)]
        calib = calibrate(cfg, params, batches, fisher=False)
        art = compress(cfg, params, CompressionSpec(
            "recalkv", rank_policy=RankPolicy(keep_ratio=0.5)), calib)
        rt = art.cfg.recalkv
        check(rt.rank_k % 128 == 0 and rt.rank_v % 128 == 0,
              f"ranks ({rt.rank_k}, {rt.rank_v}) are not 128-lane multiples")
        del calib, params
        gc.collect()
        lcfg, lparams = art.cfg, art.params
        del art
        latent_bytes = tree_bytes(lparams)
        in_use, _ = memory(device)
        n_tok = sum(int(b["tokens"].size) for b in batches)
        ph.detail = (f"{n_tok} calibration tokens, ranks "
                     f"(k {rt.rank_k}, v {rt.rank_v}) x {rt.group_size}-head "
                     f"groups, latent weights {latent_bytes / GIB:.2f} GiB")
        # dense-only leaves released: what remains is the latent model
        check(in_use < latent_bytes + 0.5 * GIB,
              f"{in_use / GIB:.2f} GiB in use after release, latent "
              f"weights are {latent_bytes / GIB:.2f} GiB")

    latent = {}
    runs = [("einsum", {}), ("pallas", {}),
            ("pallas-int8", {"cfg": dataclasses.replace(
                lcfg, cache_quant_bits=8)}),
            ("pallas-paged", {"cache_layout": "paged"})]
    for name, kw in runs:
        with Phase(f"latent-{name}", clock, device) as ph:
            run_cfg = kw.pop("cfg", lcfg)
            latent[name], m = serve(run_cfg, lparams, load, prompts,
                                    backend=name.split("-")[0], **kw)[:2]
            ph.detail = (f"{len(prompts)}/{len(prompts)} requests finished, "
                         f"{m['tokens']} tokens, {m['tokens_per_s']:.1f} "
                         f"tok/s, cache {m['cache_layout']}"
                         + (f" (page {m['page_size']})"
                            if m["page_size"] else ""))
    for name in ("pallas", "pallas-int8", "pallas-paged"):
        print(f"[latent] {name} vs einsum greedy tokens: "
              f"{agreement(latent['einsum'], latent[name])}", flush=True)

    with Phase("latent-logits", clock, device) as ph:
        two = prompts[:2]
        ref_logits = first_logits(
            dataclasses.replace(lcfg, attn_backend="einsum"), lparams, two,
            load.max_len)
        gap, detail = logit_gap(ref_logits, first_logits(
            dataclasses.replace(lcfg, attn_backend="pallas"), lparams, two,
            load.max_len))
        ph.detail = (f"pallas vs einsum relative logit gap: {detail} "
                     f"(bound {LOGIT_RTOL})")
        check(gap <= LOGIT_RTOL, f"pallas logits off einsum by {gap:.3e}")


def run_mesh(cfg, load: Load, seed: int, clock: CompileClock, device):
    """The latent engine on a (1, 4) mesh (pallas, einsum) against a (1, 1)
    pallas engine on device 0.  Latent weights come straight from the
    seed: this phase tests the sharded engine, not compression."""
    from jax.sharding import Mesh
    from repro.launch.mesh import single_device_mesh
    from repro.models import transformer as T

    devs = jax.devices()
    check(len(devs) >= 4, f"--mesh needs 4 devices, found {len(devs)}")
    mesh4 = Mesh(np.array(devs[:4]).reshape(1, 4), ("data", "model"))
    prompts = load.prompts(cfg.vocab_size, seed)
    two = prompts[:2]
    pallas = dataclasses.replace(cfg, attn_backend="pallas")

    with Phase("mesh-1x1-pallas", clock, device) as ph:
        params = T.init_params(cfg, jax.random.PRNGKey(seed))
        base, m = serve(cfg, params, load, prompts, backend="pallas",
                        mesh=single_device_mesh())[:2]
        base_logits = first_logits(pallas, params, two, load.max_len)
        ph.detail = (f"{len(prompts)}/{len(prompts)} requests finished on "
                     f"{m['mesh']}, {m['tokens_per_s']:.1f} tok/s")

    for backend in ("pallas", "einsum"):
        with Phase(f"mesh-1x4-{backend}", clock, device) as ph:
            toks, m, sharded = serve(cfg, params, load, prompts,
                                     backend=backend, mesh=mesh4)
            whole = [x.shape for x in jax.tree.leaves(sharded)
                     if not x.sharding.is_fully_replicated
                     and x.addressable_shards[0].data.shape == x.shape]
            check(not whole, f"sharded leaves whole on device 0: {whole}")
            n_sharded = sum(not x.sharding.is_fully_replicated
                            for x in jax.tree.leaves(sharded))
            gap, detail = logit_gap(base_logits, first_logits(
                dataclasses.replace(cfg, attn_backend=backend), sharded,
                two, load.max_len, mesh=mesh4))
            ph.detail = (
                f"{len(prompts)}/{len(prompts)} requests finished on "
                f"{m['mesh']}, {m['tokens_per_s']:.1f} tok/s, "
                f"decode_kernel_sharded={m['decode_kernel_sharded']}, "
                f"{n_sharded} sharded param leaves, none whole on device 0; "
                f"vs 1x1 pallas: tokens {agreement(base, toks)}, relative "
                f"logit gap {detail} (bound {LOGIT_RTOL})")
            check(gap <= LOGIT_RTOL, f"1x4 {backend} logits off 1x1 pallas "
                  f"by {gap:.3e}")
            if backend == "pallas":
                check(m["decode_kernel_sharded"],
                      "1x4 pallas decode did not run the sharded kernel")
            del sharded
            gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the four-chip sharded-engine phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    device = devs[0]
    if device.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax.devices()[0] is "
                         f"{device.platform}); it runs only on a TPU host")
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.launch.compile_cache import use_compile_cache
    check(not ops.default_interpret(), "Pallas would run interpreted")
    use_compile_cache()
    clock = CompileClock()
    print(f"[phase device] {device.platform} {device.device_kind}, "
          f"{len(devs)} device(s)", flush=True)

    if args.mesh:
        # Widths whole, depth and prompt buckets cut: the phase checks
        # sharding and collectives, which every layer repeats, and four
        # chips cost four times per second.
        cfg = dataclasses.replace(get_config("qwen3-4b", recalkv_ratio=0.5),
                                  num_layers=MESH_LAYERS, scan_layers=False)
        run_mesh(cfg, Load(prompt_lens=(128, 100, 120, 128, 90, 128, 110,
                                        128)), args.seed, clock, device)
    else:
        cfg = dataclasses.replace(get_config("qwen3-4b"), scan_layers=False)
        run_one_chip(cfg, Load(), args.seed, clock, device)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
