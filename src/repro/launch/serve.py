"""Serving launcher: continuous-batching demo over a (compressed) model.

``python -m repro.launch.serve --arch qwen3-4b --smoke --requests 8``
spins up the scheduler/sampler/executor engine, feeds it synthetic
prompts, and reports throughput, host-sync rate, slot occupancy and
queue depth, comparing dense vs ReCalKV cache footprints.

``--mesh 2,4`` serves over a (data=2, model=4) mesh — slots shard over
"data", the cache ring's sequence axis over "model" (force host devices
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to try it on
CPU).  Without ``--mesh`` the engine runs the same code path on a
degenerate (1, 1) mesh.

Exits non-zero when any request is left unfinished.  The persistent
compilation cache goes where ``repro.launch.compile_cache`` says.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, RECALKV_APPLICABLE, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import mesh_from_spec
from repro.models import transformer as T
from repro.serving import Engine, Request, SamplingParams


def cache_bytes(tree) -> int:
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--recalkv", type=float, default=None,
                    help="keep ratio, e.g. 0.5")
    ap.add_argument("--backend", choices=("einsum", "pallas"), default=None,
                    help="attention backend (pallas = fused kernels; "
                         "interpret mode off-TPU)")
    ap.add_argument("--sync-every", type=int, default=8,
                    help="decode tokens per host sync (fused window size)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="admit prompts in chunks of this many tokens")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0, help="0 = disabled")
    ap.add_argument("--top-p", type=float, default=1.0, help="1 = disabled")
    ap.add_argument("--seed", type=int, default=0, help="sampling PRNG seed")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="mesh shape, e.g. 2,4 (slots shard over data, "
                         "cache sequence over model); default single-device")
    ap.add_argument("--spec-depth", type=int, default=0,
                    help="speculative decoding: draft tokens verified per "
                         "window iteration (0 disables; streams are "
                         "invariant to this knob)")
    ap.add_argument("--draft", default=None,
                    help="draft proposer for --spec-depth > 0: 'ngram' "
                         "(prompt lookup, default) or 'layers:K' (self-"
                         "draft from the target's first K layers)")
    ap.add_argument("--cache-layout", choices=("ring", "paged"),
                    default="ring",
                    help="'paged' pools cache pages across slots with "
                         "copy-on-write prompt-prefix sharing; token "
                         "streams are identical to 'ring'")
    ap.add_argument("--page-size", type=int, default=None,
                    help="positions per cache page (paged layout only; "
                         "default: largest of 16/8/4/2/1 dividing "
                         "--max-len)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="physical pool pages incl. the null page (paged "
                         "only; default: ring-equivalent capacity)")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="double-buffered pipeline: two decode windows in "
                         "flight, token handling on a backlog thread "
                         "(--no-overlap for the blocking step loop; "
                         "streams are identical either way)")
    ap.add_argument("--aot", action="store_true",
                    help="AOT-compile the window + prefill buckets at "
                         "boot, so the first request pays load time "
                         "rather than trace time")
    ap.add_argument("--pipeline-depth", type=int, default=3,
                    help="in-flight decode windows under --overlap "
                         "(2 = the classic double buffer)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: staged requests install "
                         "into freed slots INSIDE the fused window "
                         "(device-side mid-window slot swap); streams "
                         "are identical to the sync engine")
    ap.add_argument("--admission-thread",
                    action=argparse.BooleanOptionalAction, default=None,
                    help="stage admission prefill on a worker thread "
                         "(default: on whenever --overlap is)")
    ap.add_argument("--policy", default=None,
                    choices=("fifo", "prefix-affinity", "reach-packing"),
                    help="admission policy: 'fifo' (default, strict "
                         "head-of-line), 'prefix-affinity' (group shared-"
                         "prefix requests into one wave and skip their "
                         "prefill via resident pages; paged only), "
                         "'reach-packing' (admit short requests past a "
                         "blocked long one, bounded bypass)")
    ap.add_argument("--lazy-pages", action="store_true",
                    help="lazy page reservation: allocate cache pages as "
                         "generation reaches them instead of worst-case "
                         "up front, preempting a policy-chosen victim on "
                         "pool exhaustion (paged only; streams are "
                         "identical)")
    ap.add_argument("--staging-depth", type=int, default=None,
                    help="max requests staged ahead by the admission "
                         "worker (default 2x --slots)")
    ap.add_argument("--pin-prefixes", type=int, default=0,
                    help="pin the K hottest registered prefix pages "
                         "against pool recycling (paged layout only)")
    ap.add_argument("--adaptive-spec", action="store_true",
                    help="degrade cold-draft slots to plain decode at "
                         "window boundaries (needs --spec-depth > 0; "
                         "streams are invariant)")
    args = ap.parse_args(argv)
    use_compile_cache()

    kw = {"smoke": args.smoke}
    if args.recalkv is not None:
        if not RECALKV_APPLICABLE[args.arch]:
            raise SystemExit(f"ReCalKV inapplicable to {args.arch}")
        kw["recalkv_ratio"] = args.recalkv
    cfg = get_config(args.arch, **kw)

    params = T.init_params(cfg, jax.random.PRNGKey(0))
    src = None
    if cfg.cross_source_len:
        src = jnp.asarray(
            np.random.default_rng(0).normal(
                size=(args.slots, cfg.cross_source_len, cfg.d_model)),
            cfg.dtype)
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                              top_p=args.top_p, seed=args.seed)
    eng = Engine(cfg, params, max_slots=args.slots, max_len=args.max_len,
                 source=src, backend=args.backend, sampling=sampling,
                 sync_every=args.sync_every,
                 prefill_chunk=args.prefill_chunk,
                 mesh=mesh_from_spec(args.mesh),
                 spec_depth=args.spec_depth, draft=args.draft,
                 cache_layout=args.cache_layout, page_size=args.page_size,
                 n_pages=args.n_pages, overlap=args.overlap, aot=args.aot,
                 pipeline_depth=args.pipeline_depth if args.overlap else 2,
                 continuous=args.continuous,
                 admission_thread=args.admission_thread,
                 pin_prefixes=args.pin_prefixes,
                 policy=args.policy, lazy_pages=args.lazy_pages,
                 staging_depth=args.staging_depth,
                 adaptive_spec=args.adaptive_spec)
    spec = (f", spec_depth={args.spec_depth} ({eng.metrics()['draft']})"
            if args.spec_depth else "")
    layout = ("" if args.cache_layout == "ring" else
              f", paged (page_size={eng.page_size}, "
              f"{eng.n_pages} pages)")
    mode = (f"overlapped x{args.pipeline_depth}" if args.overlap
            else "sync") + \
        (", continuous" if args.continuous else "") + \
        (", aot" if args.aot else "")
    print(f"[serve] {cfg.name}: cache {cache_bytes(eng.cache)/2**20:.1f} MiB "
          f"({args.slots} slots x {args.max_len} positions), "
          f"sync_every={args.sync_every}, mesh={eng.mesh_str} "
          f"({len(jax.devices())} devices), {mode}{spec}{layout}")

    g = np.random.default_rng(1)
    for i in range(args.requests):
        plen = int(g.integers(4, args.max_len // 3))
        eng.submit(Request(
            uid=i, prompt=g.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=args.new_tokens))
    finished = eng.run()
    eng.close()
    m = eng.metrics()
    print(f"[serve] {len(finished)} requests, {m['tokens']} tokens in "
          f"{m['run_seconds']:.1f}s ({m['tokens_per_s']:.1f} tok/s), "
          f"ttft {m['ttft_s']*1e3:.1f}ms")
    print(f"[serve] host syncs/token {m['host_syncs_per_token']:.3f} "
          f"(decode windows: {m['decode_syncs_per_token']:.3f}), "
          f"occupancy {m['occupancy_mean']:.2f}/{args.slots}, "
          f"queue depth {m['queue_depth_mean']:.2f}")
    if args.overlap:
        print(f"[serve] overlap: {m['window_overlap']:.2f} of windows "
              f"dispatched before the prior completed, "
              f"{m['windows_idle']} idle windows, "
              f"device occupancy {m['occupancy_device_mean']:.2f}"
              f"/{args.slots}"
              + (f", {m['slot_swaps']} in-scan swaps"
                 if args.continuous else ""))
    if args.spec_depth:
        print(f"[serve] speculation: accept rate {m['accept_rate']:.2f} "
              f"({m['draft_accepted']}/{m['draft_proposed']} draft tokens "
              f"accepted)")
    if args.cache_layout == "paged":
        print(f"[serve] pages: peak {m['pages_peak']}/{m['pages_total']}, "
              f"{m['pages_shared']} shares, {m['cow_forks']} COW forks, "
              f"{m['prefix_resurrections']} prefix resurrections")
        print(f"[serve] admission: policy={m['policy']}, "
              f"{m['prefill_calls']} prefill calls "
              f"({m['prefill_calls_saved']} saved), "
              f"{m['preemptions']} preemptions"
              + (f", {m['pages_parked']} pages parked"
                 if m['pages_parked'] else ""))
    if eng.unfinished["queued"] or eng.unfinished["in_flight"]:
        raise SystemExit(f"[serve] unfinished requests: {eng.unfinished}")
    return finished


if __name__ == "__main__":
    main()
