import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder CPU devices stand in for 2 pods x 256 v5e chips.
For every cell we

    with mesh:
        lowered  = jax.jit(step, in_shardings=..., donate...).lower(**specs)
        compiled = lowered.compile()
        print(compiled.memory_analysis());  print(compiled.cost_analysis())

and record memory / FLOPs / collective-bytes (parsed from the post-SPMD
HLO) into a resumable JSONL that §Roofline and benchmarks/roofline.py read.

Off the chip path: importing this module sets ``XLA_FLAGS`` for 512
forced-host CPU devices, so run it as its own CPU process, never beside
code that drives a TPU.

Usage:
    python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    python -m repro.launch.dryrun --all --out experiments/dryrun.jsonl
    python -m repro.launch.dryrun --all --multi-pod both
"""

import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, RECALKV_APPLICABLE, SHAPES, get_config, shape_applicable
from repro.launch import hlo_analysis as H
from repro.launch import hlo_cost
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, init_state
from repro.runtime import TrainConfig, make_train_step
from repro.sharding import rules

KEY_SPEC = jax.ShapeDtypeStruct((2,), jnp.uint32)


def microbatches_for(cfg: ModelConfig, global_batch: int) -> int:
    n = cfg.param_count()
    k = 16 if n > 1e11 else 8 if n > 8e9 else 4 if n > 3e9 else 2
    while global_batch % k or (global_batch // k) % 16:
        k //= 2
        if k <= 1:
            return 1
    return max(k, 1)


def moment_dtype_for(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.param_count() > 5e10 else jnp.float32


def param_shapes(cfg: ModelConfig):
    return jax.eval_shape(functools.partial(T.init_params, cfg), KEY_SPEC)


def input_specs(cfg: ModelConfig, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    seq, batch, kind = SHAPES[shape_name]
    i32 = jnp.int32
    if kind == "train":
        spec = {"tokens": jax.ShapeDtypeStruct((batch, seq), i32),
                "labels": jax.ShapeDtypeStruct((batch, seq), i32)}
        if cfg.cross_source_len:
            spec["source"] = jax.ShapeDtypeStruct(
                (batch, cfg.cross_source_len, cfg.d_model), cfg.dtype)
        return spec
    if kind == "prefill":
        spec = {"tokens": jax.ShapeDtypeStruct((batch, seq), i32),
                "lengths": jax.ShapeDtypeStruct((batch,), i32)}
        if cfg.cross_source_len:
            spec["source"] = jax.ShapeDtypeStruct(
                (batch, cfg.cross_source_len, cfg.d_model), cfg.dtype)
        return spec
    # decode: one new token against a seq_len cache
    caches = jax.eval_shape(
        functools.partial(T.init_decode_cache, cfg, batch, seq))
    return {"caches": caches,
            "tokens": jax.ShapeDtypeStruct((batch,), i32),
            "cur": jax.ShapeDtypeStruct((batch,), i32)}


def model_flops(cfg: ModelConfig, shape_name: str) -> float:
    seq, batch, kind = SHAPES[shape_name]
    n = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n * seq * batch
    if kind == "prefill":
        return 2.0 * n * seq * batch
    return 2.0 * n * batch


def build_cell(cfg: ModelConfig, shape_name: str, mesh):
    """Returns (fn, arg_shapes, in_shardings, out_shardings, donate).

    Output shardings are pinned (§Perf iteration 2): leaving them to
    propagation let XLA pick replicated layouts for the new decode caches,
    which forced the scan's ys-stacking dynamic-update-slice to
    rematerialize the full cache per device."""
    seq, batch, kind = SHAPES[shape_name]
    p_shapes = param_shapes(cfg)
    p_spec = rules.to_named(rules.param_specs(p_shapes, mesh), mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    repl = NamedSharding(mesh, P())

    if kind == "train":
        opt_cfg = AdamWConfig(moment_dtype=moment_dtype_for(cfg))
        tc = TrainConfig(microbatches=microbatches_for(cfg, batch))
        o_shapes = jax.eval_shape(
            functools.partial(init_state, cfg=opt_cfg), p_shapes)
        o_spec = rules.to_named(rules.opt_specs(o_shapes, None, mesh), mesh)
        b_shapes = input_specs(cfg, shape_name)
        b_spec = rules.to_named(rules.batch_specs(b_shapes, mesh), mesh)
        fn = make_train_step(cfg, opt_cfg, tc)
        metrics_spec = {"grad_norm": repl, "lr": repl, "loss": repl}
        return (fn, (p_shapes, o_shapes, b_shapes),
                (p_spec, o_spec, b_spec),
                (p_spec, o_spec, metrics_spec), (0, 1))

    import math as _math
    dp_axes = rules.batch_axes(mesh)
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    dp_n = _math.prod(mesh.shape[a] for a in dp_axes)

    def logits_sharding(n_batch: int):
        s0 = dp if n_batch % dp_n == 0 else None
        s1 = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
        return NamedSharding(mesh, P(s0, s1))

    if kind == "prefill":
        b_shapes = input_specs(cfg, shape_name)
        b_spec = rules.to_named(rules.batch_specs(b_shapes, mesh), mesh)

        def fn(params, batch_in):
            return T.prefill(cfg, params, batch_in["tokens"],
                             batch_in["lengths"], max_len=seq,
                             source=batch_in.get("source"))
        cache_shapes = jax.eval_shape(
            fn, p_shapes, b_shapes)[1]
        c_spec = rules.to_named(rules.cache_specs(cache_shapes, mesh), mesh)
        return (fn, (p_shapes, b_shapes), (p_spec, b_spec),
                (logits_sharding(batch), c_spec), ())

    # decode
    spec = input_specs(cfg, shape_name)
    c_spec = rules.to_named(rules.cache_specs(spec["caches"], mesh), mesh)
    tok_spec = rules.to_named(rules.batch_specs(
        {"t": spec["tokens"]}, mesh), mesh)["t"]
    cur_spec = rules.to_named(rules.batch_specs(
        {"t": spec["cur"]}, mesh), mesh)["t"]

    fn = functools.partial(T.decode_step, cfg)
    return (fn, (p_shapes, spec["caches"], spec["tokens"], spec["cur"]),
            (p_spec, c_spec, tok_spec, cur_spec),
            (logits_sharding(batch), c_spec), (1,))


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             variant: str = "auto", verbose: bool = True) -> dict:
    """Lower + compile one cell; return the §Dry-run / §Roofline record."""
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "variant": variant}
    ok, why = shape_applicable(arch, shape_name)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    seq, batch, kind = SHAPES[shape_name]
    use_recalkv = (variant == "recalkv" or
                   (variant == "auto" and kind != "train"
                    and RECALKV_APPLICABLE[arch]))
    rec["variant"] = "recalkv" if use_recalkv else "dense"
    try:
        cfg = get_config(arch, recalkv_ratio=0.5 if use_recalkv else None)
        if kind == "decode":
            # §Perf iteration 5: unrolled decode graphs avoid per-iteration
            # while-carry copies of the cache stack (serving stacks unroll).
            import dataclasses as _dc
            cfg = _dc.replace(cfg, scan_layers=False)
        mesh = make_production_mesh(multi_pod=multi_pod)
        fn, arg_shapes, in_sh, out_sh, donate = build_cell(cfg, shape_name, mesh)

        t0 = time.time()
        with mesh:
            jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                             donate_argnums=donate)
            lowered = jitted.lower(*arg_shapes)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

        mem = H.memory_report(compiled)
        cost = H.cost_report(compiled)          # XLA's own (loop-body-once)
        hlo_text = compiled.as_text()
        hc = hlo_cost.analyze(hlo_text)          # trip-count-aware model
        roof = H.Roofline(
            hlo_flops=hc.flops,
            hlo_bytes=hc.bytes,
            collective_bytes=hc.total_collective_bytes,
            model_flops=model_flops(cfg, shape_name),
            num_chips=mesh.devices.size,
        )
        rec.update(status="ok", memory=mem, xla_cost=cost,
                   collectives={k: v for k, v in hc.collective_bytes.items()},
                   top_flops=hc.top_flops[:10], top_bytes=hc.top_bytes[:10],
                   roofline=roof.as_dict())
        if verbose:
            print(f"[dryrun] {arch} {shape_name} {rec['mesh']} "
                  f"({rec['variant']}): compile {rec['compile_s']}s, "
                  f"hbm/device {mem.get('total_hbm_bytes', 0)/2**30:.2f} GiB, "
                  f"bottleneck {roof.bottleneck} "
                  f"(c={roof.t_compute:.2e}s m={roof.t_memory:.2e}s "
                  f"n={roof.t_collective:.2e}s)")
            print(f"  memory_analysis: {mem}")
            print(f"  cost_analysis:   {cost}")
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
        if verbose:
            print(f"[dryrun] {arch} {shape_name} FAILED: {rec['error']}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCHS)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["no", "yes", "both"], default="no")
    ap.add_argument("--variant", choices=["auto", "dense", "recalkv"],
                    default="auto")
    ap.add_argument("--out", default="experiments/dryrun.jsonl")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    archs = ARCHS if (args.all or not args.arch) else (args.arch,)
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"no": [False], "yes": [True], "both": [False, True]}[args.multi_pod]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = set()
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") in ("ok", "skipped"):
                        done.add((r["arch"], r["shape"], r["mesh"]))
                except json.JSONDecodeError:
                    pass

    with open(args.out, "a") as f:
        for arch in archs:
            for shape_name in shapes:
                for mp in pods:
                    mesh_name = "2x16x16" if mp else "16x16"
                    if (arch, shape_name, mesh_name) in done:
                        print(f"[dryrun] skip cached {arch} {shape_name} {mesh_name}")
                        continue
                    rec = run_cell(arch, shape_name, multi_pod=mp,
                                   variant=args.variant)
                    f.write(json.dumps(rec) + "\n")
                    f.flush()


if __name__ == "__main__":
    main()
