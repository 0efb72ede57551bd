"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

Wires config -> data -> pjit'd train_step -> fault-tolerant loop.  On this
CPU container it drives the smoke configs end-to-end (the examples train a
~100M model for a few hundred steps); on TPU the same entry point runs the
full configs over the production mesh (pass --mesh 16x16).

Multi-host note: launch one process per host with the same arguments;
jax.distributed.initialize() picks up the cluster env (TPU pods set it
automatically) and the per-process code is identical — the data pipeline
is index-addressable so each process computes its own shard.
"""

from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.data import DataConfig, batch as data_batch
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.optim import AdamWConfig
from repro.runtime import TrainConfig, make_train_step, train_loop
from repro.sharding import rules


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--schedule", choices=["cosine", "wsd"], default="cosine")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--mesh", default=None, help="e.g. 16x16 (TPU only)")
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    # MiniCPM ships with WSD — honor it by default for that arch.
    schedule = "wsd" if args.arch == "minicpm-2b" else args.schedule
    opt_cfg = AdamWConfig(lr=args.lr)
    tc = TrainConfig(
        microbatches=args.microbatches, schedule=schedule,
        warmup_steps=max(args.steps // 10, 1), total_steps=args.steps,
        grad_compress=args.grad_compress, ckpt_dir=args.ckpt_dir)

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq)
    src = None
    if cfg.cross_source_len:
        g = np.random.default_rng(0)
        src = jnp.asarray(
            g.normal(size=(args.batch, cfg.cross_source_len, cfg.d_model)),
            cfg.dtype)

    def batch_fn(step):
        b = data_batch(dc, "train", step, args.batch)
        out = {k: jnp.asarray(v) for k, v in b.items()}
        if src is not None:
            out["source"] = src
        return out

    step_fn = make_train_step(cfg, opt_cfg, tc)
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("pod", "data", "model")[-len(dims):]
        mesh = jax.make_mesh(dims, axes)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        p_shapes = jax.eval_shape(functools.partial(T.init_params, cfg), key)
        p_sh = rules.to_named(rules.param_specs(p_shapes, mesh), mesh)
        step_fn = jax.jit(step_fn, in_shardings=(p_sh, None, None),
                          donate_argnums=(0, 1))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0, 1))

    out = train_loop(cfg, opt_cfg, tc, batch_fn, step_fn=step_fn)
    print(f"final loss: {out['losses'][-1]:.4f} "
          f"(first: {out['losses'][0]:.4f})")
    return out


if __name__ == "__main__":
    main()
