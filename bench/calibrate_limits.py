#!/usr/bin/env python3
"""Readings that a limit of ``correct`` is set from, on the chip.

    python3 bench/calibrate_limits.py --workload <cell> --seeds 11,12,13 \\
        --seconds 20 --out limits-<cell>.json

One process; for each seed: weights and engine from the seed, the cell's
own traffic for a short window at the cell's own load, then, with the
engine freed, the same comparison a run makes, over the same sample of
finished requests, read twice: for the tokens the program served (the
lower reading: sound runs), and for the tokens the control would serve,
the reference computed in fp8 (the upper reading).  PERF.md gives the
readings and the limit set between them."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from bench.lib import cell as C, check, registry
    cell = registry.resolve(args.workload)
    C.open_device(cell, require_tpu=True)
    compiles = C.CompileLog()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        fam, w, eng = C.build(cell, seed)
        win = C.measure(eng, cell, cell.mix, seed, args.seconds,
                        fam["program"], compiles)
        eng.close()
        live = win.drv.live
        del eng, win
        gc.collect()
        finished = [r for r in live if r.done]
        chosen = check.sample(finished, cell.mix["sample"], seed)
        t = time.perf_counter()
        widest, n = check.widest_gap(fam["reference"], cell.cfg, w, chosen,
                                     ("reference", "fp8"))
        row = {"seed": seed, "program": widest["reference"],
               "control_fp8": widest["fp8"], "tokens": n,
               "requests": len(chosen),
               "check_s": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del w, live, finished, chosen
        gc.collect()
    with open(args.out, "w") as f:
        json.dump(rows, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
