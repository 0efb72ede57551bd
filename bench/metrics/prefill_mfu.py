"""Model FLOPs of the prompts admitted in the window over the device time
of the admission prefill programs (``jit__prefill_fn``) in the trace,
over the chip's peak bf16 rate, in %."""

from bench.lib.work import window_work

PROGRAM = "jit__prefill_fn"


def read(rec):
    if rec.trace is None:
        return None
    t = rec.trace["programs"].get(PROGRAM, 0.0)
    flops = window_work(rec)["prefill_flops"]
    if t <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (t * rec.peaks["bf16_flops_per_s"])
