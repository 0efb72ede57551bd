"""Roofline share of the flash prefill kernel: the least time the chip
could take for the causal attention of the prompts admitted in the window
over the device time of the custom calls inside the admission prefill
programs (``jit__prefill_fn``), in %."""

from bench.lib.work import window_work
from bench.peaks import roofline_seconds

PROGRAM = "jit__prefill_fn"


def read(rec):
    if rec.trace is None:
        return None
    t = rec.trace["custom_calls"].get(PROGRAM, 0.0)
    work = window_work(rec)
    if t <= 0 or work["prefill_attn_bytes"] <= 0:
        return None
    best = roofline_seconds(work["prefill_attn_flops"],
                            work["prefill_attn_bytes"], rec.peaks)
    return 100.0 * best / t
