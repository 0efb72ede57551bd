"""Roofline share of the decode attention kernel (``latent_decode``, which
also serves the dense cache): the least time the chip could take for the
attention work of the decode tokens delivered in the window (live context
only) over the device time of the custom calls inside the decode window
program (``jit_counted_fn``), in %."""

from bench.lib.work import window_work
from bench.peaks import roofline_seconds

PROGRAM = "jit_counted_fn"


def read(rec):
    if rec.trace is None:
        return None
    t = rec.trace["custom_calls"].get(PROGRAM, 0.0)
    work = window_work(rec)
    if t <= 0 or work["decode_attn_bytes"] <= 0:
        return None
    best = roofline_seconds(work["decode_attn_flops"],
                            work["decode_attn_bytes"], rec.peaks)
    return 100.0 * best / t
