"""Model FLOPs of every prefill and decode token delivered in the window
(the configuration's count functions: weight matmuls, attention over the
live context, the ReCalKV key rebuild) over window seconds times the
chip's peak bf16 rate, in %."""

from bench.lib.work import window_work


def read(rec):
    flops = window_work(rec)["flops"]
    return 100.0 * flops / (rec.window_s * rec.peaks["bf16_flops_per_s"])
