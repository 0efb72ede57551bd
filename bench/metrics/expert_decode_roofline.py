"""Roofline share of the held-expert grouped matmul (``expert_gmm``) in
the decode window: the least time the chip could take for the decode
expert work of the window over the device self time of the
``jit_counted_fn/expert_gmm`` op, in %.  The work comes from the engine's
MoE counters, differenced over the window: FLOPs 2 * 3 * d * f per held
(token, expert) row; bytes each non-empty held group's three matrices
once per layer call plus each row's activations in and out.  None without
a trace, without the counters, or when the op is not among the trace's
top ops."""

from bench.peaks import roofline_seconds

OP = "jit_counted_fn/expert_gmm"


def read(rec):
    if rec.trace is None:
        return None
    t = sum(s for name, s in rec.trace["device_ops"] if name == OP)
    a, b = rec.eng_start, rec.eng_end
    if t <= 0 or "moe_decode_rows" not in b:
        return None
    rows = b["moe_decode_rows"] - a["moe_decode_rows"]
    groups = b["moe_decode_groups"] - a["moe_decode_groups"]
    if rows <= 0:
        return None
    c, cfg = rec.counts, rec.cell.cfg
    flops = c.expert_row_flops(cfg) * rows
    nbytes = (groups * c.expert_weight_bytes(cfg)
              + rows * c.expert_row_bytes(cfg))
    return 100.0 * roofline_seconds(flops, nbytes, rec.peaks) / t
