"""Decode rows that share one expert weight read: held (token, expert)
pairs over non-empty held groups, summed over every MoE layer of the
decode windows harvested in the measured window (``Engine.metrics()``
counters, differenced).  None where the engine has no such counters."""


def read(rec):
    a, b = rec.eng_start, rec.eng_end
    if "moe_decode_groups" not in b:
        return None
    groups = b["moe_decode_groups"] - a["moe_decode_groups"]
    if groups <= 0:
        return None
    return (b["moe_decode_rows"] - a["moe_decode_rows"]) / groups
