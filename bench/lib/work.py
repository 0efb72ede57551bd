"""The work delivered inside the window, from the harness's own records
and the configuration's count functions: a token at output index i of a
request with prompt P came from a decode step over P + i positions; index
0 came from the request's admission prefill."""

from __future__ import annotations


def window_work(rec) -> dict:
    c, cfg = rec.counts, rec.cell.cfg
    out = dict(flops=0, prefill_flops=0, decode_attn_flops=0,
               decode_attn_bytes=0, prefill_attn_flops=0,
               prefill_attn_bytes=0)
    for r in rec.live:
        P = r.spec.prompt_len
        for i, t in enumerate(r.times):
            if not rec.in_window(t):
                continue
            if i == 0:
                f = c.prefill_flops(cfg, P)
                out["prefill_flops"] += f
                out["prefill_attn_flops"] += c.prefill_attn_flops(cfg, P)
                out["prefill_attn_bytes"] += c.prefill_attn_bytes(cfg, P)
            else:
                f = c.decode_token_flops(cfg, P + i)
                out["decode_attn_flops"] += c.decode_attn_flops(cfg, P + i)
                out["decode_attn_bytes"] += c.decode_attn_bytes(cfg, P + i)
            out["flops"] += f
    windows = rec.eng_end["windows"] - rec.eng_start["windows"]
    steps = windows * rec.eng_end["sync_every"]
    out["decode_attn_bytes"] += steps * c.decode_step_fixed_bytes(cfg)
    return out
