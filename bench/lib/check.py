"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the program finished, drawn from the seed and always holding
the longest one, is run through the plain reference of the configuration's
family: one causal forward over each prompt with its served tokens.  At
every served position the reference's best logit is compared with the
reference's logit of the token the program served; the widest gap is the
number compared.  Greedy decoding with no rounding at all would read 0; a
token the program altered, a state it left unchanged or a lower precision
reads far above the limit.

Limits come from bench/limits/<workload>.json, each set between the
largest reading of sound runs and the smallest reading of the control, as
PERF.md records."""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib.seeds import np_rng


@dataclasses.dataclass
class Number:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def sample(finished, n_sample: int, seed: int):
    """The longest finished request (prompt plus output) and ``n_sample``
    others drawn from the seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (r.spec.prompt_len
                                           + r.spec.output_len, r.spec.index))
    rest = [r for r in finished if r is not longest]
    rng = np_rng(seed, stream=3)
    pick = rng.choice(len(rest), size=min(n_sample, len(rest)),
                      replace=False) if rest else []
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(ref_module, cfg, w, live, precisions=("reference",)):
    """Per served token: the gap below the reference's best logit of the
    token the program served, and for each lower precision the gap of the
    token that precision puts first.  Returns {precision: gaps}."""
    toks = np.concatenate([np.asarray(live.prompt, np.int32),
                           np.asarray(live.tokens[:-1], np.int32)])
    P = len(live.prompt)
    rows = np.arange(P - 1, P - 1 + len(live.tokens))
    ref = np.asarray(ref_module.logits(cfg, w, toks, rows, "reference"))
    best = ref.max(axis=1)
    out = {"reference": best - ref[np.arange(len(rows)), live.tokens]}
    for prec in precisions:
        if prec == "reference":
            continue
        low = np.asarray(ref_module.logits(cfg, w, toks, rows, prec))
        out[prec] = best - ref[np.arange(len(rows)), low.argmax(axis=1)]
    return out


def widest_gap(ref_module, cfg, w, chosen, precisions=("reference",)):
    """Widest gap over every served token of the chosen requests, per
    precision, and the number of tokens compared."""
    widest = {p: 0.0 for p in precisions}
    n = 0
    for live in chosen:
        gaps = served_gaps(ref_module, cfg, w, live, precisions)
        n += len(live.tokens)
        for p in precisions:
            widest[p] = max(widest[p], float(gaps[p].max()))
    return widest, n
