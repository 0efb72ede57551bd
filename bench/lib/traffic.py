"""The one traffic generator.  A mix is a JSON file of parameters under
``bench/traffic/``; this module turns it into requests, and a seed into
their tokens.

Every seed gets the same requests in the same order, and for an open loop
the same arrivals: sizes are stratified quantiles of the mix's
distributions, and gaps stratified quantiles of the exponential, each
ordered once by the mix's own ``size_seed``; the run's seed draws the
tokens.  A run's work then does not hang on its seed: with windows that
hold a few tens of long requests, the order alone moved the admissions
inside a window, and with them the rate, by some 20%.

File keys:
  loop      "closed" (``clients`` callers, each sending its next request
            when the last one finished) or "open" (arrivals at ``rate``
            requests/s, Poisson: exponential gaps, whatever the server does)
  prompt, output   {"dist": "lognormal", "median", "sigma", "min", "max"}
            or {"dist": "uniform", "min", "max"}: lengths in tokens
  block     requests per stratified block (the pattern repeats)
  size_seed seed of the fixed pairing and order of sizes and gaps
  prelude_s seconds the traffic runs before the measured window opens
  drain_s   seconds past the close to wait for requests due in the window
  sample    requests compared against the reference after the window,
            besides the longest one finished
  why       one line: why the mix exists
"""

from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path

import numpy as np

from bench.lib.seeds import np_rng

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request as the generator draws it.  ``due`` is seconds after
    the traffic starts (open loop) and 0 for a closed loop, whose due
    time is when its client sends it."""
    index: int
    prompt_len: int
    output_len: int
    due: float


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    mix = json.loads((directory / f"{name}.json").read_text())
    if mix["loop"] not in ("open", "closed"):
        raise ValueError(f"traffic {name}: loop must be open or closed")
    return mix


def quantiles(dist: dict, m: int) -> list[int]:
    """m stratified draws: the distribution's quantiles at (i + 0.5) / m."""
    us = [(i + 0.5) / m for i in range(m)]
    if dist["dist"] == "lognormal":
        nd = statistics.NormalDist(math.log(dist["median"]), dist["sigma"])
        vals = [math.exp(nd.inv_cdf(u)) for u in us]
    elif dist["dist"] == "uniform":
        lo, hi = dist["min"], dist["max"]
        vals = [lo + u * (hi - lo + 1) - 0.5 for u in us]
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return [int(min(max(round(v), dist["min"]), dist["max"])) for v in vals]


def size_pairs(mix: dict) -> list[tuple[int, int]]:
    """The (prompt, output) sizes of one block, in the order they are
    sent: quantiles paired and ordered by the mix's ``size_seed``."""
    m = mix["block"]
    prompts, outputs = quantiles(mix["prompt"], m), quantiles(mix["output"], m)
    rng = np.random.default_rng(mix["size_seed"])
    pair, order = rng.permutation(m), rng.permutation(m)
    return [(prompts[i], outputs[pair[i]]) for i in order]


def gaps(mix: dict) -> list[float]:
    """The fixed set of inter-arrival gaps of one block (open loop)."""
    m, rate = mix["block"], float(mix["rate"])
    return [-math.log(1.0 - (i + 0.5) / m) / rate for i in range(m)]


def requests(mix: dict, n: int) -> list[Spec]:
    """The first n requests, in the order they are sent: the block of
    ``size_pairs`` (and of ``gaps``, in its own fixed order) repeated."""
    pairs = size_pairs(mix)
    gap_set = None
    if mix["loop"] == "open":
        g = gaps(mix)
        order = np.random.default_rng(mix["size_seed"] + 1).permutation(len(g))
        gap_set = [g[i] for i in order]
    out, t = [], 0.0
    while len(out) < n:
        j = len(out) % len(pairs)
        if gap_set:
            t += gap_set[j]
        p, o = pairs[j]
        out.append(Spec(len(out), p, o, t if gap_set else 0.0))
    return out


def prompt_tokens(seed: int, spec: Spec, vocab: int) -> np.ndarray:
    """The prompt's token ids: uniform over the vocabulary, from the seed."""
    return np_rng(seed, stream=1_000_000 + spec.index).integers(
        0, vocab, spec.prompt_len).astype(np.int32)


def capacity(mix: dict, seconds: float, max_rate: float) -> int:
    """How many requests a run of ``seconds`` (prelude and window) can
    need: arrivals for an open loop, at most ``max_rate`` completions per
    second plus one per client for a closed one."""
    span = seconds + mix["prelude_s"]
    if mix["loop"] == "open":
        return int(span * float(mix["rate"]) * 1.5) + mix["block"]
    return int(span * max_rate) + mix["clients"] + mix["block"]
