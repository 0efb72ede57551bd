import collections

import numpy as np
import pytest

from bench.lib import traffic
from bench.tests.tiny import TINY_MIXES

# the committed mix, and an open loop that no committed cell runs yet,
# with a block long enough for its gaps to average to the rate
MIXES = {"longctx": traffic.load("longctx"),
         "open": dict(TINY_MIXES["tiny_open"], rate=1.5, block=88)}


@pytest.mark.parametrize("name", sorted(MIXES))
def test_tokens_follow_the_seed_and_nothing_else(name):
    mix = MIXES[name]
    reqs = traffic.requests(mix, 300)
    assert reqs == traffic.requests(mix, 300)
    a = traffic.prompt_tokens(2 ** 31 + 99, reqs[5], 151936)
    assert np.array_equal(a, traffic.prompt_tokens(2 ** 31 + 99, reqs[5],
                                                   151936))
    assert not np.array_equal(a, traffic.prompt_tokens(2 ** 31 + 100,
                                                       reqs[5], 151936))
    assert len(a) == reqs[5].prompt_len


@pytest.mark.parametrize("name", sorted(MIXES))
def test_blocks_repeat_the_same_sizes_and_gaps(name):
    mix = MIXES[name]
    m = mix["block"]
    reqs = traffic.requests(mix, 3 * m)
    sizes = [(r.prompt_len, r.output_len) for r in reqs]
    assert sizes[:m] == sizes[m:2 * m] == sizes[2 * m:]
    assert collections.Counter(sizes[:m]) == collections.Counter(
        traffic.size_pairs(mix))
    # sent in a mixed order, not sorted by length
    assert [p for p, _ in sizes[:m]] != sorted(p for p, _ in sizes[:m])
    if mix["loop"] == "open":
        assert reqs[m - 1].due == pytest.approx(sum(traffic.gaps(mix)))
        assert reqs[2 * m - 1].due == pytest.approx(2 * sum(traffic.gaps(mix)))


def test_sizes_stay_in_range_and_follow_the_mix():
    mix = traffic.load("longctx")
    pairs = traffic.size_pairs(mix)
    ps = sorted(p for p, _ in pairs)
    assert ps[0] >= 1024 and ps[-1] <= 3584
    assert ps[len(ps) // 2] == pytest.approx(2048, rel=0.05)
    outs = [o for _, o in pairs]
    assert min(outs) >= 128 and max(outs) <= 512
    assert np.mean(outs) == pytest.approx(320, rel=0.02)


def test_open_loop_rate():
    mix = MIXES["open"]
    reqs = traffic.requests(mix, 10 * mix["block"])
    rate = len(reqs) / reqs[-1].due
    assert rate == pytest.approx(mix["rate"], rel=0.01)
