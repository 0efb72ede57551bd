"""The comparison that decides ``correct``, driven through a whole run at
a CPU size (tiny Qwen3, interpret-mode kernels) with the chip check
skipped: a sound run passes, and the control and each fault a served cell
can have come out not correct.

The tiny limit 0.015 sits between what sound tiny runs read (at most
0.004 over six seeds) and what the fp8 control reads (at least 0.045);
the cells' own limits are set from chip readings (PERF.md)."""

import time

import pytest

from bench.lib import cell, check
from bench.tests.tiny import make_root

TINY_LIMIT = 0.015
CELLS = ["tiny-recalkv.tiny_closed", "tiny-dense.tiny_open"]
SEED = 2 ** 31 + 2


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"), limit=TINY_LIMIT)


def run(root, name, seed=SEED, lines=None):
    def log(*a, **_):
        if lines is not None:
            lines.append(" ".join(map(str, a)))
    result, _ = cell.run_cell(name, seed, 2.0, False,
                              t_process=time.perf_counter(),
                              require_tpu=False, root=root, log=log)
    return result


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(root, name):
    lines = []
    res = run(root, name, lines=lines)
    # the warm-up reached every program the window runs
    assert ("inside the window: 0 compiles, 0 programs loaded from the "
            "persistent cache") in lines
    assert res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] <= TINY_LIMIT
    assert list(res["checks"])[-1] == "unfinished"
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_fp8_control_is_not_correct(root, name, monkeypatch):
    """The reference in fp8 (weights, activations, cache) put in the
    program's place: the tokens it would serve are judged instead."""
    widest = check.widest_gap

    def control(ref, cfg, w, chosen, precisions=("reference",)):
        got, n = widest(ref, cfg, w, chosen, ("reference", "fp8"))
        return {"reference": got["fp8"]}, n

    monkeypatch.setattr(check, "widest_gap", control)
    assert not run(root, name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_token_altered_where_produced_is_not_correct(root, name,
                                                     monkeypatch):
    import repro.serving.sampler as S
    orig = S.sample_tokens

    def off_by_one(logits, *a, **k):
        return (orig(logits, *a, **k) + 1) % logits.shape[-1]

    monkeypatch.setattr(S, "sample_tokens", off_by_one)
    assert not run(root, name)["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_step_leaving_its_state_unchanged_is_not_correct(root, name,
                                                         monkeypatch):
    import repro.models.kv_cache as KC
    monkeypatch.setattr(KC, "apply_decode_writes",
                        lambda caches, *a, **k: caches)
    assert not run(root, name)["correct"]
