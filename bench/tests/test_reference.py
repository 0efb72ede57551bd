"""The plain reference against the program's prefill at a CPU size, and
the fp8 control against the reference."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench.models.qwen3 import program, reference, weights
from bench.tests.tiny import tiny_config


@pytest.mark.parametrize("kind", ["recalkv", "dense"])
@pytest.mark.parametrize("backend", ["einsum", "pallas"])
def test_reference_matches_program_prefill(kind, backend):
    from repro.models import transformer as T
    cfg = tiny_config(kind)
    w = weights.init(cfg, 2 ** 31 + 17)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    mc = dataclasses.replace(program.model_config(cfg), attn_backend=backend)
    got, _ = T.prefill(mc, program.params(cfg, w),
                       jnp.asarray(toks[None], jnp.int32),
                       jnp.asarray([40]), max_len=64)
    ref = reference.logits(cfg, w, toks, [39])
    scale = float(jnp.max(jnp.abs(ref)))
    # bf16 activations through two layers: about 1% of the largest logit
    assert float(jnp.max(jnp.abs(got[0] - ref[0]))) < 0.03 * scale
    fp8 = reference.logits(cfg, w, toks, [39], precision="fp8")
    assert float(jnp.max(jnp.abs(fp8[0] - ref[0]))) > 0.03 * scale


def test_rows_pick_positions():
    cfg = tiny_config("recalkv")
    w = weights.init(cfg, 3)
    toks = np.arange(30) % cfg["vocab_size"]
    all_rows = reference.logits(cfg, w, toks, np.arange(30))
    some = reference.logits(cfg, w, toks, [4, 29])
    np.testing.assert_allclose(np.asarray(some),
                               np.asarray(all_rows)[[4, 29]], rtol=1e-5,
                               atol=1e-5)
    # causal: a token appended after position 4 changes nothing before it
    longer = reference.logits(cfg, w, np.concatenate([toks, [1, 2]]), [4])
    np.testing.assert_allclose(np.asarray(longer)[0],
                               np.asarray(all_rows)[4], rtol=1e-5, atol=1e-5)
