"""Per-architecture smoke tests (reduced configs) + decode equivalence.

Every assigned arch: one train step (loss finite, shapes right) and one
prefill+decode step on CPU.  Decode==forward equivalence is checked for
representative families (dense ring, latent ring, MLA absorbed, SSM state,
hybrid, enc-dec).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, RECALKV_APPLICABLE, get_config
from repro.models import transformer as T

KEY = jax.random.PRNGKey(0)


def make_batch(cfg, B=2, Tn=16, seed=1):
    g = np.random.default_rng(seed)
    toks = jnp.asarray(g.integers(0, cfg.vocab_size, (B, Tn)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    if cfg.cross_source_len:
        batch["source"] = jnp.asarray(
            g.normal(size=(B, cfg.cross_source_len, cfg.d_model)), cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_train_step(arch):
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype=jnp.float32)
    params = T.init_params(cfg, KEY)
    batch = make_batch(cfg)
    loss, metrics = T.loss_fn(cfg, params, batch)
    assert jnp.isfinite(loss), arch
    hidden, _ = T.forward_hidden(cfg, params, batch["tokens"],
                                 batch.get("source"))
    assert hidden.shape == (2, 16, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(hidden)))
    # one SGD-flavored step moves the loss
    grads = jax.grad(lambda p: T.loss_fn(cfg, p, batch)[0])(params)
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_decode_step(arch):
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype=jnp.float32)
    params = T.init_params(cfg, KEY)
    batch = make_batch(cfg)
    B, Tn = batch["tokens"].shape
    logits, cache = T.prefill(cfg, params, batch["tokens"],
                              jnp.full((B,), Tn), max_len=32,
                              source=batch.get("source"))
    assert logits.shape == (B, cfg.vocab_size)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache = T.decode_step(cfg, params, cache, nxt, jnp.full((B,), Tn))
    assert logits2.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2))), arch


DECODE_EQUIV_ARCHS = [
    "qwen3-4b",            # dense GQA + qk-norm
    "h2o-danube-1.8b",     # sliding-window ring buffer
    "gemma3-12b",          # local:global mix, dual theta
    "falcon-mamba-7b",     # pure state
    "recurrentgemma-9b",   # hybrid rglru + local (MQA)
    "deepseek-v3-671b",    # absorbed MLA + MoE
    "whisper-small",       # enc-dec with cross cache
]


@pytest.mark.parametrize("arch", DECODE_EQUIV_ARCHS)
def test_decode_matches_forward(arch):
    """Prefill-then-decode must reproduce the full forward logits (MoE
    archs included: routing is dropless, so a token's expert outputs do
    not depend on the batch shape)."""
    cfg = dataclasses.replace(get_config(arch, smoke=True), dtype=jnp.float32,
                              scan_layers=False)
    params = T.init_params(cfg, KEY)
    B, Tn, Lp = 2, 12, 8
    batch = make_batch(cfg, B=B, Tn=Tn)
    hidden, _ = T.forward_hidden(cfg, params, batch["tokens"],
                                 batch.get("source"))
    full = T.logits_for(cfg, params, hidden)
    lg, cache = T.prefill(cfg, params, batch["tokens"][:, :Lp],
                          jnp.full((B,), Lp), max_len=Tn + 4,
                          source=batch.get("source"))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, Lp - 1]),
                               rtol=1e-3, atol=1e-3)
    for t in range(Lp, Tn):
        lg, cache = T.decode_step(cfg, params, cache, batch["tokens"][:, t],
                                  jnp.full((B,), t))
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(full[:, t]), rtol=2e-3, atol=2e-3,
            err_msg=f"{arch} step {t}")


def test_recalkv_decode_matches_forward():
    """Latent-cache decode == latent forward (compressed model path)."""
    cfg = dataclasses.replace(
        get_config("qwen3-4b", smoke=True, recalkv_ratio=0.5),
        dtype=jnp.float32, scan_layers=False)
    params = T.init_params(cfg, KEY)
    batch = make_batch(cfg)
    B, Tn, Lp = 2, 16, 10
    hidden, _ = T.forward_hidden(cfg, params, batch["tokens"])
    full = T.logits_for(cfg, params, hidden)
    lg, cache = T.prefill(cfg, params, batch["tokens"][:, :Lp],
                          jnp.full((B,), Lp), max_len=Tn)
    for t in range(Lp, Tn):
        lg, cache = T.decode_step(cfg, params, cache, batch["tokens"][:, t],
                                  jnp.full((B,), t))
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, t]),
                                   rtol=2e-3, atol=2e-3)


def test_ragged_prefill_lengths():
    """Right-padded prefill: each sequence's logits at its own last token."""
    cfg = dataclasses.replace(get_config("qwen3-4b", smoke=True),
                              dtype=jnp.float32)
    params = T.init_params(cfg, KEY)
    g = np.random.default_rng(3)
    toks = jnp.asarray(g.integers(0, cfg.vocab_size, (2, 10)), jnp.int32)
    lengths = jnp.asarray([10, 6], jnp.int32)
    lg, cache = T.prefill(cfg, params, toks, lengths, max_len=16)
    # sequence 1 padded: its logits must equal an unpadded length-6 prefill
    lg6, _ = T.prefill(cfg, params, toks[1:, :6], jnp.asarray([6]), max_len=16)
    np.testing.assert_allclose(np.asarray(lg[1]), np.asarray(lg6[0]),
                               rtol=1e-3, atol=1e-3)


def test_scan_matches_unrolled():
    """scan-over-periods and the unrolled stack compute the same function."""
    base = get_config("gemma3-12b", smoke=True)
    cfg_s = dataclasses.replace(base, dtype=jnp.float32, scan_layers=True)
    cfg_u = dataclasses.replace(base, dtype=jnp.float32, scan_layers=False)
    params_s = T.init_params(cfg_s, KEY)
    # re-layout scanned params into the unrolled structure
    prefix = []
    n_per = cfg_s.num_periods
    for per in range(n_per):
        for j in range(cfg_s.period):
            prefix.append(jax.tree.map(lambda a: a[per], params_s["blocks"][j]))
    params_u = dict(params_s)
    params_u["prefix"] = tuple(prefix)
    params_u["blocks"] = ()
    batch = make_batch(cfg_s)
    h_s, _ = T.forward_hidden(cfg_s, params_s, batch["tokens"])
    h_u, _ = T.forward_hidden(cfg_u, params_u, batch["tokens"])
    np.testing.assert_allclose(np.asarray(h_s), np.asarray(h_u),
                               rtol=1e-4, atol=1e-4)
