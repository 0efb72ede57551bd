"""Sampler determinism suite: greedy parity, exact top-k masking, minimal
top-p nucleus, and token-for-token PRNG reproducibility through the
engine's fused decode loop."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import Engine, Request, SamplingParams
from repro.serving.sampler import NEG_INF, filtered_logits, sample_tokens

KEY = jax.random.PRNGKey(0)


def _cfg(**kw):
    cfg = get_config("qwen3-4b", smoke=True, **kw)
    return dataclasses.replace(cfg, dtype=jnp.float32)


def _keys(n):
    return jnp.stack([jax.random.PRNGKey(100 + i) for i in range(n)])


class TestSampleTokens:
    def test_temperature_zero_is_exact_argmax(self):
        rng = np.random.default_rng(0)
        logits = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
        out = sample_tokens(logits, jnp.zeros(5), jnp.zeros(5, jnp.int32),
                            jnp.ones(5), _keys(5))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_temperature_to_zero_converges_to_greedy(self):
        """As T -> 0 the sampled distribution collapses onto argmax."""
        rng = np.random.default_rng(1)
        logits = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
        out = sample_tokens(logits, jnp.full(4, 1e-4),
                            jnp.zeros(4, jnp.int32), jnp.ones(4), _keys(4))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_tiny_temperature_routes_to_greedy_no_nan(self):
        """Regression: temperature=1e-8 was clamped to _TEMP_EPS and the
        scaled logits could overflow float32 to inf, turning the top-p
        softmax — and every sampled token in the row — into NaN garbage.
        Sub-floor temperatures are semantically greedy and must return
        exact argmax."""
        rng = np.random.default_rng(21)
        # large-magnitude logits make the overflow concrete: 3e3 / 1e-6
        # is comfortably finite, but the old path scaled by 1e6 with no
        # clamp and mixed rows could push the filter into inf territory
        logits = jnp.asarray(rng.normal(size=(4, 64)) * 3e3, jnp.float32)
        temps = jnp.asarray([1e-8, 1e-7, 0.0, 1e-9], jnp.float32)
        out = sample_tokens(logits, temps, jnp.zeros(4, jnp.int32),
                            jnp.full(4, 0.9), _keys(4))
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_tiny_temperature_mixed_with_sampled_rows(self):
        """The greedy routing is per-row: a sub-floor row in a batch with
        genuinely sampled rows returns argmax while the sampled rows stay
        finite and in-vocab (no NaN poisoning through the shared filter)."""
        rng = np.random.default_rng(22)
        logits = jnp.asarray(rng.normal(size=(3, 32)) * 1e4, jnp.float32)
        temps = jnp.asarray([1e-8, 0.9, 1e-7], jnp.float32)
        out = np.asarray(sample_tokens(
            logits, temps, jnp.full(3, 8, jnp.int32), jnp.full(3, 0.9),
            _keys(3)))
        greedy = np.asarray(jnp.argmax(logits, -1))
        assert out[0] == greedy[0] and out[2] == greedy[2]
        assert 0 <= out[1] < 32

    def test_huge_logits_with_small_temperature_stay_finite(self):
        """Scaled logits are clamped before filtering: even logits near
        the float32 edge divided by a small temperature must produce an
        in-vocab token, not a NaN-driven index.  (Values that overflow
        before the clamp tie at the bound, so either max-tier token is
        acceptable — the contract is finiteness, not ordering at 1e39.)"""
        logits = jnp.asarray([[1e35, 2e35, -1e35, 0.0]], jnp.float32)
        out = np.asarray(sample_tokens(
            logits, jnp.asarray([1e-4]), jnp.zeros(1, jnp.int32),
            jnp.asarray([0.5]), _keys(1)))
        assert out[0] in (0, 1)     # a max-tier token, never NaN garbage

    def test_per_row_mixed_policies_one_call(self):
        """Greedy and sampled rows coexist in one batched call (one trace
        serves any request mix)."""
        rng = np.random.default_rng(2)
        logits = jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
        temp = jnp.asarray([0.0, 5.0, 0.0])
        greedy = np.asarray(jnp.argmax(logits, -1))
        out = np.asarray(sample_tokens(logits, temp, jnp.zeros(3, jnp.int32),
                                       jnp.ones(3), _keys(3)))
        assert out[0] == greedy[0] and out[2] == greedy[2]


class TestTopK:
    def test_masks_exactly_k(self):
        rng = np.random.default_rng(3)
        logits = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
        for k in (1, 7, 32, 128):
            out = filtered_logits(logits, jnp.full(4, k, jnp.int32),
                                  jnp.ones(4))
            kept = np.asarray(out > NEG_INF / 2).sum(axis=-1)
            np.testing.assert_array_equal(kept, np.full(4, k))

    def test_keeps_the_k_largest(self):
        logits = jnp.asarray([[0.1, 3.0, 2.0, -1.0, 2.5]], jnp.float32)
        out = np.asarray(filtered_logits(
            logits, jnp.asarray([3], jnp.int32), jnp.ones(1)))[0]
        assert set(np.nonzero(out > -1e29)[0]) == {1, 2, 4}

    def test_zero_disables(self):
        logits = jnp.asarray(np.random.default_rng(4).normal(size=(2, 16)),
                             jnp.float32)
        out = filtered_logits(logits, jnp.zeros(2, jnp.int32), jnp.ones(2))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))


class TestTopP:
    def test_minimal_nucleus(self):
        """probs (0.5, 0.3, 0.15, 0.05): top_p=0.75 must keep exactly
        {0.5, 0.3} — the smallest prefix reaching 0.75."""
        probs = np.asarray([0.5, 0.3, 0.15, 0.05])
        logits = jnp.asarray(np.log(probs)[None, :], jnp.float32)
        out = np.asarray(filtered_logits(
            logits, jnp.zeros(1, jnp.int32), jnp.asarray([0.75])))[0]
        assert set(np.nonzero(out > -1e29)[0]) == {0, 1}

    def test_crossing_token_is_kept(self):
        """top_p=0.79: cumulative 0.5, 0.8 — token 1 crosses and is kept."""
        probs = np.asarray([0.5, 0.3, 0.15, 0.05])
        logits = jnp.asarray(np.log(probs)[None, :], jnp.float32)
        out = np.asarray(filtered_logits(
            logits, jnp.zeros(1, jnp.int32), jnp.asarray([0.79])))[0]
        assert set(np.nonzero(out > -1e29)[0]) == {0, 1}

    def test_top1_always_survives(self):
        logits = jnp.asarray([[0.0, 5.0, 1.0]], jnp.float32)
        out = np.asarray(filtered_logits(
            logits, jnp.zeros(1, jnp.int32), jnp.asarray([1e-6])))[0]
        assert set(np.nonzero(out > -1e29)[0]) == {1}

    def test_one_disables(self):
        logits = jnp.asarray(np.random.default_rng(5).normal(size=(2, 16)),
                             jnp.float32)
        out = filtered_logits(logits, jnp.zeros(2, jnp.int32), jnp.ones(2))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))


class TestSamplingParamsValidation:
    @pytest.mark.parametrize("bad", [
        {"temperature": -0.1}, {"top_k": -1}, {"top_p": 0.0},
        {"top_p": 1.5},
    ])
    def test_rejects(self, bad):
        with pytest.raises(ValueError):
            SamplingParams(**bad)


class TestSlotKey:
    @pytest.mark.parametrize("seed", [0, 11, 2 ** 31 - 1, 2 ** 31, -7,
                                      2 ** 40 + 3])
    def test_host_key_is_jax_fold_in(self, seed):
        """The host-computed slot key is JAX's fold_in(PRNGKey(seed),
        uid) under JAX's default config, bit for bit."""
        g = np.random.default_rng(seed & 0xFFFF)
        uids = [0, 1, 2 ** 31, 2 ** 32 - 1] + [
            int(u) for u in g.integers(0, 2 ** 32, 16)]
        sp = SamplingParams(seed=seed)
        for uid in uids:
            want = np.asarray(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                 uid))
            got = sp.slot_key(uid)
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want, err_msg=str(uid))


class TestEngineSampling:
    """The determinism contracts through the full fused decode loop."""

    def _serve(self, cfg, params, prompts, sampling, *, slots=2,
               max_new=6, sync_every=8):
        eng = Engine(cfg, params, max_slots=slots, max_len=37,
                     sampling=sampling, sync_every=sync_every)
        for i, pr in enumerate(prompts):
            eng.submit(Request(uid=i, prompt=pr.copy(), max_new_tokens=max_new))
        return {r.uid: r.out_tokens for r in eng.run()}

    def test_temperature_zero_matches_seed_greedy_loop(self):
        """temperature=0 through the fused loop == the seed engine's
        prefill + per-token argmax decode, token for token."""
        cfg = _cfg(recalkv_ratio=0.5)
        params = T.init_params(cfg, KEY)
        g = np.random.default_rng(6)
        prompts = [g.integers(0, cfg.vocab_size, 5 + i).astype(np.int32)
                   for i in range(3)]
        got = self._serve(cfg, params, prompts,
                          SamplingParams(temperature=0.0), slots=3)
        for i, pr in enumerate(prompts):
            toks = jnp.asarray(pr[None, :])
            lens = jnp.asarray([len(pr)], jnp.int32)
            logits, caches = T.prefill(cfg, params, toks, lens, max_len=37)
            ref = [int(np.asarray(jnp.argmax(logits, -1))[0])]
            cur = lens.astype(jnp.int32)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            while len(ref) < 6:
                logits, caches = T.decode_step(cfg, params, caches, tok, cur)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                ref.append(int(np.asarray(tok)[0]))
                cur = cur + 1
            assert got[i] == ref, f"uid={i}"

    def test_fixed_key_reproduces_token_for_token(self):
        cfg = _cfg()
        params = T.init_params(cfg, KEY)
        g = np.random.default_rng(7)
        prompts = [g.integers(0, cfg.vocab_size, 6).astype(np.int32)
                   for _ in range(2)]
        sp = SamplingParams(temperature=0.9, top_k=64, top_p=0.95, seed=11)
        a = self._serve(cfg, params, prompts, sp)
        b = self._serve(cfg, params, prompts, sp)
        assert a == b

    def test_sampled_stream_is_batch_invariant(self):
        """Per-slot keys advance per *emitted* token, so a request's
        sampled stream must not depend on its batch-mates or on window
        size."""
        cfg = _cfg()
        params = T.init_params(cfg, KEY)
        g = np.random.default_rng(8)
        prompt = g.integers(0, cfg.vocab_size, 7).astype(np.int32)
        sp = SamplingParams(temperature=0.8, seed=3)
        solo = self._serve(cfg, params, [prompt], sp, slots=1, sync_every=4)
        noise = [g.integers(0, cfg.vocab_size, 4 + i).astype(np.int32)
                 for i in range(2)]
        crowded = self._serve(cfg, params, [prompt] + noise, sp, slots=3,
                              sync_every=8)
        assert solo[0] == crowded[0]

    def test_seed_changes_the_stream(self):
        cfg = _cfg()
        params = T.init_params(cfg, KEY)
        g = np.random.default_rng(9)
        prompts = [g.integers(0, cfg.vocab_size, 6).astype(np.int32)]
        a = self._serve(cfg, params, prompts,
                        SamplingParams(temperature=1.5, seed=0), max_new=12)
        b = self._serve(cfg, params, prompts,
                        SamplingParams(temperature=1.5, seed=1), max_new=12)
        assert a[0] != b[0]

    def test_per_request_sampling_overrides_engine_default(self):
        cfg = _cfg()
        params = T.init_params(cfg, KEY)
        g = np.random.default_rng(10)
        prompt = g.integers(0, cfg.vocab_size, 6).astype(np.int32)
        eng = Engine(cfg, params, max_slots=2, max_len=37,
                     sampling=SamplingParams(temperature=1.2, seed=5))
        eng.submit(Request(uid=0, prompt=prompt.copy(), max_new_tokens=6,
                           sampling=SamplingParams(temperature=0.0)))
        eng.submit(Request(uid=1, prompt=prompt.copy(), max_new_tokens=6))
        done = {r.uid: r.out_tokens for r in eng.run()}
        greedy = self._serve(cfg, params, [prompt],
                             SamplingParams(temperature=0.0), slots=1)
        assert done[0] == greedy[0]          # override -> greedy
        assert done[1] != done[0]            # default stays sampled
