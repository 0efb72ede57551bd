"""The dropless held-expert MoE layer (models/layers.py), its grouped
matmul kernel (kernels/expert_gmm.py) and the engine's MoE counters, at
a small size on the CPU with interpret-mode kernels.

A device holds experts first_held .. first_held + num_held - 1 of a
router that spans all of them, and computes only their part of each
token's output: the shares of a layer add up to the whole layer, and a
token's output never depends on the rows beside it."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from repro.kernels import ref  # noqa: E402
from repro.kernels.expert_gmm import expert_gmm  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import ModelConfig, MoEConfig  # noqa: E402

BACKENDS = ["einsum", "pallas"]
E, K, D, F = 16, 4, 32, 16


def moe_cfg(backend="einsum", first_held=0, num_held=None):
    return ModelConfig(
        name="moe-held-test", family="moe", num_layers=1, d_model=D,
        num_heads=4, num_kv_heads=4, d_head=8, d_ff=F, vocab_size=64,
        moe=MoEConfig(num_experts=E, top_k=K, d_expert=F,
                      first_held=first_held, num_held=num_held),
        attn_backend=backend, dtype=jnp.float32)


def whole_layer():
    """Parameters of an uncut 16-expert layer, and an input batch."""
    p = T.init_ffn_params(moe_cfg(), jax.random.PRNGKey(0), dense=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, D), jnp.float32)
    return p, x


@functools.partial(jax.jit, static_argnums=2)
def run(p, x, cfg, valid=None):
    """The layer under cfg's backend: Pallas kernel or ragged_dot."""
    return L.moe_ffn(p, x, cfg, valid,
                     use_kernel=cfg.attn_backend == "pallas")


def share(p, first, n):
    return dict(p, wg=p["wg"][first:first + n], wi=p["wi"][first:first + n],
                wo=p["wo"][first:first + n])


@pytest.mark.parametrize("backend", BACKENDS)
def test_held_shares_add_up_to_the_whole_layer(backend):
    """All 8 shares of 2 experts each, computed with the same router, sum
    to the uncut layer (float32: only summation order differs)."""
    p, x = whole_layer()
    whole, _ = run(p, x, moe_cfg(backend))
    parts = [run(share(p, e0, 2), x, moe_cfg(backend, e0, 2))[0]
             for e0 in range(0, E, 2)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(parts[0] - whole))) > 1e-3


@pytest.mark.parametrize("backend", BACKENDS)
def test_a_token_output_ignores_its_batch_mates(backend):
    """Alone, beside other tokens, beside pad rows and beside empty slots
    (rows marked invalid), a token's MoE output is the same.  Capacity
    dispatch would drop it when its experts filled up; here the only
    difference allowed is float32 rounding of the same products."""
    cfg = moe_cfg(backend, first_held=4, num_held=6)
    p, x = whole_layer()
    p = share(p, 4, 6)
    tok = x[:1, :1]
    alone, _ = run(p, tok, cfg)
    crowd, _ = run(p, x, cfg)
    valid = jnp.zeros((3, 5), bool).at[0, 0].set(True)
    padded, _ = run(p, x, cfg, valid=valid)
    for other in (crowd[0, 0], padded[0, 0]):
        np.testing.assert_allclose(np.asarray(other), np.asarray(alone[0, 0]),
                                   rtol=1e-5, atol=1e-6)
    # invalid rows are routed to no expert: they get nothing at all
    assert float(jnp.max(jnp.abs(padded[1:]))) == 0.0
    assert float(jnp.max(jnp.abs(padded[0, 1:]))) == 0.0


def host_recount(p, x, cfg, valid):
    """The three MoE counters recounted on the host from the router."""
    m = cfg.moe
    h = np.asarray(x, np.float64).reshape(-1, D)
    logits = h @ np.asarray(p["router"], np.float64)
    sel = np.argsort(-logits, axis=1)[:, :m.top_k]
    live = np.asarray(valid).reshape(-1)
    held = (sel >= m.first_held) & (sel < m.first_held + m.held_count)
    held &= live[:, None]
    return [int(live.sum()) * m.top_k, int(held.sum()),
            len(set(sel[held].tolist()))]


@pytest.mark.parametrize("backend", BACKENDS)
def test_counters_equal_a_host_recount(backend):
    cfg = moe_cfg(backend, first_held=2, num_held=8)
    p, x = whole_layer()
    p = share(p, 2, 8)
    valid = jnp.asarray(np.random.default_rng(3).random((3, 5)) < 0.6)
    _, aux = run(p, x, cfg, valid=valid)
    assert [int(v) for v in aux["moe"]] == host_recount(p, x, cfg, valid)


GMM_CASES = {
    "mixed_with_empty_groups": ([3, 0, 5, 0, 2], 12),
    "no_held_rows": ([0, 0, 0, 0, 0], 12),
    "all_rows_in_one_group": ([0, 12, 0, 0, 0], 12),
    "groups_across_row_tiles": ([40, 0, 100, 7, 1], 160),
    "rows_past_the_groups": ([0, 0, 0, 0, 3], 300),
}


@pytest.mark.parametrize("case", sorted(GMM_CASES))
def test_expert_gmm_matches_its_oracle(case):
    sizes, m = GMM_CASES[case]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, 64, 256)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    got = expert_gmm(x, w, gs, block_m=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.expert_gmm(x, w, gs)),
                               rtol=1e-5, atol=1e-4)


def test_expert_gmm_bf16_rows():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(48, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(3, 64, 128)), jnp.bfloat16)
    gs = jnp.asarray([20, 0, 21], jnp.int32)
    got = expert_gmm(x, w, gs, interpret=True)
    assert got.dtype == jnp.bfloat16
    # float32 accumulation, one bf16 rounding of the result
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref.expert_gmm(x, w, gs)),
                               rtol=1e-2, atol=5e-2)


def stacked_case(case):
    """Rows, a 3-layer (L, G, K, N) weight stack and group sizes: a
    GMM_CASES routing in float32, or bf16 rows whose first row tile is
    shared by two groups."""
    if case == "bf16_rows":
        (sizes, m), dt = ([20, 0, 21], 48), jnp.bfloat16
    else:
        (sizes, m), dt = GMM_CASES[case], jnp.float32
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(m, 64)), dt)
    w = jnp.asarray(rng.normal(size=(3, len(sizes), 64, 256)), dt)
    return x, w, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", sorted(GMM_CASES) + ["bf16_rows"])
def test_stacked_expert_gmm_reads_the_layer_in_place(case, layer):
    """Over a layer stack at a traced layer the kernel is the one-layer
    kernel over that layer's slice, bit for bit, and its oracle's
    match."""
    x, w, gs = stacked_case(case)
    got = expert_gmm(x, w, gs, layer=jnp.int32(layer), block_m=32,
                     interpret=True)
    one = expert_gmm(x, w[layer], gs, block_m=32, interpret=True)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
    want = ref.expert_gmm(x, w, gs, layer=layer)
    np.testing.assert_array_equal(np.asarray(want),
                                  np.asarray(ref.expert_gmm(x, w[layer], gs)))
    tol = ({"rtol": 1e-2, "atol": 5e-2} if case == "bf16_rows"
           else {"rtol": 1e-5, "atol": 1e-4})
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **tol)


def scan_consts(fn, *args):
    """Shapes of the operands the first layer scan of ``fn`` closes
    over (its loop-invariant operands)."""
    eqn = next(e for e in jax.make_jaxpr(fn)(*args).eqns
               if e.primitive.name == "scan")
    return [v.aval.shape for v in eqn.invars[:eqn.params["num_consts"]]]


@pytest.mark.parametrize("pattern", [("attn",), ("attn_dense", "attn")],
                         ids=["moe", "dense_then_moe"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_scanned_stack_matches_the_per_layer_form(backend, pattern):
    """Two scanned periods, whose MoE blocks hand their whole expert
    stacks to every layer, give the logits and MoE counters of the same
    layers unscanned, each with its own (G, K, N) slice, in prefill and
    decode."""
    cfg = dataclasses.replace(moe_cfg(backend, first_held=2, num_held=8),
                              num_layers=2 * len(pattern),
                              layer_pattern=pattern)
    params = T.init_params(cfg, jax.random.PRNGKey(4))
    flat = dataclasses.replace(cfg, scan_layers=False)
    per_layer = dict(params, blocks=(), prefix=tuple(
        jax.tree.map(lambda a, i=i: a[i], blk)
        for i in range(2) for blk in params["blocks"]))
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, 64, (3, 6)), jnp.int32)
    lens = jnp.asarray([6, 4, 1])
    nxt = jnp.asarray(rng.integers(0, 64, 3), jnp.int32)
    active = jnp.asarray([True, True, False])
    outs = []
    for c, p in ((cfg, params), (flat, per_layer)):
        lg, caches = jax.jit(functools.partial(T.prefill, c, max_len=16))(
            p, toks, lens)
        step = functools.partial(T.decode_step, c, moe_counts=True)
        outs.append((lg, caches) + jax.jit(step)(p, caches, nxt, lens,
                                                 active))
    (pre, caches, dec, _, counts), (pre1, _, dec1, _, counts1) = outs
    # the scanned decode step closes over the (periods, held, K, N) stacks
    step = functools.partial(T.decode_step, cfg, moe_counts=True)
    consts = scan_consts(step, params, caches, nxt, lens, active)
    assert consts.count((2, 8, D, F)) == 2 and (2, 8, F, D) in consts
    np.testing.assert_allclose(np.asarray(pre), np.asarray(pre1),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(dec1),
                               rtol=1e-6, atol=1e-6)
    assert [int(v) for v in counts] == [int(v) for v in counts1]
    assert int(counts[0]) == 2 * 2 * K


# -- through the engine ------------------------------------------------------

def _tiny_family():
    from bench.models.qwen3_moe import program, reference, weights
    from bench.tests.tiny_moe import tiny_moe_config
    return program, reference, weights, tiny_moe_config()


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_matches_the_reference(backend):
    """Prefill, then decode through the latent ring (one ReCalKV group of
    the 4 KV heads, G = 1) in the serving engine, for requests batched in
    its slots, in float32: every served token is within 1e-3 of the
    reference's best logit (sound runs read 0: every served token is the
    reference's argmax; the MoE part scaled by 0.9 reads above).  The Pallas run uses the overlapped pipeline (counters
    harvested from the window's status), the einsum run the synchronous
    one (counters read back with the window)."""
    from repro.serving import Engine
    program, reference, weights, cfg = _tiny_family()
    w = jax.tree.map(lambda a: a.astype(jnp.float32),
                     weights.init(cfg, 2 ** 31 + 5))
    mc = dataclasses.replace(program.model_config(cfg), attn_backend=backend,
                             dtype=jnp.float32)
    assert mc.recalkv.num_groups(mc.num_kv_heads) == 1
    eng = Engine(mc, program.params(cfg, w), max_slots=3, max_len=64,
                 sync_every=4,
                 **({"overlap": True, "pipeline_depth": 3}
                    if backend == "pallas" else {}))
    rng = np.random.default_rng(11)
    reqs = [program.request(i, rng.integers(0, cfg["vocab_size"], n)
                            .astype(np.int32), new, None)
            for i, (n, new) in enumerate([(9, 12), (17, 6), (5, 10),
                                          (12, 7)])]
    for r in reqs:
        eng.submit(r)
    eng.run()
    worst = 0.0
    for r in reqs:
        assert len(r.out_tokens) == r.max_new_tokens
        toks = np.concatenate([r.prompt, r.out_tokens[:-1]])
        rows = np.arange(len(r.prompt) - 1, len(toks))
        lg = np.asarray(reference.logits(cfg, w, toks, rows))
        gap = lg.max(axis=1) - lg[np.arange(len(rows)), r.out_tokens]
        worst = max(worst, float(gap.max()))
    assert worst < 1e-3
    # every decode step routed each live slot's token over all experts:
    # k pairs per layer
    m = eng.metrics()
    assert m["moe_decode_rows_routed"] == (
        eng._act_iters * cfg["num_experts_per_tok"]
        * cfg["num_hidden_layers"])
    assert 0 < m["moe_decode_rows"] <= m["moe_decode_rows_routed"]
    assert 0 < m["moe_decode_groups"] <= m["moe_decode_rows"]


def test_decode_step_counts_live_slots_only():
    """decode_step's MoE counters on a fixed batch count the live slot's
    k pairs per layer, and nothing for an idle slot."""
    program, _, weights, cfg = _tiny_family()
    w = weights.init(cfg, 3)
    mc = program.model_config(cfg)
    params = program.params(cfg, w)
    toks = jnp.asarray([[3, 9, 27, 81], [5, 25, 125, 7]], jnp.int32)
    _, caches = jax.jit(functools.partial(T.prefill, mc, max_len=16))(
        params, toks, jnp.asarray([4, 4]))
    step = jax.jit(functools.partial(T.decode_step, mc, moe_counts=True))
    n_layers, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    for active, live in (([True, False], 1), ([False, False], 0)):
        _, _, counts = step(params, caches, jnp.asarray([1, 2]),
                            jnp.asarray([4, 4]), jnp.asarray(active))
        assert int(counts[0]) == live * k * n_layers
        assert int(counts[2]) <= int(counts[1]) <= int(counts[0])
