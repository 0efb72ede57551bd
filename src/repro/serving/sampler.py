"""On-device token sampling for the serving engine.

``SamplingParams`` is the per-request policy (greedy / temperature /
top-k / top-p); ``sample_tokens`` is the batched, jit-friendly kernel the
executor's fused decode loop calls every step.  Every knob is a per-slot
*array* (not a Python value), so one trace serves any mix of requests —
a greedy slot and a top-p slot ride the same ``lax.scan`` iteration.

Determinism: each slot carries its own PRNG key (derived from
``SamplingParams.seed`` and the request uid), advanced once per *emitted*
token — a request's sampled stream is therefore reproducible run-to-run
and independent of its batch-mates or of scheduler stalls.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = jnp.float32(-1e30)
_TEMP_EPS = 1e-6
# Scaled logits are clipped to +-_SCALED_MAX before filtering: a tiny
# temperature divides logits toward float32 infinity, and one inf turns
# the top-p softmax (and then the whole filtered row) into NaN.  The
# bound sits well inside float32 range but above any real logit scale,
# and NEG_INF masking stays strictly below it, so ordering — hence the
# sampled stream — is unchanged for sane inputs.
_SCALED_MAX = jnp.float32(1e29)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding policy.

    temperature  0.0 -> greedy argmax (exact parity with the seed engine);
                 otherwise logits are scaled by 1/temperature.
    top_k        keep only the k highest logits (0 -> disabled).
    top_p        keep the minimal nucleus whose probability mass reaches
                 top_p, computed on the temperature-scaled distribution
                 after top-k (1.0 -> disabled).
    seed         folded with the request uid into the slot's PRNG key.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError("temperature must be >= 0")
        if self.top_k < 0:
            raise ValueError("top_k must be >= 0 (0 disables)")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")

    def slot_key(self, uid: int) -> np.ndarray:
        """The (2,) uint32 PRNG key a slot starts from for this request,
        derived on the host: the Threefry-2x32 hash of ``(0, uid)`` under
        the key ``(0, seed)`` (both taken mod 2**32) — what JAX's
        ``fold_in(PRNGKey(seed), uid)`` gives under its default config.
        A device-side derivation would read its key back behind every
        decode window in flight (about 2 s an admission on a TPU v5e at
        a pipeline depth of 3)."""
        return np.array(_threefry2x32((0, self.seed & _U32), (0, uid & _U32)),
                        np.uint32)


_U32 = 0xFFFFFFFF


def _threefry2x32(key, x):
    """The Threefry-2x32 hash of one count pair under ``key``, on host
    ints: JAX's own (``jax._src.prng._threefry2x32_lowering``), five
    groups of four rounds with a key injection after each."""
    ks = (key[0], key[1], key[0] ^ key[1] ^ 0x1BD11BDA)
    x0, x1 = (x[0] + ks[0]) & _U32, (x[1] + ks[1]) & _U32
    for i in range(5):
        for r in ((13, 15, 26, 6), (17, 29, 16, 24))[i % 2]:
            x0 = (x0 + x1) & _U32
            x1 = (((x1 << r) | (x1 >> (32 - r))) & _U32) ^ x0
        x0 = (x0 + ks[(i + 1) % 3]) & _U32
        x1 = (x1 + ks[(i + 2) % 3] + i + 1) & _U32
    return x0, x1


GREEDY = SamplingParams()


def filtered_logits(logits: jax.Array, top_k: jax.Array,
                    top_p: jax.Array) -> jax.Array:
    """Apply per-row top-k then minimal-nucleus top-p masking.

    logits (B, V) float32; top_k (B,) int32 (0 disables); top_p (B,)
    float32 (>= 1 disables).  Top-k keeps *exactly* k entries (ties broken
    by argsort order); top-p keeps the smallest prefix of the sorted
    distribution whose cumulative probability reaches top_p (the entry
    that crosses the threshold is kept; the top-1 always survives).
    """
    V = logits.shape[-1]
    order = jnp.argsort(-logits, axis=-1)            # descending indices
    ranks = jnp.argsort(order, axis=-1)              # rank of each entry
    k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)
    keep_k = ranks < k[:, None]
    masked = jnp.where(keep_k, logits, NEG_INF)

    sorted_l = jnp.take_along_axis(masked, order, axis=-1)
    probs = jax.nn.softmax(sorted_l, axis=-1)
    before = jnp.cumsum(probs, axis=-1) - probs      # mass strictly above
    keep_sorted = before < top_p[:, None]
    keep_sorted = keep_sorted.at[:, 0].set(True)
    keep_p = jnp.take_along_axis(keep_sorted, ranks, axis=-1)
    return jnp.where(keep_k & keep_p, logits, NEG_INF)


def sample_tokens(logits: jax.Array, temperature: jax.Array,
                  top_k: jax.Array, top_p: jax.Array,
                  keys: jax.Array, *, spec=None) -> jax.Array:
    """One sampled token per row.  logits (B, V); knobs (B,) arrays;
    keys (B, 2) uint32 per-slot PRNG keys (use-once — the caller carries
    the split).  Rows with temperature below the ``_TEMP_EPS`` floor
    (including 0) return exact argmax — a sub-floor temperature is
    already a collapsed distribution, and scaling by its reciprocal
    would overflow float32; an all-greedy batch skips the sort-based
    filtering entirely (lax.cond), so a greedy serving engine pays
    nothing for the sampling machinery.

    ``spec`` (optional NamedSharding for the (B, V) logits: slot axis
    sharded, vocab replicated) pins the sampler's working set under a
    mesh.  Logits arrive vocab-sharded from the tensor-parallel lm_head;
    every sampling op (argsort/cumsum over V, the per-row categorical
    draw) is row-local, so one explicit reshard up front makes the whole
    filter+draw local to the slot shard instead of letting SPMD re-derive
    (and possibly re-gather) per op."""
    logits = logits.astype(jnp.float32)
    if spec is not None:
        logits = jax.lax.with_sharding_constraint(logits, spec)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # Temperatures below the clamp floor are semantically greedy (the
    # distribution has collapsed onto argmax) — route them to the exact
    # argmax branch instead of scaling logits by up to 1/_TEMP_EPS, which
    # could overflow float32 and NaN the whole filtered row.
    is_greedy = temperature < _TEMP_EPS

    def sampled(_):
        t = jnp.maximum(temperature, _TEMP_EPS)[:, None]
        scaled = jnp.clip(logits / t, -_SCALED_MAX, _SCALED_MAX)
        masked = filtered_logits(scaled, top_k, top_p)
        s = jax.vmap(jax.random.categorical)(keys, masked).astype(jnp.int32)
        return jnp.where(is_greedy, greedy, s)

    return jax.lax.cond(jnp.all(is_greedy), lambda _: greedy, sampled, None)


def split_keys(keys: jax.Array) -> jax.Array:
    """Advance a batch of per-slot PRNG keys one step: (B, 2) uint32 ->
    (B, 2, 2) where [:, 0] is the draw key for this step and [:, 1] the
    chain carried forward.  One helper so the decode window, the
    speculative window and the admission path derive keys identically —
    the per-request stream depends only on how many tokens that slot has
    *emitted*, which is what makes overlapped/staged admission
    token-for-token equal to the sync engine.  Safe to call from the
    admission worker thread: pure jax dispatch, no host state."""
    return jax.vmap(lambda k: jax.random.split(k, 2))(keys)


# Jitted admission-time sampler.  Admission used to call sample_tokens
# eagerly (op-by-op dispatch on the wave's first logits); both the sync
# and the overlapped engine now share this one jitted entry point so the
# first token of a request is bitwise identical whichever path admitted
# it — sample_tokens is batch-invariant per row, so bucket padding rows
# cannot perturb real rows.  ``spec`` stays static (it is a hashable
# NamedSharding or None, not an array).
sample_tokens_jit = jax.jit(sample_tokens, static_argnames=("spec",))
