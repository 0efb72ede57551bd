"""Pallas TPU kernel: grouped matmul over the experts a device holds.

Rows arrive sorted by expert together with each expert's group size; row r
of group g is multiplied by ``rhs[g]``.  The grid is (column tiles, work
items), one work item per (group, row tile) the group touches, listed group
by group.  A weight block's index only changes when the group does, and
Pallas issues no copy for a block whose index repeats, so each non-empty
group's weights are read once per call however many row tiles it spans.
Empty groups get no work item and rows past the last group no row tile;
the item count is a traced grid bound, so one compile serves every
routing.  The contraction is whole in one block (no k loop): decode rows
are few, and the weight read is the work.

The weights may be one layer's ``(G, K, N)`` or a scanned stack's whole
``(L, G, K, N)`` with a traced ``layer``, which is scalar-prefetched and
picks the blocks at ``(layer, group, 0, j)``: a layer scan then hands the
kernel its loop-invariant stack, and no slice of it is copied out before
the call (a custom call cannot read a slice of a larger buffer).  One
layer is the stack ``rhs[None]`` at layer 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes of one weight block (K x tn): two of them are in flight at once.
WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024


def _col_tile(k: int, n: int, itemsize: int) -> int:
    """Widest multiple of 128 that divides ``n`` and keeps a (k, tn) weight
    block within WEIGHT_BLOCK_BYTES; ``n`` itself when none does."""
    best = n
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn * itemsize <= WEIGHT_BLOCK_BYTES:
            best = tn
    return best


def work_items(group_sizes: jax.Array, m_tiles: int, tm: int):
    """(starts, ends, item_group, item_tile, n_items): each group's row
    range, and for every work item its group and row tile.  Arrays are
    sized for the most items any routing can need (m_tiles + groups - 1);
    only the first ``n_items`` are run."""
    g = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    cap = m_tiles + g - 1
    item_group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), tiles,
                            total_repeat_length=cap)
    offset = jnp.arange(cap, dtype=jnp.int32) - (jnp.cumsum(tiles)
                                                 - tiles)[item_group]
    item_tile = jnp.clip(first[item_group] + offset, 0, m_tiles - 1)
    return (starts.astype(jnp.int32), ends.astype(jnp.int32), item_group,
            item_tile.astype(jnp.int32), tiles.sum().astype(jnp.int32))


def _kernel(layer_ref, starts_ref, ends_ref, group_ref, tile_ref, x_ref,
            w_ref, o_ref, *, tm):
    i = pl.program_id(1)
    g = group_ref[i]
    row = tile_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                      o_ref.shape, 0)
    mine = (row >= starts_ref[g]) & (row < ends_ref[g])
    acc = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    # a row tile shared by two groups is visited once by each: keep the
    # rows the other group wrote
    o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def expert_gmm(lhs, rhs, group_sizes, *, layer=None, block_m: int = 128,
               interpret: bool = False):
    """lhs (m, K) rows sorted by group; rhs (G, K, N), or (L, G, K, N)
    with ``layer`` a traced int32 scalar; group_sizes (G,) int32 with sum
    <= m.  Returns (m, N) in lhs's dtype (float32 accumulation): row r of
    group g is lhs[r] @ rhs[g] (rhs[layer, g]); rows past the last group
    are zero."""
    if layer is None:
        rhs, layer = rhs[None], 0
    m, k = lhs.shape
    n = rhs.shape[3]
    tm = min(block_m, -(-m // 16) * 16)
    mp = -(-m // tm) * tm
    if mp != m:
        lhs = jnp.pad(lhs, ((0, mp - m), (0, 0)))
    m_tiles = mp // tm
    tn = _col_tile(k, n, rhs.dtype.itemsize)
    starts, ends, item_group, item_tile, n_items = work_items(
        group_sizes.astype(jnp.int32), m_tiles, tm)
    # at least one item, whose rows mask to nothing when no group has rows
    n_items = jnp.maximum(n_items, 1)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, n_items),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, l, s, e, g, t: (t[i], 0)),
                pl.BlockSpec((None, None, k, tn),
                             lambda j, i, l, s, e, g, t: (l[0], g[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, l, s, e, g, t: (t[i], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="expert_gmm",
    )(layer, starts, ends, item_group, item_tile, lhs, rhs)
    rows = jax.lax.broadcasted_iota(jnp.int32, (mp, 1), 0)
    out = jnp.where(rows < ends[-1], out, 0)
    return out[:m] if mp != m else out
