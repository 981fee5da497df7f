"""Vectorized rank search over a sorted table — the query hot path.

TPU adaptation of Accumulo's per-query binary search (paper §IV-B): branchy
log(N) probing is a CPU idiom; on TPU we compute
``lower_bound(q) = sum_tiles count(tile_elements < q)`` with VMEM-tiled
branch-free vector compares, embarrassingly parallel over queries and tiles.
Grid = (query_blocks, table_tiles); the table tile axis is the innermost
(sequential) grid dimension so the output block accumulates in place.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rank_kernel(q_ref, tab_ref, o_ref, *, strict: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...]        # (bq, 1) int32
    t = tab_ref[...]      # (1, bt) int32
    cmp = (t < q) if strict else (t <= q)
    o_ref[...] += jnp.sum(cmp.astype(jnp.int32), axis=1, keepdims=True)


def rank_pallas(tab: jax.Array, q: jax.Array, *, strict: bool,
                block_q: int = 256, block_t: int = 2048,
                interpret: bool = True) -> jax.Array:
    """Ranks of ``q`` in sorted ``tab``. Inputs already padded to blocks.

    tab: (1, N) int32 sorted, padded with I32_MAX.
    q:   (Q, 1) int32.
    """
    n_q, n_t = q.shape[0], tab.shape[1]
    grid = (n_q // block_q, n_t // block_t)
    return pl.pallas_call(
        functools.partial(_rank_kernel, strict=strict),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_t), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_q, 1), jnp.int32),
        interpret=interpret,
    )(q, tab)


def _rank_batched_kernel(q_ref, tab_ref, o_ref, *, strict: bool):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...]        # (bq, 1) int32 — shared across runs
    t = tab_ref[0]        # (1, bt) int32 — one tile of run k
    cmp = (t < q) if strict else (t <= q)
    o_ref[0] += jnp.sum(cmp.astype(jnp.int32), axis=1, keepdims=True)


def rank_pallas_batched(tabs: jax.Array, q: jax.Array, *, strict: bool,
                        block_q: int = 256, block_t: int = 2048,
                        interpret: bool = True) -> jax.Array:
    """Ranks of ``q`` in EACH of K stacked sorted runs — the fused LSM read
    path searches every resident run of a shard in one launch instead of K.

    Grid = (runs, query_blocks, table_tiles); the table tile axis stays the
    innermost (sequential) dimension so each (run, query-block) output block
    accumulates in place, exactly like the single-run kernel. The run axis
    is a leading array dimension of its own, so every block's last two
    dims are the single-run kernel's ((1, block_t) table tile, (block_q, 1)
    ranks) and meet the TPU (8, 128) tiling rule.

    tabs: (K, 1, N) int32, each run sorted, padded with I32_MAX.
    q:    (Q, 1) int32.
    Returns (K, Q, 1) int32 ranks.
    """
    n_k, _, n_t = tabs.shape
    n_q = q.shape[0]
    grid = (n_k, n_q // block_q, n_t // block_t)
    return pl.pallas_call(
        functools.partial(_rank_batched_kernel, strict=strict),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda k, i, j: (i, 0)),
            pl.BlockSpec((1, 1, block_t), lambda k, i, j: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1), lambda k, i, j: (k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_k, n_q, 1), jnp.int32),
        interpret=interpret,
    )(q, tabs)
