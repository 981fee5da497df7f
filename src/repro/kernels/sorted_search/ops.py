"""jit'd public wrapper for the sorted_search kernel."""
import functools

import jax
import jax.numpy as jnp

from ..common import INTERPRET, I32_MAX, pad_to
from .kernel import rank_pallas, rank_pallas_batched


@functools.partial(jax.jit, static_argnames=("side", "block_q", "block_t", "interpret"))
def sorted_search(tab: jax.Array, q: jax.Array, side: str = "left",
                  block_q: int = 256, block_t: int = 2048,
                  interpret: bool = INTERPRET) -> jax.Array:
    """Vectorized searchsorted: positions of ``q`` in sorted 1-D ``tab``.

    ``tab`` must be padded with I32_MAX beyond its valid prefix (the pad
    never counts: every real query is < I32_MAX).
    """
    q2, n_q = pad_to(q.astype(jnp.int32).reshape(-1, 1), block_q, 0, 0)
    tab2, _ = pad_to(tab.astype(jnp.int32).reshape(1, -1), block_t, 1, I32_MAX)
    out = rank_pallas(tab2, q2, strict=(side == "left"),
                      block_q=block_q, block_t=block_t, interpret=interpret)
    return out[:n_q, 0]


@functools.partial(jax.jit, static_argnames=("side", "block_q", "block_t", "interpret"))
def sorted_search_batched(tabs: jax.Array, q: jax.Array, side: str = "left",
                          block_q: int = 256, block_t: int = 2048,
                          interpret: bool = INTERPRET) -> jax.Array:
    """Batched searchsorted: ranks of ``q`` in each row of ``tabs[K, N]``.

    Every row must be sorted and padded with I32_MAX past its valid prefix.
    One kernel launch covers all K runs — the fused LSM read path's rank
    search. Returns int32[K, Q].
    """
    q2, n_q = pad_to(q.astype(jnp.int32).reshape(-1, 1), block_q, 0, 0)
    tabs2, _ = pad_to(tabs.astype(jnp.int32)[:, None, :], block_t, 2, I32_MAX)
    out = rank_pallas_batched(tabs2, q2, strict=(side == "left"),
                              block_q=block_q, block_t=block_t,
                              interpret=interpret)
    return out[:, :n_q, 0]


@functools.partial(jax.jit, static_argnames=("block_q", "block_t",
                                             "interpret"))
def sorted_search_endpoints(tabs: jax.Array, lohi: jax.Array,
                            block_q: int = 256, block_t: int = 2048,
                            interpret: bool = INTERPRET):
    """Fence-to-fence endpoint ranks for a ``[lo, hi)`` range scan: the
    ``side='left'`` ranks of both endpoints in each row of ``tabs[K, N]``,
    in ONE kernel launch (``lohi`` is the length-2 [lo, hi] vector; ``hi``
    is exclusive, so both endpoints rank strictly). Returns
    (start[K], end[K]) int32 — the candidate window of each run.
    """
    out = sorted_search_batched(tabs, lohi, "left", block_q=block_q,
                                block_t=block_t, interpret=interpret)
    return out[:, 0], out[:, 1]
