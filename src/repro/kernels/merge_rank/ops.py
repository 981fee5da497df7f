"""jit'd wrapper: merge sorted runs by rank + scatter (1-D) / one-hot (2-D).

Invalid entries in either run must carry key (I32_MAX, I32_MAX); they sort
to the tail of the merged output naturally, so fixed-capacity tablets merge
without knowing their valid counts inside the kernel.
"""
import functools

import jax
import jax.numpy as jnp

from ..common import INTERPRET, I32_MAX, pad_to
from .kernel import pair_rank_pallas, row_rank_pallas


@functools.partial(jax.jit, static_argnames=("block_q", "block_t", "interpret"))
def merge_sorted(ar, ac, av, br, bc, bv, block_q: int = 256,
                 block_t: int = 2048, interpret: bool = INTERPRET):
    """Merge sorted runs A and B (each sorted lex by (r, c), pads = I32_MAX).

    Returns (r, c, v) of length len(A)+len(B); valid entries first in sorted
    order, A-side entries preceding B-side entries on equal keys (so a later
    dedup pass can implement last-wins for the newer B side).
    """
    n_a, n_b = ar.shape[0], br.shape[0]
    ar_p, _ = pad_to(ar.astype(jnp.int32), block_q, 0, I32_MAX)
    ac_p, _ = pad_to(ac.astype(jnp.int32), block_q, 0, I32_MAX)
    br_p, _ = pad_to(br.astype(jnp.int32), block_q, 0, I32_MAX)
    bc_p, _ = pad_to(bc.astype(jnp.int32), block_q, 0, I32_MAX)
    at_r, _ = pad_to(ar.astype(jnp.int32).reshape(1, -1), block_t, 1, I32_MAX)
    at_c, _ = pad_to(ac.astype(jnp.int32).reshape(1, -1), block_t, 1, I32_MAX)
    bt_r, _ = pad_to(br.astype(jnp.int32).reshape(1, -1), block_t, 1, I32_MAX)
    bt_c, _ = pad_to(bc.astype(jnp.int32).reshape(1, -1), block_t, 1, I32_MAX)

    rank_a = pair_rank_pallas(bt_r, bt_c, ar_p.reshape(-1, 1), ac_p.reshape(-1, 1),
                              strict=True, block_q=block_q, block_t=block_t,
                              interpret=interpret)[: n_a, 0]
    rank_b = pair_rank_pallas(at_r, at_c, br_p.reshape(-1, 1), bc_p.reshape(-1, 1),
                              strict=False, block_q=block_q, block_t=block_t,
                              interpret=interpret)[: n_b, 0]
    # rank counts include the other side's I32_MAX pads only for pad queries,
    # which always land at/after position len(valid A)+len(valid B).
    pos_a = jnp.minimum(jnp.arange(n_a, dtype=jnp.int32) + rank_a, n_a + n_b - 1)
    pos_b = jnp.minimum(jnp.arange(n_b, dtype=jnp.int32) + rank_b, n_a + n_b - 1)

    out_r = jnp.full((n_a + n_b,), I32_MAX, dtype=jnp.int32)
    out_c = jnp.full((n_a + n_b,), I32_MAX, dtype=jnp.int32)
    out_v = jnp.zeros((n_a + n_b,), dtype=av.dtype)
    # scatter pads first is unnecessary: pad positions are disjoint from
    # valid positions; among-pad collisions are harmless (pad over pad).
    out_r = out_r.at[pos_b].set(br.astype(jnp.int32)).at[pos_a].set(ar.astype(jnp.int32))
    out_c = out_c.at[pos_b].set(bc.astype(jnp.int32)).at[pos_a].set(ac.astype(jnp.int32))
    out_v = out_v.at[pos_b].set(bv).at[pos_a].set(av)
    # valid A entries can never share a slot with valid B entries; pads from
    # A (written last) may overwrite pads from B — both are I32_MAX, fine.
    return out_r, out_c, out_v


def kway_merge(runs, use_pallas: bool = True, interpret: bool = INTERPRET):
    """Merge k sorted runs into one by pairwise reduction (major compaction).

    ``runs`` is a list of (rows, cols, vals) triples sorted lex by (r, c)
    with I32_MAX key pads, ordered OLDEST FIRST. Each pairwise merge keeps
    the left (older) side first on equal keys, and the tree reduction only
    ever merges a prefix-contiguous older group with a newer one, so the
    merged output preserves global age order within every equal-key group.
    A single downstream dedup pass therefore implements every Accumulo
    combiner in ``db.iterators`` (last = newest wins, sum/min/max see all
    contributions exactly once).

    Without Pallas the runs are concatenated oldest first and put in
    order by ONE stable (row, col) sort: the same output as the pairwise
    tree, from one sort instead of k - 1 (the TPU compiler spends tens of
    seconds on each sort in a program).

    Returns (rows, cols, vals) of length sum(len(run)); valid entries first.
    """
    if not runs:
        raise ValueError("kway_merge needs at least one run")
    runs = list(runs)
    if not use_pallas:
        cat = [jnp.concatenate([run[i] for run in runs]) for i in range(3)]
        return tuple(jax.lax.sort(tuple(cat), num_keys=2, is_stable=True))
    while len(runs) > 1:
        nxt = [merge_sorted(*runs[i], *runs[i + 1], interpret=interpret)
               for i in range(0, len(runs) - 1, 2)]
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return runs[0]


def merge_combine_rows(keys, vals, use_pallas: bool = False,
                       block_q: int = 8, block_t: int = 128,
                       interpret: bool = INTERPRET):
    """Row-wise K-way merge-combine by rank + scatter (traced inline —
    callers jit). The batched read-path variant of ``merge_sorted``:

    ``keys`` int32 [Q, N] — each row is the CONCATENATION of K sorted
    candidate segments (one per run, (col, age)-packed by the fused query
    so valid keys are globally unique per row); pads carry I32_MAX.
    ``vals`` [Q, N] rides along. Returns (keys, vals) with every row in
    ascending key order, pads at the tail.

    Because valid keys are unique per row, an element's strict self-rank
    against its whole row IS its merged position — the K-way
    generalization of ``merge_sorted``'s rank-in-the-other-run scheme,
    collapsed to a single rank pass (no pairwise reduction tree). The
    permutation is applied as a ONE-HOT contraction rather than a
    scatter: XLA:CPU lowers 2-D scatters to a slow serialized loop
    (~1 ms for a [512, 20] tile) while the rank == position one-hot
    einsum vectorizes (~3.5x faster, same asymptotics as the rank pass
    itself). Pads all rank at n_valid and are masked out of the one-hot,
    so unfilled output slots take I32_MAX (keys) / 0 (vals). Cost is N^2
    branch-free compares per row vs the sort's N log N comparator ops —
    a win for the small candidate widths the fused read path produces
    (XLA:CPU comparator sorts are scalar and branchy; the compare tensor
    vectorizes).
    """
    n_q, n_w = keys.shape
    if use_pallas:
        qp, wp = -n_q % block_q, -n_w % block_t
        kp = jnp.pad(keys, ((0, qp), (0, wp)), constant_values=I32_MAX)
        rank = row_rank_pallas(kp, block_q=block_q, block_t=block_t,
                               interpret=interpret)[:n_q, :n_w]
    else:
        from .ref import row_rank_ref
        rank = row_rank_ref(keys)
    valid = keys != I32_MAX
    iota = jnp.arange(n_w, dtype=jnp.int32)
    onehot = ((rank[:, :, None] == iota[None, None, :])
              & valid[:, :, None])                       # [Q, src, dst]
    ohi = onehot.astype(jnp.int32)
    filled = jnp.sum(ohi, axis=1)                        # [Q, dst] in {0,1}
    out_k = (jnp.einsum("qj,qjp->qp", jnp.where(valid, keys, 0), ohi)
             + (1 - filled) * I32_MAX)
    out_v = jnp.einsum("qj,qjp->qp", jnp.where(valid, vals, 0),
                       onehot.astype(vals.dtype))
    return out_k, out_v
