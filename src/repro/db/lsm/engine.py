"""Leveled LSM run structure — the multi-run tablet server storage engine.

Replaces the single-sorted-run tablet with Accumulo's actual layout:

  memtable (unsorted, in ``ShardedTable``)
     │ minor compaction: sort + dedup, O(m log m) — NOT O(table capacity)
     ▼
  L0: up to ``l0_slots`` independent sorted runs of memtable size
     │ major compaction when L0 fills: k-way merge via the Pallas
     │ ``merge_rank`` kernel (``kernels.merge_rank.kway_merge``)
     ▼
  L1..Ld: one geometrically larger sorted run per level (static
          capacities, so every device op is jit-compatible)

Each run carries a packed-uint32 bloom filter over its row ids (sized per
level — deep levels absorb most negative lookups) and fence pointers
(block-start row ids). Combiner semantics (``db.iterators``) hold across
any flush/compaction schedule because every merge preserves age order
within equal-key groups and every dedup applies the same combiner.

Two read paths serve point queries (neither ever flushes):

* **fused** (default, ``query_shard_fused``): the entire shard — every
  leveled run, the whole L0 stack, and the memtable tail — is searched by
  ONE jitted dispatch per query TILE. Runs keep their static stacked
  shapes (levels are distinct-capacity buckets, L0 is already a [K0, m]
  batch; empty slots are inert I32_MAX padding, so no re-bucketing is
  ever needed), each run's fence-bracketed rank search is block
  bloom-gated (``lax.cond`` — a tile that misses a run's filter skips its
  probe entirely), and the cross-run age-ordered combine happens
  on-device via the batched ``merge_rank`` rank+scatter merge. Batches
  larger than the tile split into fixed-size blocks that reuse ONE jit
  cache entry: ceil(Q/tile) dispatches, never a per-run fallback.
* **per-run** (``query_shard``): one bloom-gated kernel launch per
  resident run, combined on the host. Kept as the A/B baseline
  (``fused_reads=False``) and for the stale-mirror recovery corners.

All state is stacked [S, ...] across shards; flushes and compactions are
vmapped so the S simulated tablet servers advance in lockstep (one hot
shard compacts its peers early — harmless, entries just move down a level).
"""
from __future__ import annotations

import functools
from time import perf_counter
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels.common import I32_MAX, INTERPRET
from ...obs import default_registry, default_tracer
from ...kernels.merge_rank import kway_merge, merge_combine_rows
from ...kernels.sorted_search import (sorted_search_batched,
                                      sorted_search_endpoints)
from .bloom import (BITS_PER_KEY, MAX_HASHES, NUM_HASHES, bloom_build,
                    bloom_maybe_contains, bloom_maybe_contains_batch,
                    fence_build, num_words, theoretical_fp_rate)


def fence_block(cap: int) -> int:
    """Fence block size: small enough to bracket, large enough to amortize."""
    if cap < 32:
        return max(1, cap // 2)
    return max(16, min(1024, cap // 16))


def plan_levels(capacity_per_shard: int, mem_cap: int, l0_slots: int,
                fanout: int) -> List[int]:
    """Static per-level run capacities L1..Ld (geometric; deepest holds
    everything the structure can legally contain)."""
    need = l0_slots * mem_cap  # max entries a full L0 pushes down
    caps: List[int] = []
    c = need  # L1 absorbs exactly one L0's worth -> cheap frequent merges
    while c < capacity_per_shard:
        caps.append(c)
        c *= fanout
    caps.append(max(capacity_per_shard, need + sum(caps)))
    return caps


def _per_level(spec: Union[int, Sequence[int]], n_levels: int) -> Tuple[int, ...]:
    """Expand a scalar-or-sequence sizing spec to one value per level.

    A sequence shorter than the level count repeats its last entry for the
    deeper levels (so ``(8, 12, 16)`` means: L1 8 bits, L2 12, L3+ 16)."""
    if isinstance(spec, (int, np.integer)):
        return (int(spec),) * n_levels
    spec = tuple(int(x) for x in spec)
    if not spec:
        raise ValueError("empty bloom sizing spec")
    return tuple(spec[min(i, len(spec) - 1)] for i in range(n_levels))


def _bucket(n: int, lo: int = 8) -> int:
    """Next pow2 >= max(n, lo): static jit shapes for ragged host inputs."""
    return 1 << (max(n, lo) - 1).bit_length()


# ---------------------------------------------------------------- device ops
def _sort_dedup(r, c, v, combiner: str):
    """Sort one buffer lex by (row, col) (stable → age order kept), apply
    the combiner, compact valid entries to the front. Returns (r, c, v, n)."""
    from ..kvstore import _dedup_combine  # shared with the legacy engine

    cap = r.shape[0]
    sr, sc, sv = jax.lax.sort((r, c, v), num_keys=2, is_stable=True)
    keep, out_v = _dedup_combine(sr, sc, sv, combiner)
    pos = jnp.cumsum(keep) - 1
    idx = jnp.where(keep, pos, cap)
    return (
        jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(sr, mode="drop"),
        jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(sc, mode="drop"),
        jnp.zeros((cap,), jnp.float32).at[idx].set(out_v, mode="drop"),
        keep.sum().astype(jnp.int32),
    )


@functools.lru_cache(maxsize=None)
def _flush_fn(combiner: str, n_words: int, block: int, n_hashes: int):
    """jit(vmap): memtable [S, m] -> one sorted+deduped L0 run per shard,
    with bloom + fence metadata. Cost O(m log m) per shard."""

    def one(r, c, v):
        rr, cc, vv, n = _sort_dedup(r, c, v, combiner)
        return (rr, cc, vv, n, bloom_build(rr, n_words, n_hashes),
                fence_build(rr, block), rr[0], rr[jnp.maximum(n - 1, 0)])

    return jax.jit(jax.vmap(one))


@functools.lru_cache(maxsize=None)
def _bloom_rebuild_fn(n_words: int, n_hashes: int, nested: bool):
    """jit: rebuild blooms for stacked runs on snapshot load — cached at
    module level so repeated ``recover()`` calls (crash-fuzz loops, test
    suites) reuse the compiled graph instead of re-tracing per call."""
    one = functools.partial(bloom_build, n_words=n_words, n_hashes=n_hashes)
    f = jax.vmap(jax.vmap(one)) if nested else jax.vmap(one)
    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def _write_slot_fn():
    """Write each shard's flushed run into ITS next free L0 slot
    (``slot`` is a traced [S] vector — shards fill independently). A shard
    whose slot index equals K0 (full L0, nothing incoming) drops the
    write."""

    def write(l0_r, l0_c, l0_v, l0_b, l0_f, rr, cc, vv, bb, ff, slot):
        s = jnp.arange(l0_r.shape[0])
        return (l0_r.at[s, slot].set(rr, mode="drop"),
                l0_c.at[s, slot].set(cc, mode="drop"),
                l0_v.at[s, slot].set(vv, mode="drop"),
                l0_b.at[s, slot].set(bb, mode="drop"),
                l0_f.at[s, slot].set(ff, mode="drop"))

    return jax.jit(write)


@functools.lru_cache(maxsize=None)
def _compact_fn(combiner: str, use_pallas: bool, out_cap: int, n_words: int,
                block: int, n_hashes: int):
    """jit(vmap): k-way merge L0 runs + levels 1..d into level d.

    Inputs per shard: l0 [K0, m] plus a tuple of level runs ordered
    DEEPEST FIRST (deepest = oldest). kway_merge keeps age order within
    equal-key groups, so one dedup pass applies the combiner exactly.
    """

    def one(l0_r, l0_c, l0_v, lvls):
        runs = [lv for lv in lvls]
        runs += [(l0_r[k], l0_c[k], l0_v[k]) for k in range(l0_r.shape[0])]
        mr, mc, mv = kway_merge(runs, use_pallas=use_pallas,
                                interpret=INTERPRET)
        from ..kvstore import _dedup_combine
        keep, out_v = _dedup_combine(mr, mc, mv, combiner)
        pos = jnp.cumsum(keep) - 1
        idx = jnp.where(keep, pos, out_cap)
        rr = jnp.full((out_cap,), I32_MAX, jnp.int32).at[idx].set(mr, mode="drop")
        cc = jnp.full((out_cap,), I32_MAX, jnp.int32).at[idx].set(mc, mode="drop")
        vv = jnp.zeros((out_cap,), jnp.float32).at[idx].set(out_v, mode="drop")
        n = keep.sum().astype(jnp.int32)
        return (rr, cc, vv, n, bloom_build(rr, n_words, n_hashes),
                fence_build(rr, block), rr[0], rr[jnp.maximum(n - 1, 0)])

    return jax.jit(jax.vmap(one, in_axes=(0, 0, 0, 0)))


@functools.partial(jax.jit, static_argnames=("max_return", "block"))
def run_query_rows(rows, cols, vals, fence, q, max_return: int, block: int):
    """Fence-bracketed point row query against one sorted run.

    The fence array (block-start row ids) locates the block holding each
    query's start/end rank; the exact rank search then touches only that
    block (+1 entry of spill) — the in-memory analogue of reading a single
    index-addressed RFile block. Returns (cols[Q, max_return],
    vals[Q, max_return], ok[Q, max_return], counts[Q]).
    """
    cap = rows.shape[0]
    w = block + 1

    def bracketed(qi, side):
        fi = jnp.searchsorted(fence, qi, side=side)
        base = jnp.clip(jnp.maximum(fi - 1, 0) * block, 0, cap - w)
        win = jax.lax.dynamic_slice(rows, (base,), (w,))
        return (base + jnp.searchsorted(win, qi, side=side)).astype(jnp.int32)

    start = jax.vmap(lambda qi: bracketed(qi, "left"))(q)
    end = jax.vmap(lambda qi: bracketed(qi, "right"))(q)
    idx = start[:, None] + jnp.arange(max_return, dtype=jnp.int32)[None, :]
    ok = idx < end[:, None]
    idxc = jnp.clip(idx, 0, cap - 1)
    return cols[idxc], vals[idxc], ok, end - start


@functools.partial(jax.jit, static_argnames=("max_return", "block", "n_hashes"))
def run_query_gated(rows, cols, vals, fence, bloom, q, max_return: int,
                    block: int, n_hashes: int = NUM_HASHES):
    """Bloom-gated run query in ONE dispatch: probe the bloom filter and,
    only when some queried row may be present (lax.cond — the search branch
    is genuinely skipped otherwise), run the fence-bracketed rank search.
    Returns (any_hit, cols, vals, ok, counts). The per-run baseline path
    launches these for every run back-to-back and syncs once; the fused
    path replaces the N launches with one."""
    any_hit = jnp.any(bloom_maybe_contains(bloom, q, n_hashes))

    def probe(_):
        return run_query_rows(rows, cols, vals, fence, q, max_return, block)

    def skip(_):
        nq = q.shape[0]
        return (jnp.zeros((nq, max_return), jnp.int32),
                jnp.zeros((nq, max_return), jnp.float32),
                jnp.zeros((nq, max_return), jnp.bool_),
                jnp.zeros((nq,), jnp.int32))

    return (any_hit,) + jax.lax.cond(any_hit, probe, skip, None)


# ----------------------------------------------------------- fused read path
def _probe_stack(rows, cols, vals, fences, q, max_return: int, block: int,
                 use_pallas: bool):
    """Fence-bracketed rank search of ``q`` against K stacked runs, traced
    inline (callers jit). rows/cols/vals [K, cap], fences [K, nb], q [Q].
    Returns (cols[K, Q, R], vals[K, Q, R], ok[K, Q, R], counts[K, Q]).

    Under ``use_pallas`` the fence rank search runs through the batched
    Pallas ``sorted_search`` kernel (one launch for all K fence arrays).
    The run axis is unrolled (K is static and small): vmapping it turns
    the per-query ``dynamic_slice`` window reads into a generic gather,
    which XLA:CPU lowers ~16x slower — the unrolled form keeps the same
    single dispatch with the fast slice lowering.
    """
    n_k, cap = rows.shape
    w = block + 1
    if use_pallas:
        fl = sorted_search_batched(fences, q, "left", interpret=INTERPRET)
        fr = sorted_search_batched(fences, q, "right", interpret=INTERPRET)
    else:
        fl = jnp.stack([jnp.searchsorted(fences[k], q, side="left")
                        .astype(jnp.int32) for k in range(n_k)])
        fr = jnp.stack([jnp.searchsorted(fences[k], q, side="right")
                        .astype(jnp.int32) for k in range(n_k)])
    iota = jnp.arange(max_return, dtype=jnp.int32)
    c_o, v_o, ok_o, cnt_o = [], [], [], []
    for k in range(n_k):
        rws = rows[k]

        def bracket(qi, fi, side):
            base = jnp.clip(jnp.maximum(fi - 1, 0) * block, 0, cap - w)
            win = jax.lax.dynamic_slice(rws, (base,), (w,))
            return (base + jnp.searchsorted(win, qi, side=side)
                    ).astype(jnp.int32)

        start = jax.vmap(lambda qi, fi: bracket(qi, fi, "left"))(q, fl[k])
        end = jax.vmap(lambda qi, fi: bracket(qi, fi, "right"))(q, fr[k])
        idx = start[:, None] + iota[None, :]
        idxc = jnp.clip(idx, 0, cap - 1)
        c_o.append(cols[k][idxc])
        v_o.append(vals[k][idxc])
        ok_o.append(idx < end[:, None])
        cnt_o.append(end - start)
    return (jnp.stack(c_o), jnp.stack(v_o), jnp.stack(ok_o),
            jnp.stack(cnt_o))


@functools.lru_cache(maxsize=None)
def _fused_query_fn(combiner: str, level_blocks: Tuple[int, ...],
                    level_hashes: Tuple[int, ...], b0: int, h0: int,
                    max_return: int, mem_mode: str, pack: bool,
                    use_pallas: bool, has_filter: bool = False):
    """Build THE single-dispatch query: the resident leveled runs (deepest
    first), the used L0 slots, and (optionally) the memtable tail of one
    shard are searched and cross-run combined inside one ``jax.jit``.

    Static key = resident geometry (per-level fence blocks + bloom hash
    counts) x (max_return, mem_mode, pack, use_pallas); array shapes
    (level caps, used slots, memtable bucket, query bucket) retrace under
    the same jit. Age order: levels deepest→shallowest get ages 1..L
    (oldest data lives deepest), L0 slots L+1..L+K0 (slot k was flushed
    before slot k+1), the memtable L+K0+1 (newest). ``mem_mode``:
    ``"sorted"`` = the host pre-sorted/deduped the mirror (cheap, cached
    between inserts); ``"raw"`` = unsorted device slices, sort in-dispatch
    (the stale-mirror SPMD path); ``"none"`` = empty.

    The on-device combine orders each query's candidates by (col, age)
    and reduces equal-col groups with the combiner — exactly
    ``combine_triples`` semantics, no host work. Under ``pack`` the
    (col, age) key pair packs into ONE int32 (valid when
    id_capacity * age_padding < 2**30) and the packed keys — unique per
    query row — are merged by the batched ``merge_rank`` rank+scatter
    merge (``merge_combine_rows``: strict self-rank IS the merged
    position; Pallas ``row_rank`` kernel under ``use_pallas``) as long as
    the candidate width stays within its quadratic-compare budget; wider
    retries and unpackable geometry fall back to ``lax.sort``.

    Every run's probe is BLOCK bloom-gated: the whole query block's hit
    mask feeds a ``lax.cond``, so a block that misses a run's filter
    entirely skips that run's fence search and window gathers — with
    query tiling, a tile whose key range lands outside a run costs only
    the bloom probes.

    With ``has_filter`` the dispatch takes an extra sorted int32 column
    id set (padded with I32_MAX) and drops candidates outside it ON
    DEVICE (sorted-membership via ``searchsorted``) before the combine —
    the residual ``isin(cols)`` of a row-driven read never reaches the
    host.

    Returns (cols[Q, W], vals[Q, W], keep[Q, W], cnt_max, hits[L+K0])
    with W = n_runs * max_return; ``cnt_max`` > max_return signals the
    host to re-dispatch wider (batch-scanner semantics), and ``hits``
    reports per-run bloom verdicts for observability.
    """
    from ..kvstore import _dedup_combine

    n_levels = len(level_blocks)

    def fused(q, levels, l0, mem, filt=None):
        seg_cols, seg_vals, seg_ok, seg_age, cnts, hits = [], [], [], [], [], []
        n_q = q.shape[0]
        iota = jnp.arange(max_return, dtype=jnp.int32)

        def skip(_):
            return (jnp.zeros((n_q, max_return), jnp.int32),
                    jnp.zeros((n_q, max_return), jnp.float32),
                    jnp.zeros((n_q, max_return), jnp.bool_),
                    jnp.zeros((n_q,), jnp.int32))

        # leveled runs, deepest (oldest) first — ages 1..L
        for i, (rows, cols, vals, fence, bloom) in enumerate(levels):
            hit = bloom_maybe_contains(bloom, q, level_hashes[i])
            any_hit = jnp.any(hit)

            def probe(_, rows=rows, cols=cols, vals=vals, fence=fence,
                      blk=level_blocks[i]):
                c_o, v_o, ok, cnt = _probe_stack(
                    rows[None], cols[None], vals[None], fence[None], q,
                    max_return, blk, use_pallas)
                return c_o[0], v_o[0], ok[0], cnt[0]

            c_o, v_o, ok, cnt = jax.lax.cond(any_hit, probe, skip, None)
            seg_cols.append(c_o)
            seg_vals.append(v_o)
            seg_ok.append(ok & hit[:, None])
            seg_age.append(i + 1)
            cnts.append(cnt)
            hits.append(any_hit)
        # the used L0 slots — ages L+1..L+K0 (a slot empty for THIS shard
        # while used by a peer is inert I32_MAX padding); gated per slot,
        # same cond pattern
        l0_rows, l0_cols, l0_vals, l0_fence, l0_bloom = l0
        k0 = l0_rows.shape[0]
        if k0:
            l0_hit = bloom_maybe_contains_batch(l0_bloom, q, h0)  # [K0, Q]
            for k in range(k0):
                any_k = jnp.any(l0_hit[k])

                def probe_k(_, k=k):
                    c_o, v_o, ok, cnt = _probe_stack(
                        l0_rows[k][None], l0_cols[k][None], l0_vals[k][None],
                        l0_fence[k][None], q, max_return, b0, use_pallas)
                    return c_o[0], v_o[0], ok[0], cnt[0]

                c_o, v_o, ok, cnt = jax.lax.cond(any_k, probe_k, skip, None)
                seg_cols.append(c_o)
                seg_vals.append(v_o)
                seg_ok.append(ok & l0_hit[k][:, None])
                seg_age.append(n_levels + 1 + k)
                cnts.append(cnt)
                hits.append(any_k)
        # the memtable tail (newest): one pre-combined sorted pseudo-run
        # (intra-memtable combine commutes with the cross-run combine —
        # flush relies on the same property)
        if mem_mode != "none":
            mem_r, mem_c, mem_v = mem
            if mem_mode == "raw":
                mem_r, mem_c, mem_v, _ = _sort_dedup(mem_r, mem_c, mem_v,
                                                     combiner)
            start = jnp.searchsorted(mem_r, q, side="left").astype(jnp.int32)
            end = jnp.searchsorted(mem_r, q, side="right").astype(jnp.int32)
            idx = start[:, None] + iota[None, :]
            idxc = jnp.clip(idx, 0, mem_r.shape[0] - 1)
            seg_cols.append(mem_c[idxc])
            seg_vals.append(mem_v[idxc])
            seg_ok.append(idx < end[:, None])
            seg_age.append(n_levels + k0 + 1)
            cnts.append(end - start)
        # cross-run age-ordered combine, on-device
        cols_all = jnp.concatenate(seg_cols, axis=1)              # [Q, W]
        vals_all = jnp.concatenate(seg_vals, axis=1)
        ok_all = jnp.concatenate(seg_ok, axis=1)
        if has_filter:
            # residual column filter, on-device: sorted membership test
            # (filt pads with I32_MAX, which never equals a valid col)
            pos = jnp.clip(jnp.searchsorted(filt, cols_all), 0,
                           filt.shape[0] - 1)
            ok_all = ok_all & (filt[pos] == cols_all)
        ages = jnp.concatenate(
            [jnp.full((n_q, max_return), a, jnp.int32) for a in seg_age],
            axis=1)
        if pack:
            shift = (len(seg_age) + 1).bit_length()  # ages fit below shift
            key = jnp.where(ok_all, (cols_all << shift) + ages, I32_MAX)
            if cols_all.shape[1] <= 256:
                # packed keys are UNIQUE per row (cols unique within a run
                # segment, ages distinguish runs) — the merge_rank
                # rank+scatter combine beats XLA:CPU's scalar comparator
                # sort at these widths (N^2 branch-free compares, SIMD).
                key_s, val_s = merge_combine_rows(key, vals_all,
                                                  use_pallas=use_pallas,
                                                  interpret=INTERPRET)
            else:
                # widen retries can blow the candidate width up; the
                # quadratic compare loses to N log N there — fall back to
                # the packed single-key sort.
                key_s, val_s = jax.lax.sort((key, vals_all), dimension=1,
                                            num_keys=1)
            col_s = jnp.where(key_s == I32_MAX, I32_MAX, key_s >> shift)
        else:
            col_m = jnp.where(ok_all, cols_all, I32_MAX)
            col_s, _, val_s = jax.lax.sort(
                (col_m, ages, vals_all), dimension=1, num_keys=2)
        keep, out_v = jax.vmap(
            lambda r, v: _dedup_combine(r, jnp.zeros_like(r), v, combiner)
        )(col_s, val_s)
        cnt_max = jnp.max(jnp.stack([jnp.max(c) for c in cnts]))
        hits_vec = (jnp.stack(hits) if hits
                    else jnp.zeros((0,), jnp.bool_))
        return col_s, jnp.where(keep, out_v, 0.0), keep, cnt_max, hits_vec

    return jax.jit(fused)


@functools.lru_cache(maxsize=None)
def _fused_scan_fn(combiner: str, level_blocks: Tuple[int, ...], b0: int,
                   width: int, mem_mode: str, id_capacity: int,
                   use_pallas: bool, has_filter: bool = False):
    """Build THE single-dispatch range scan: a ``[lo, hi)`` row-range over
    one shard's resident leveled runs (deepest first), used L0 slots, and
    (optionally) memtable tail, answered inside one ``jax.jit``.

    Both endpoints are fence-bracketed exactly like the point path — rank
    ``lo`` and ``hi`` with ``side='left'`` (``hi`` exclusive), so each run
    contributes the contiguous candidate window ``[start, end)``. Under
    ``use_pallas`` the fence ranks go through the batched Pallas
    ``sorted_search`` kernel (the L0 stack in one launch, each level as a
    1-row batch). Per-run windows of static ``width`` are gathered into a
    ``[runs, width]`` candidate block; ``cnt_max`` > width signals the
    host to re-dispatch wider (batch-scanner semantics).

    The on-device merge-dedup sorts all candidates by ``(row, col, age)``
    and reduces equal-(row, col) groups with the combiner. Sort strategy
    by static key geometry (``kbits`` = id bits, ``abits`` = age bits):

    * ``2*kbits + abits <= 30``: ONE packed int32 key — XLA:CPU's fast
      single-key sort, same trick as the point path;
    * ``kbits + abits <= 31`` (the common 2^22-id config): (col, age)
      packs into one int32 and two STABLE single-key sorts (secondary
      then primary) implement the lexicographic order — still ~2 fast
      sorts instead of one ~10x-slower comparator sort;
    * else: a 3-key comparator sort (correctness fallback).

    With ``has_filter`` the dispatch takes an extra sorted int32 column
    id set (padded with I32_MAX) and masks candidates outside it before
    the merge-dedup — a range scan with a residual ``isin(cols)`` filter
    stays one dispatch with zero host post-filtering.

    Returns (rows[W], cols[W], vals[W], keep[W], cnt_max) with
    W = n_runs * width; kept entries are the combined triples sorted lex
    by (row, col).
    """
    from ..kvstore import _dedup_combine

    n_levels = len(level_blocks)

    def fused(lohi, levels, l0, mem, filt=None):
        iota = jnp.arange(width, dtype=jnp.int32)
        seg_r, seg_c, seg_v, seg_ok, seg_age, cnts = [], [], [], [], [], []

        def bracket(rows, f_ranks, block):
            cap = rows.shape[0]
            w = block + 1

            def one(qi, fi):
                base = jnp.clip(jnp.maximum(fi - 1, 0) * block, 0, cap - w)
                win = jax.lax.dynamic_slice(rows, (base,), (w,))
                return (base + jnp.searchsorted(win, qi, side="left")
                        ).astype(jnp.int32)

            return one(lohi[0], f_ranks[0]), one(lohi[1], f_ranks[1])

        def window(rows, cols, vals, start, end, age):
            idx = start + iota
            idxc = jnp.clip(idx, 0, rows.shape[0] - 1)
            seg_r.append(rows[idxc])
            seg_c.append(cols[idxc])
            seg_v.append(vals[idxc])
            seg_ok.append(idx < end)
            seg_age.append(age)
            cnts.append(end - start)

        # leveled runs, deepest (oldest) first — ages 1..L
        for i, (rows, cols, vals, fence, _bloom) in enumerate(levels):
            if use_pallas:
                flo, fhi = sorted_search_endpoints(fence[None], lohi,
                                                   interpret=INTERPRET)
                fr = jnp.stack([flo[0], fhi[0]])
            else:
                fr = jnp.searchsorted(fence, lohi, side="left"
                                      ).astype(jnp.int32)
            start, end = bracket(rows, fr, level_blocks[i])
            window(rows, cols, vals, start, end, i + 1)
        # the used L0 slots — ages L+1..L+K0
        l0_rows, l0_cols, l0_vals, l0_fence, _l0_bloom = l0
        k0 = l0_rows.shape[0]
        if k0:
            if use_pallas:
                flo0, fhi0 = sorted_search_endpoints(l0_fence, lohi,
                                                     interpret=INTERPRET)
                fr0 = jnp.stack([flo0, fhi0], axis=1)
            else:
                fr0 = jnp.stack([jnp.searchsorted(l0_fence[k], lohi,
                                                  side="left")
                                 .astype(jnp.int32) for k in range(k0)])
            for k in range(k0):
                start, end = bracket(l0_rows[k], fr0[k], b0)
                window(l0_rows[k], l0_cols[k], l0_vals[k], start, end,
                       n_levels + 1 + k)
        # the memtable tail (newest) — no fence metadata, direct ranks
        if mem_mode != "none":
            mem_r, mem_c, mem_v = mem
            if mem_mode == "raw":
                mem_r, mem_c, mem_v, _ = _sort_dedup(mem_r, mem_c, mem_v,
                                                     combiner)
            start = jnp.searchsorted(mem_r, lohi[0], side="left"
                                     ).astype(jnp.int32)
            end = jnp.searchsorted(mem_r, lohi[1], side="left"
                                   ).astype(jnp.int32)
            window(mem_r, mem_c, mem_v, start, end, n_levels + k0 + 1)
        # flat [W] candidate block, W = n_runs * width
        rows_all = jnp.concatenate(seg_r)
        cols_all = jnp.concatenate(seg_c)
        vals_all = jnp.concatenate(seg_v)
        ok_all = jnp.concatenate(seg_ok)
        if has_filter:
            # residual column filter, on-device: sorted membership test
            # (filt pads with I32_MAX, which never equals a valid col)
            pos = jnp.clip(jnp.searchsorted(filt, cols_all), 0,
                           filt.shape[0] - 1)
            ok_all = ok_all & (filt[pos] == cols_all)
        ages = jnp.concatenate([jnp.full((width,), a, jnp.int32)
                                for a in seg_age])
        abits = (len(seg_age) + 1).bit_length()
        kbits = max((id_capacity - 1).bit_length(), 1)
        if 2 * kbits + abits <= 30:
            key = jnp.where(ok_all, (rows_all << (kbits + abits))
                            + (cols_all << abits) + ages, I32_MAX)
            key_s, val_s = jax.lax.sort((key, vals_all), dimension=0,
                                        num_keys=1)
            pad = key_s == I32_MAX
            row_s = jnp.where(pad, I32_MAX, key_s >> (kbits + abits))
            col_s = jnp.where(pad, I32_MAX,
                              (key_s >> abits) & ((1 << kbits) - 1))
        elif kbits + abits <= 31:
            row_m = jnp.where(ok_all, rows_all, I32_MAX)
            key2 = jnp.where(ok_all, (cols_all << abits) + ages, I32_MAX)
            k2_s, row_1, val_1 = jax.lax.sort(
                (key2, row_m, vals_all), dimension=0, num_keys=1,
                is_stable=True)
            row_s, k2_f, val_s = jax.lax.sort(
                (row_1, k2_s, val_1), dimension=0, num_keys=1,
                is_stable=True)
            pad = row_s == I32_MAX
            col_s = jnp.where(pad, I32_MAX, k2_f >> abits)
        else:
            row_m = jnp.where(ok_all, rows_all, I32_MAX)
            col_m = jnp.where(ok_all, cols_all, I32_MAX)
            row_s, col_s, _, val_s = jax.lax.sort(
                (row_m, col_m, ages, vals_all), dimension=0, num_keys=3)
        keep, out_v = _dedup_combine(row_s, col_s, val_s, combiner)
        cnt_max = jnp.max(jnp.stack(cnts))
        return row_s, col_s, jnp.where(keep, out_v, 0.0), keep, cnt_max

    return jax.jit(fused)


def combine_triples(r: np.ndarray, c: np.ndarray, v: np.ndarray,
                    age: np.ndarray, combiner: str):
    """Host-side cross-run combine: sort candidates by (row, col, age) and
    reduce each key group per the combiner. Each source is already deduped
    (or, for the raw memtable, in append order with a constant age — the
    stable sort keeps append order, so 'last' still wins correctly)."""
    if len(r) == 0:
        z = np.zeros(0, np.int32)
        return z, z.copy(), np.zeros(0, np.float32)
    order = np.lexsort((age, c, r))
    r, c, v = r[order], c[order], v[order]
    new = np.ones(len(r), bool)
    new[1:] = (r[1:] != r[:-1]) | (c[1:] != c[:-1])
    starts = np.flatnonzero(new)
    if combiner == "last":
        ends = np.append(starts[1:], len(r)) - 1
        return r[starts], c[starts], v[ends]
    if combiner == "sum":
        vv = np.add.reduceat(v, starts)
    elif combiner == "min":
        vv = np.minimum.reduceat(v, starts)
    elif combiner == "max":
        vv = np.maximum.reduceat(v, starts)
    else:
        raise ValueError(f"unknown combiner {combiner!r}")
    return r[starts], c[starts], vv.astype(np.float32)


def _prep_mem(mem_host: Optional[Tuple], mem_sorted: bool):
    """Pad an unflushed memtable tail to a jit-stable bucket and pick the
    in-dispatch treatment: ``"sorted"`` = host pre-sorted/deduped mirror,
    ``"raw"`` = sort in-dispatch (stale-mirror/device path), ``"none"``."""
    mem_n = 0 if mem_host is None else len(mem_host[0])
    if not mem_n:
        return None, "none"
    mb = _bucket(mem_n)
    mr, mc, mv = mem_host
    if isinstance(mr, np.ndarray):
        pr = np.full(mb, I32_MAX, np.int32)
        pc = np.full(mb, I32_MAX, np.int32)
        pv = np.zeros(mb, np.float32)
        pr[:mem_n], pc[:mem_n], pv[:mem_n] = mr, mc, mv
        return (pr, pc, pv), ("sorted" if mem_sorted else "raw")
    # device arrays: pad lazily, stays async
    pad = mb - mem_n
    return (jnp.pad(mr, (0, pad), constant_values=I32_MAX),
            jnp.pad(mc, (0, pad), constant_values=I32_MAX),
            jnp.pad(mv, (0, pad))), "raw"


# counter schema shared by BOTH engines ("single" reports zeros where an
# op doesn't apply) so A/B stats line up in BENCH_ingest.json
STAT_KEYS = ("flushes", "major_compactions", "runs_probed", "runs_skipped",
             "fused_dispatches", "fused_widen_retries", "fused_tiles",
             "perrun_dispatches", "scan_dispatches", "scan_widen_retries")


# ------------------------------------------------------------------ engine
class LSMRuns:
    """The leveled run structure for S shards (no memtable — that stays in
    ``ShardedTable`` and is handed to ``flush_memtable``/read methods).

    ``bloom_bits_per_key`` / ``bloom_hashes`` size the per-run filters:
    scalars apply everywhere; sequences give one value per level (last
    entry repeats for deeper levels — ROADMAP "Bloom sizing": deep levels
    see most negative lookups, so size them denser). L0 runs always use
    the first entry (they are small and short-lived)."""

    def __init__(self, num_shards: int, capacity_per_shard: int,
                 mem_cap: int, combiner: str, use_pallas: bool = False,
                 l0_slots: int = 4, fanout: int = 4,
                 bloom_bits_per_key: Union[int, Sequence[int]] = BITS_PER_KEY,
                 bloom_hashes: Union[int, Sequence[int]] = NUM_HASHES,
                 id_capacity: int = 1 << 22, name: str = "lsm"):
        assert mem_cap >= 8, "LSM memtable too small to index"
        self.S = num_shards
        self.name = name
        self.cap = capacity_per_shard
        self.mem_cap = mem_cap
        self.combiner = combiner
        self.use_pallas = use_pallas
        self.id_capacity = id_capacity  # bounds col ids: fused key packing
        self.K0 = l0_slots
        self.fanout = fanout
        self.level_caps = plan_levels(capacity_per_shard, mem_cap, l0_slots,
                                      fanout)
        n_levels = len(self.level_caps)
        self.bloom_bits = _per_level(bloom_bits_per_key, n_levels)
        self.bloom_hashes = _per_level(bloom_hashes, n_levels)
        bad = [h for h in self.bloom_hashes if not 1 <= h <= MAX_HASHES]
        if bad:
            # _MULTS bounds the hash family; silently clamping would make
            # the manifest (and theoretical_fp_rate) lie about the filter
            raise ValueError(
                f"bloom_hashes {bad} outside [1, {MAX_HASHES}]")
        S, m, K0 = num_shards, mem_cap, l0_slots
        self._w0 = num_words(m, self.bloom_bits[0])
        self._h0 = self.bloom_hashes[0]
        self._b0 = fence_block(m)
        nblk0 = -(-m // self._b0)
        self.l0_rows = jnp.full((S, K0, m), I32_MAX, jnp.int32)
        self.l0_cols = jnp.full((S, K0, m), I32_MAX, jnp.int32)
        self.l0_vals = jnp.zeros((S, K0, m), jnp.float32)
        self.l0_bloom = jnp.zeros((S, K0, self._w0), jnp.uint32)
        self.l0_fence = jnp.full((S, K0, nblk0), I32_MAX, jnp.int32)
        self.l0_n = np.zeros((S, K0), np.int64)
        # host-side row ranges per run: skip runs without device roundtrips
        self.l0_min = np.full((S, K0), I32_MAX, np.int64)
        self.l0_max = np.full((S, K0), -1, np.int64)
        # per-SHARD used-slot counts: shards fill (and major-compact) their
        # own L0 independently — one hot shard no longer drags its peers
        # through a lockstep merge (ROADMAP "Leveled compaction tuning")
        self.l0_used = np.zeros((S,), np.int64)
        self.levels: List[dict] = []
        for i, cap in enumerate(self.level_caps):
            w = num_words(cap, self.bloom_bits[i])
            b = fence_block(cap)
            self.levels.append({
                "cap": cap, "words": w, "block": b,
                "bits": self.bloom_bits[i], "hashes": self.bloom_hashes[i],
                "rows": jnp.full((S, cap), I32_MAX, jnp.int32),
                "cols": jnp.full((S, cap), I32_MAX, jnp.int32),
                "vals": jnp.zeros((S, cap), jnp.float32),
                "bloom": jnp.zeros((S, w), jnp.uint32),
                "fence": jnp.full((S, -(-cap // b)), I32_MAX, jnp.int32),
                "n": np.zeros((S,), np.int64),
                "minr": np.full((S,), I32_MAX, np.int64),
                "maxr": np.full((S,), -1, np.int64),
            })
        # read/write-path observability: the old ad-hoc stats dict is now
        # registry counters labeled by table name (the `.stats` property
        # keeps the dict view). Series are reset at construction so a
        # fresh engine reads zeros, same as the dict did — two LIVE
        # engines sharing one table name share (and clobber) series,
        # which only test code does.
        self._reg = default_registry()
        self._trace = default_tracer()
        self._ctr = {k: self._reg.counter("lsm_" + k, table=name)
                     for k in STAT_KEYS}
        self._c_shard_flush = [
            self._reg.counter("lsm_shard_flushes", table=name, shard=s)
            for s in range(S)]
        self._c_shard_compact = [
            self._reg.counter("lsm_shard_compactions", table=name, shard=s)
            for s in range(S)]
        self._h_flush = self._reg.histogram("db_op_latency_s", table=name,
                                            op="flush")
        self._h_compact = self._reg.histogram("db_op_latency_s", table=name,
                                              op="major_compaction")
        # compile/retrace telemetry: one inc per fresh static signature of
        # the fused read builders (see _fused_query_compiled)
        self._c_retrace_q = self._reg.counter("lsm_retraces", table=name,
                                              op="query")
        self._c_retrace_s = self._reg.counter("lsm_retraces", table=name,
                                              op="scan")
        # write-amplification inputs: entries written into runs by flushes
        # and rewritten by compactions (vs db_ingest_entries)
        self._c_flush_entries = self._reg.counter("lsm_flush_entries",
                                                  table=name)
        self._c_compact_entries = self._reg.counter("lsm_compact_entries",
                                                    table=name)
        for inst in ([self._h_flush, self._h_compact]
                     + list(self._ctr.values())
                     + [self._c_retrace_q, self._c_retrace_s,
                        self._c_flush_entries, self._c_compact_entries]
                     + self._c_shard_flush + self._c_shard_compact):
            inst.reset()
        # per-run sliced views of the stacked arrays (slicing copies ~MBs
        # eagerly per query otherwise); invalidated on flush/compaction.
        # Fused-path entries key ("fused", s) and hold the level tuple +
        # L0 stack views handed to the single-dispatch query.
        self._view_cache: dict = {}

    @property
    def stats(self) -> dict:
        """Backward-compatible dict view of the registry counters (the old
        ad-hoc stats dict). Read-only: a fresh dict per access."""
        return {k: int(c.value) for k, c in self._ctr.items()}

    def warmup(self, mem_r, mem_c, mem_v) -> None:
        """Compile the flush + every compaction depth's graph by running
        them on the current (typically empty) state; results are discarded,
        so no state mutates. Keeps jit time out of benchmark windows."""
        rr, cc, vv, n, bb, ff, _, _ = _flush_fn(
            self.combiner, self._w0, self._b0, self._h0)(mem_r, mem_c, mem_v)
        _write_slot_fn()(self.l0_rows, self.l0_cols, self.l0_vals,
                         self.l0_bloom, self.l0_fence, rr, cc, vv, bb, ff,
                         jnp.zeros((self.S,), jnp.int32))
        for d, lv in enumerate(self.levels):
            lvls = tuple((self.levels[i]["rows"], self.levels[i]["cols"],
                          self.levels[i]["vals"]) for i in range(d, -1, -1))
            out = _compact_fn(self.combiner, self.use_pallas, lv["cap"],
                              lv["words"], lv["block"], lv["hashes"])(
                self.l0_rows, self.l0_cols, self.l0_vals, lvls)
            jax.block_until_ready(out)

    # ----------------------------------------------------------- write path
    def flush_memtable(self, mem_r, mem_c, mem_v) -> None:
        """Minor compaction: memtable -> one L0 run per shard, O(m log m).
        Shards whose OWN L0 is full (and that actually have data to flush)
        are major-compacted first — peers keep their L0 runs untouched.
        May raise OverflowError (capacity back-pressure, like the legacy
        engine)."""
        t0 = perf_counter()
        with self._trace.span("flush", table=self.name):
            self._flush_memtable(mem_r, mem_c, mem_v)
        self._h_flush.observe(perf_counter() - t0)

    def _flush_memtable(self, mem_r, mem_c, mem_v) -> None:
        rr, cc, vv, n, bb, ff, mn, mx = _flush_fn(
            self.combiner, self._w0, self._b0, self._h0)(mem_r, mem_c, mem_v)
        n_host = np.asarray(n).astype(np.int64)
        landing = n_host > 0          # shards receiving a non-empty run
        full = (self.l0_used >= self.K0) & landing
        if full.any():
            self.major_compact(mask=full)
        slot = self.l0_used.copy()    # per-shard next free slot (K0 = drop)
        (self.l0_rows, self.l0_cols, self.l0_vals, self.l0_bloom,
         self.l0_fence) = _write_slot_fn()(
            self.l0_rows, self.l0_cols, self.l0_vals, self.l0_bloom,
            self.l0_fence, rr, cc, vv, bb, ff,
            jnp.asarray(slot, jnp.int32))
        sidx = np.flatnonzero(landing)
        self.l0_n[sidx, slot[sidx]] = n_host[sidx]
        self.l0_min[sidx, slot[sidx]] = np.asarray(mn).astype(np.int64)[sidx]
        self.l0_max[sidx, slot[sidx]] = np.asarray(mx).astype(np.int64)[sidx]
        # all L0 slot views (and the fused stacked views, which embed the
        # L0 stack) alias the re-written arrays; drop them
        self._view_cache = {k: v for k, v in self._view_cache.items()
                            if k[0] not in ("l0", "fused")}
        self.l0_used = self.l0_used + landing.astype(np.int64)
        self._ctr["flushes"].inc()
        self._c_flush_entries.inc(int(n_host[sidx].sum()))
        for s in sidx:
            self._c_shard_flush[s].inc()
        full = self.l0_used >= self.K0
        if full.any():
            self.major_compact(mask=full)

    def _pick_depth(self, mask: np.ndarray) -> int:
        """Smallest level whose capacity bounds the (pre-dedup) merge size
        for every COMPACTING shard; the deepest level is the fallback."""
        bound = self.l0_n.sum(axis=1)  # [S]
        for d, lv in enumerate(self.levels):
            bound = bound + lv["n"]
            if int(bound[mask].max()) <= lv["cap"]:
                return d
        return len(self.levels) - 1

    def major_compact(self, mask: Optional[np.ndarray] = None) -> None:
        """Size-triggered major compaction: k-way merge the L0 runs and
        levels 1..d into level d (Pallas merge_rank under ``use_pallas``).

        ``mask`` selects WHICH shards compact (default: every shard with
        L0 data). The merge itself stays one vmapped dispatch over all S
        shards (static shapes); unmasked shards' merged output is simply
        discarded — their runs, counts, and L0 slots are untouched, so a
        single hot shard filling its L0 no longer forces a lockstep merge
        of every peer."""
        if mask is None:
            mask = self.l0_used > 0
        mask = np.asarray(mask, bool)
        if not mask.any():
            return
        t0 = perf_counter()
        with self._trace.span("major_compact", table=self.name,
                              shards=int(mask.sum())):
            self._major_compact(mask)
        self._h_compact.observe(perf_counter() - t0)

    def _major_compact(self, mask: np.ndarray) -> None:
        d = self._pick_depth(mask)
        target = self.levels[d]
        # deepest first = oldest first (kway_merge contract)
        lvls = tuple((self.levels[i]["rows"], self.levels[i]["cols"],
                      self.levels[i]["vals"]) for i in range(d, -1, -1))
        rr, cc, vv, n, bb, ff, mn, mx = _compact_fn(
            self.combiner, self.use_pallas, target["cap"], target["words"],
            target["block"], target["hashes"])(
            self.l0_rows, self.l0_cols, self.l0_vals, lvls)
        n_host = np.asarray(n)
        if d == len(self.levels) - 1 and int(n_host[mask].max()) > self.cap:
            raise OverflowError(
                f"LSM shard overflow: {int(n_host[mask].max())} > {self.cap}")
        m_dev = jnp.asarray(mask)

        def sel(new, old):
            m = m_dev.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(m, new, old)

        target.update(
            rows=sel(rr, target["rows"]), cols=sel(cc, target["cols"]),
            vals=sel(vv, target["vals"]), bloom=sel(bb, target["bloom"]),
            fence=sel(ff, target["fence"]),
            n=np.where(mask, n_host, target["n"]).astype(np.int64),
            minr=np.where(mask, np.asarray(mn),
                          target["minr"]).astype(np.int64),
            maxr=np.where(mask, np.asarray(mx),
                          target["maxr"]).astype(np.int64))
        # clear L0 + the shallower levels for the compacted shards ONLY
        m3 = m_dev[:, None, None]
        self.l0_rows = jnp.where(m3, jnp.int32(I32_MAX), self.l0_rows)
        self.l0_cols = jnp.where(m3, jnp.int32(I32_MAX), self.l0_cols)
        self.l0_vals = jnp.where(m3, jnp.float32(0.0), self.l0_vals)
        self.l0_bloom = jnp.where(m3, jnp.uint32(0), self.l0_bloom)
        self.l0_fence = jnp.where(m3, jnp.int32(I32_MAX), self.l0_fence)
        self.l0_n[mask] = 0
        self.l0_min[mask] = I32_MAX
        self.l0_max[mask] = -1
        self.l0_used[mask] = 0
        m2 = m_dev[:, None]
        for i in range(d):
            lv = self.levels[i]
            lv["rows"] = jnp.where(m2, jnp.int32(I32_MAX), lv["rows"])
            lv["cols"] = jnp.where(m2, jnp.int32(I32_MAX), lv["cols"])
            lv["vals"] = jnp.where(m2, jnp.float32(0.0), lv["vals"])
            lv["bloom"] = jnp.where(m2, jnp.uint32(0), lv["bloom"])
            lv["fence"] = jnp.where(m2, jnp.int32(I32_MAX), lv["fence"])
            lv["n"][mask] = 0
            lv["minr"][mask] = I32_MAX
            lv["maxr"][mask] = -1
        self._view_cache.clear()
        self._ctr["major_compactions"].inc()
        self._c_compact_entries.inc(int(n_host[mask].sum()))
        for s in np.flatnonzero(mask):
            self._c_shard_compact[s].inc()

    # ------------------------------------------------------------ read path
    def resident_runs(self, s: int) -> int:
        """How many non-empty runs shard ``s`` holds (levels + L0)."""
        n = sum(1 for lv in self.levels if lv["n"][s])
        n += sum(1 for k in range(int(self.l0_used[s])) if self.l0_n[s, k])
        return n

    # ------------------------------------------------------ tablet support
    def clear_shard(self, s: int) -> None:
        """Drop EVERY resident run of one shard — L0 slots and all
        levels, including the deepest. Tablet migration uses this: the
        caller has already scanned the shard's combined triples and will
        re-insert them under the new tablet map, so the old physical
        placement must vanish first (otherwise moved entries would be
        served from both shards)."""
        mask = np.zeros((self.S,), bool)
        mask[s] = True
        m_dev = jnp.asarray(mask)
        m3 = m_dev[:, None, None]
        self.l0_rows = jnp.where(m3, jnp.int32(I32_MAX), self.l0_rows)
        self.l0_cols = jnp.where(m3, jnp.int32(I32_MAX), self.l0_cols)
        self.l0_vals = jnp.where(m3, jnp.float32(0.0), self.l0_vals)
        self.l0_bloom = jnp.where(m3, jnp.uint32(0), self.l0_bloom)
        self.l0_fence = jnp.where(m3, jnp.int32(I32_MAX), self.l0_fence)
        self.l0_n[mask] = 0
        self.l0_min[mask] = I32_MAX
        self.l0_max[mask] = -1
        self.l0_used[mask] = 0
        m2 = m_dev[:, None]
        for lv in self.levels:
            lv["rows"] = jnp.where(m2, jnp.int32(I32_MAX), lv["rows"])
            lv["cols"] = jnp.where(m2, jnp.int32(I32_MAX), lv["cols"])
            lv["vals"] = jnp.where(m2, jnp.float32(0.0), lv["vals"])
            lv["bloom"] = jnp.where(m2, jnp.uint32(0), lv["bloom"])
            lv["fence"] = jnp.where(m2, jnp.int32(I32_MAX), lv["fence"])
            lv["n"][mask] = 0
            lv["minr"][mask] = I32_MAX
            lv["maxr"][mask] = -1
        self._view_cache.clear()

    def fence_keys(self, s: int, lo: int, hi: int) -> np.ndarray:
        """Sorted host view of shard ``s``'s resident fence keys inside
        ``[lo, hi)``. Fences sample each sorted run at fixed block
        stride, so their distribution tracks the shard's key
        distribution without scanning any run."""
        keys = []
        for lv in self.levels:
            if lv["n"][s] and lv["minr"][s] < hi and lv["maxr"][s] >= lo:
                keys.append(np.asarray(lv["fence"][s]))
        for k in range(int(self.l0_used[s])):
            if (self.l0_n[s, k] and self.l0_min[s, k] < hi
                    and self.l0_max[s, k] >= lo):
                keys.append(np.asarray(self.l0_fence[s, k]))
        if not keys:
            return np.zeros(0, np.int64)
        cat = np.concatenate(keys).astype(np.int64)
        cat = cat[(cat >= lo) & (cat < hi) & (cat != I32_MAX)]
        cat.sort()
        return cat

    def fence_median(self, s: int, lo: int, hi: int) -> int:
        """Median resident fence key of shard ``s`` within ``[lo, hi)``
        — the tablet split point: an approximate median KEY of the
        shard's data in the range, for free. Falls back to the range
        midpoint when no fence lands inside; the result is always
        strictly interior to ``(lo, hi)`` (callers ensure width > 1)."""
        ks = self.fence_keys(s, lo, hi)
        med = int(np.median(ks)) if len(ks) else (int(lo) + int(hi)) // 2
        return int(min(max(med, int(lo) + 1), int(hi) - 1))

    # --------------------------------------------------------- health view
    def refresh_health_gauges(self, bloom_probes: int = 0) -> None:
        """Derive the engine health gauges from current state: resident
        runs + compaction debt per shard, read amplification (runs probed
        per read dispatch) and write amplification (entries written by
        flush/compaction per entry ingested) per table. All inputs are
        host-side mirrors/counters — no device sync. ``bloom_probes > 0``
        additionally measures the observed bloom fp rate by probing each
        resident run's filter with keys provably outside its row range
        (costs one tiny dispatch per resident run)."""
        reg = self._reg
        for s in range(self.S):
            reg.gauge("lsm_resident_runs", table=self.name, shard=s).set(
                self.resident_runs(s))
            u = int(self.l0_used[s])
            reg.gauge("lsm_compaction_debt_entries", table=self.name,
                      shard=s).set(int(self.l0_n[s, :u].sum()))
        c = self._ctr
        reads = int(c["fused_dispatches"].value
                    + c["perrun_dispatches"].value)
        probed = int(c["runs_probed"].value)
        reg.gauge("lsm_read_amplification", table=self.name).set(
            probed / reads if reads else 0.0)
        ingested = sum(int(x.value) for x in
                       reg.series("db_ingest_entries", table=self.name))
        written = int(self._c_flush_entries.value
                      + self._c_compact_entries.value)
        reg.gauge("lsm_write_amplification", table=self.name).set(
            written / ingested if ingested else 0.0)
        if bloom_probes:
            obs_fp, theo_fp = self._bloom_fp_probe(bloom_probes)
            reg.gauge("lsm_bloom_fp_observed", table=self.name).set(obs_fp)
            reg.gauge("lsm_bloom_fp_theoretical",
                      table=self.name).set(theo_fp)

    def _bloom_fp_probe(self, probes: int):
        """(observed, theoretical) bloom fp rate over the resident runs.

        Probe keys are sampled outside a run's host-tracked [minr, maxr]
        row range, so the run provably does not contain them — any filter
        hit is a certain false positive. The theoretical rate is the
        classic bound, probe-count weighted across runs."""
        rng = np.random.default_rng(0xB100F)
        tot_probes = tot_fp = 0
        theo_w = 0.0
        for s in range(self.S):
            runs = [(lv["bloom"][s], lv["hashes"], lv["words"],
                     int(lv["n"][s]), int(lv["minr"][s]), int(lv["maxr"][s]))
                    for lv in self.levels if lv["n"][s]]
            runs += [(self.l0_bloom[s, k], self._h0, self._w0,
                      int(self.l0_n[s, k]), int(self.l0_min[s, k]),
                      int(self.l0_max[s, k]))
                     for k in range(int(self.l0_used[s]))
                     if self.l0_n[s, k]]
            for words, n_hashes, n_words, n_keys, minr, maxr in runs:
                cand = rng.integers(0, self.id_capacity, 4 * probes)
                cand = cand[(cand < minr) | (cand > maxr)][:probes]
                if len(cand) < probes:
                    continue  # run spans ~the whole id space: no negatives
                hits = bloom_maybe_contains(
                    jnp.asarray(words), jnp.asarray(cand, jnp.int32),
                    n_hashes=n_hashes)
                tot_fp += int(np.asarray(hits).sum())
                tot_probes += probes
                theo_w += probes * theoretical_fp_rate(n_keys, n_words,
                                                       n_hashes)
        if not tot_probes:
            return 0.0, 0.0
        return tot_fp / tot_probes, theo_w / tot_probes

    def _iter_runs_oldest_first(self, s: int):
        """Yield (rows, cols, vals, fence, bloom, n, block, minr, maxr,
        hashes) per-run views of shard ``s``, oldest (deepest level) to
        newest (latest L0 slot)."""
        for i in range(len(self.levels) - 1, -1, -1):
            lv = self.levels[i]
            if lv["n"][s]:
                key = ("lvl", i, s)
                view = self._view_cache.get(key)
                if view is None:
                    view = (lv["rows"][s], lv["cols"][s], lv["vals"][s],
                            lv["fence"][s], lv["bloom"][s])
                    self._view_cache[key] = view
                yield view + (int(lv["n"][s]), lv["block"],
                              int(lv["minr"][s]), int(lv["maxr"][s]),
                              lv["hashes"])
        for k in range(int(self.l0_used[s])):
            if self.l0_n[s, k]:
                key = ("l0", k, s)
                view = self._view_cache.get(key)
                if view is None:
                    view = (self.l0_rows[s, k], self.l0_cols[s, k],
                            self.l0_vals[s, k], self.l0_fence[s, k],
                            self.l0_bloom[s, k])
                    self._view_cache[key] = view
                yield view + (int(self.l0_n[s, k]), self._b0,
                              int(self.l0_min[s, k]), int(self.l0_max[s, k]),
                              self._h0)

    def _fused_views(self, s: int):
        """Per-shard stacked views for the fused dispatch: the RESIDENT
        leveled runs (deepest first, with their static fence-block/hash
        meta) plus the L0 stack sliced to the used slots. Restricting the
        dispatch to resident runs is what lets it beat the per-run path —
        probing an empty 256k-capacity level costs real gather work.
        Residency only changes on flush/compaction, which is exactly when
        this cache invalidates, so the slicing cost is amortized across
        every query in between (no per-query re-bucketing)."""
        key = ("fused", s)
        view = self._view_cache.get(key)
        if view is None:
            live = [i for i in range(len(self.levels) - 1, -1, -1)
                    if self.levels[i]["n"][s]]
            levels = tuple(
                (self.levels[i]["rows"][s], self.levels[i]["cols"][s],
                 self.levels[i]["vals"][s], self.levels[i]["fence"][s],
                 self.levels[i]["bloom"][s])
                for i in live)
            blocks = tuple(self.levels[i]["block"] for i in live)
            hashes = tuple(self.levels[i]["hashes"] for i in live)
            u = int(self.l0_used[s])
            l0 = (self.l0_rows[s, :u], self.l0_cols[s, :u],
                  self.l0_vals[s, :u], self.l0_fence[s, :u],
                  self.l0_bloom[s, :u])
            view = (levels, blocks, hashes, tuple(live), l0)
            self._view_cache[key] = view
        return view

    # -- compile/retrace telemetry ----------------------------------------
    # The fused read builders are lru_cache'd on their STATIC signature, so
    # a builder cache miss == one fresh XLA trace+compile. Counting misses
    # turns the "no batch size ever retraces" serving invariant into a
    # registry-asserted guarantee: after warm_reads() the lsm_retraces
    # counter must stay flat across any batch-size sweep.
    def _fused_query_compiled(self, *key):
        misses0 = _fused_query_fn.cache_info().misses
        fn = _fused_query_fn(*key)
        ci = _fused_query_fn.cache_info()
        if ci.misses != misses0:
            self._c_retrace_q.inc()
            self._reg.gauge("lsm_compiled_shapes", op="query").set(
                ci.currsize)
        return fn

    def _fused_scan_compiled(self, *key):
        misses0 = _fused_scan_fn.cache_info().misses
        fn = _fused_scan_fn(*key)
        ci = _fused_scan_fn.cache_info()
        if ci.misses != misses0:
            self._c_retrace_s.inc()
            self._reg.gauge("lsm_compiled_shapes", op="scan").set(
                ci.currsize)
        return fn

    def query_shard_fused(self, s: int, q: np.ndarray,
                          mem_host: Optional[Tuple] = None,
                          max_return: int = 256,
                          mem_sorted: bool = False,
                          q_tile: Optional[int] = None,
                          col_filter: Optional[np.ndarray] = None):
        """Point row queries for one shard, fused: each dispatch searches
        the resident leveled runs, the used L0 slots, and the memtable
        tail and age-order combines on-device. ``q`` must be sorted unique
        int32 (the ``ShardedTable`` driver guarantees it); ``mem_host`` is
        the shard's unflushed tail as (rows, cols, vals) arrays — numpy
        (host mirror; pass ``mem_sorted=True`` if already
        (row, col)-sorted and combiner-deduped) or device slices
        (stale-mirror SPMD path). NO flush happens.

        When ``q_tile`` is set the read path serves every batch size from
        exactly TWO static shapes: tiny point reads (n_q <= 8) use the
        small bucket, and everything else pads UP to the ``q_tile`` tile —
        batches larger than the tile split into ceil(n_q / tile)
        dispatches of that one shape, each independently widen-retryable.
        One jit cache entry therefore covers every large batch size the
        caller ever sends (a fresh size never retraces — the legacy
        engine, whose query shape follows the batch, recompiles per novel
        size). Each run's probe is block bloom-gated inside the dispatch,
        so a tile whose keys all miss a run's filter skips that run's
        search entirely. Tiles are contiguous slices of the sorted ``q``,
        so concatenating per-tile results preserves global row order.
        ``q_tile=None`` keeps the legacy bucket-by-batch-size shapes.

        ``col_filter`` (optional int32 id set) pushes the residual
        column ``isin`` of a row-driven read into the dispatch as an
        on-device sorted-membership mask — no host post-filter."""
        n_q = len(q)
        filt_dev = None
        has_filter = col_filter is not None
        if has_filter:
            cf = np.unique(np.asarray(col_filter, np.int32))
            if len(cf) == 0:  # empty filter: nothing can match
                z = np.zeros(0, np.int32)
                return z, z.copy(), np.zeros(0, np.float32)
            cf_pad = np.full(_bucket(len(cf)), I32_MAX, np.int32)
            cf_pad[:len(cf)] = cf
            filt_dev = jnp.asarray(cf_pad)
        mem, mem_mode = _prep_mem(mem_host, mem_sorted)
        levels, blocks, hashes, live, l0 = self._fused_views(s)
        n_runs = len(levels) + int(l0[0].shape[0]) + (mem_mode != "none")
        # single-int32 (col, age) key packing needs col * age_pad headroom
        pack = self.id_capacity <= (1 << 24) and n_runs + 2 < 64
        # small initial per-run return width: the combine cost scales with
        # Qtile * (runs * width)^2, and point reads rarely exceed a few
        # entries per run — cnt_max triggers the widen retry when they do
        r_ret = min(4, _bucket(max_return))
        tile = (_bucket(n_q) if q_tile is None or n_q <= 8
                else _bucket(q_tile))
        n_tiles = max(1, -(-n_q // tile))
        if n_tiles > 1:
            self._ctr["fused_tiles"].inc(n_tiles)
        fn = self._fused_query_compiled(self.combiner, blocks, hashes,
                                        self._b0, self._h0, r_ret,
                                        mem_mode, pack, self.use_pallas,
                                        has_filter)
        tr = self._trace
        out_r, out_c, out_v = [], [], []
        hit_any = None
        with tr.span("query.fused", table=self.name, shard=s, n_q=n_q,
                     tiles=n_tiles):
            for t in range(n_tiles):
                q_blk = q[t * tile:(t + 1) * tile]
                nb = len(q_blk)
                q_pad = np.full(tile, -1, np.int32)  # -1: matches nothing
                q_pad[:nb] = q_blk
                self._ctr["fused_dispatches"].inc()
                with tr.span("dispatch", tile=t):
                    out = fn(q_pad, levels, l0, mem, filt_dev)
                with tr.span("host_sync"):
                    cols_s, vals_s, keep, cnt_max, hits = \
                        tuple(np.asarray(x) for x in out)
                if int(cnt_max) > r_ret:  # widen + retry (scanner)
                    self._ctr["fused_widen_retries"].inc()
                    self._ctr["fused_dispatches"].inc()
                    with tr.span("widen_retry", width=int(cnt_max)):
                        wfn = self._fused_query_compiled(
                            self.combiner, blocks, hashes, self._b0,
                            self._h0, _bucket(int(cnt_max)), mem_mode,
                            pack, self.use_pallas, has_filter)
                        out = wfn(q_pad, levels, l0, mem, filt_dev)
                        cols_s, vals_s, keep, cnt_max, hits = \
                            tuple(np.asarray(x) for x in out)
                qi, ki = np.nonzero(keep[:nb])
                out_r.append(q_blk[qi])
                out_c.append(cols_s[:nb][qi, ki])
                out_v.append(vals_s[:nb][qi, ki])
                hit_any = hits if hit_any is None else (hit_any | hits)
        # observability: a run counts as probed if ANY tile's query block
        # hit its bloom; hits = [resident levels deepest-first, used slots]
        probed, skipped = self._ctr["runs_probed"], self._ctr["runs_skipped"]
        for i in range(len(live)):
            (probed if hit_any[i] else skipped).inc()
        for k in range(int(self.l0_used[s])):
            if self.l0_n[s, k]:
                (probed if hit_any[len(live) + k] else skipped).inc()
        return (np.concatenate(out_r).astype(np.int32),
                np.concatenate(out_c).astype(np.int32),
                np.concatenate(out_v).astype(np.float32))

    def scan_shard_fused(self, s: int, lo: int, hi: int,
                         mem_host: Optional[Tuple] = None,
                         width: int = 64, mem_sorted: bool = False,
                         col_filter: Optional[np.ndarray] = None):
        """Row-range scan ``[lo, hi)`` of one shard in ONE jitted dispatch
        + ONE host sync: every resident leveled run, used L0 slot, and the
        memtable tail is fence-bracketed at both endpoints and the
        candidate windows are merged-deduped on-device (the read-path
        analogue of the fused point query — no per-run dispatches, no
        id-list point expansion). ``width`` is the initial per-run window;
        a run whose range slice overflows it triggers ONE widen retry at
        the next pow2 ≥ the true max slice. Returns combined
        (rows, cols, vals) sorted lex by (row, col). NO flush happens.

        ``col_filter`` (optional int32 id set) masks columns outside the
        set on-device before the merge-dedup (residual ``isin``)."""
        lo, hi = int(lo), int(hi)
        empty = (np.zeros(0, np.int32), np.zeros(0, np.int32),
                 np.zeros(0, np.float32))
        filt_dev = None
        has_filter = col_filter is not None
        if has_filter:
            cf = np.unique(np.asarray(col_filter, np.int32))
            if len(cf) == 0:  # empty filter: nothing can match
                return empty
            cf_pad = np.full(_bucket(len(cf)), I32_MAX, np.int32)
            cf_pad[:len(cf)] = cf
            filt_dev = jnp.asarray(cf_pad)
        mem, mem_mode = _prep_mem(mem_host, mem_sorted)
        if hi <= lo:
            return empty
        # host run-range metadata: skip the dispatch entirely when no
        # resident run (and no memtable tail) intersects [lo, hi)
        inter = mem_mode != "none"
        if not inter:
            for lv in self.levels:
                if lv["n"][s] and lv["minr"][s] < hi and lv["maxr"][s] >= lo:
                    inter = True
                    break
        if not inter:
            for k in range(int(self.l0_used[s])):
                if (self.l0_n[s, k] and self.l0_min[s, k] < hi
                        and self.l0_max[s, k] >= lo):
                    inter = True
                    break
        if not inter:
            return empty
        levels, blocks, hashes, live, l0 = self._fused_views(s)
        if not levels and not int(l0[0].shape[0]) and mem_mode == "none":
            return empty
        lohi = jnp.asarray(np.asarray([lo, hi], np.int32))
        w = _bucket(width, lo=16)
        fn = self._fused_scan_compiled(self.combiner, blocks, self._b0, w,
                                       mem_mode, self.id_capacity,
                                       self.use_pallas, has_filter)
        tr = self._trace
        self._ctr["scan_dispatches"].inc()
        with tr.span("scan.fused", table=self.name, shard=s, lo=lo, hi=hi):
            with tr.span("dispatch"):
                out = fn(lohi, levels, l0, mem, filt_dev)
            with tr.span("host_sync"):
                rows_s, cols_s, vals_s, keep, cnt_max = \
                    tuple(np.asarray(x) for x in out)
            if int(cnt_max) > w:  # widen + retry (batch-scanner semantics)
                self._ctr["scan_widen_retries"].inc()
                self._ctr["scan_dispatches"].inc()
                with tr.span("widen_retry", width=int(cnt_max)):
                    fn = self._fused_scan_compiled(
                        self.combiner, blocks, self._b0,
                        _bucket(int(cnt_max)), mem_mode,
                        self.id_capacity, self.use_pallas, has_filter)
                    out = fn(lohi, levels, l0, mem, filt_dev)
                    rows_s, cols_s, vals_s, keep, _ = \
                        tuple(np.asarray(x) for x in out)
        ki = np.flatnonzero(keep)
        return (rows_s[ki].astype(np.int32), cols_s[ki].astype(np.int32),
                vals_s[ki].astype(np.float32))

    def query_shard(self, s: int, q: np.ndarray, mem_r, mem_c, mem_v,
                    mem_n: int, max_return: int,
                    mem_host: Optional[Tuple[np.ndarray, ...]] = None):
        """Per-run baseline read path: probe runs oldest→newest plus the
        memtable tail, one bloom-gated launch per resident run, combine
        across sources on the host. NO flush happens.

        Two-phase: launch the bloom-gated query of every candidate run
        asynchronously, then sync once and harvest — latency is one device
        round-trip but still N dispatches; ``query_shard_fused`` collapses
        those into one. ``mem_host`` is an optional host mirror of the
        shard's memtable (avoids pulling the device buffer)."""
        q_dev = jnp.asarray(q)
        q_sorted = np.sort(q)
        launched = []
        age = 0
        for rows, cols, vals, fence, bloom, n, block, minr, maxr, hashes in \
                self._iter_runs_oldest_first(s):
            age += 1
            if q_sorted[-1] < minr or q_sorted[0] > maxr:
                self._ctr["runs_skipped"].inc()
                continue
            self._ctr["perrun_dispatches"].inc()
            out = run_query_gated(rows, cols, vals, fence, bloom, q_dev,
                                  max_return, block, hashes)
            launched.append((age, (rows, cols, vals, fence, block), out))
        cand_r, cand_c, cand_v, cand_a = [], [], [], []
        for age_i, run, (any_hit, cols_o, vals_o, ok, cnt) in launched:
            if not bool(any_hit):  # bloom says absent — search was skipped
                self._ctr["runs_skipped"].inc()
                continue
            self._ctr["runs_probed"].inc()
            cnt = np.asarray(cnt)
            if cnt.max(initial=0) > max_return:  # widen + retry (scanner)
                rows, cols, vals, fence, block = run
                self._ctr["perrun_dispatches"].inc()
                cols_o, vals_o, ok, cnt = run_query_rows(
                    rows, cols, vals, fence, q_dev, int(cnt.max()), block)
            ok = np.asarray(ok)
            cols_o, vals_o = np.asarray(cols_o), np.asarray(vals_o)
            qi, ki = np.nonzero(ok)
            cand_r.append(q[qi]); cand_c.append(cols_o[qi, ki])
            cand_v.append(vals_o[qi, ki])
            cand_a.append(np.full(len(qi), age_i, np.int32))
        if mem_n:
            if mem_host is not None:
                mr, mc, mv = mem_host
            else:
                mr = np.asarray(mem_r[:mem_n])
                mc = np.asarray(mem_c[:mem_n])
                mv = np.asarray(mem_v[:mem_n])
            mask = np.isin(mr, q)
            if mask.any():
                cand_r.append(mr[mask])
                cand_c.append(mc[mask])
                cand_v.append(mv[mask])
                cand_a.append(np.full(int(mask.sum()), age + 1, np.int32))
        if not cand_r:
            z = np.zeros(0, np.int32)
            return z, z.copy(), np.zeros(0, np.float32)
        return combine_triples(np.concatenate(cand_r).astype(np.int32),
                               np.concatenate(cand_c).astype(np.int32),
                               np.concatenate(cand_v).astype(np.float32),
                               np.concatenate(cand_a), self.combiner)

    def scan_shard(self, s: int, mem_r, mem_c, mem_v, mem_n: int,
                   mem_host: Optional[Tuple[np.ndarray, ...]] = None):
        """All (row, col, val) of one shard, combined across runs + memtable,
        sorted lex by (row, col). NO flush happens."""
        cand = []
        age = 0
        for rows, cols, vals, fence, bloom, n, block, minr, maxr, hashes in \
                self._iter_runs_oldest_first(s):
            age += 1
            cand.append((np.asarray(rows[:n]), np.asarray(cols[:n]),
                         np.asarray(vals[:n]),
                         np.full(n, age, np.int32)))
        if mem_n:
            if mem_host is not None:
                mr, mc, mv = mem_host
            else:
                mr = np.asarray(mem_r[:mem_n])
                mc = np.asarray(mem_c[:mem_n])
                mv = np.asarray(mem_v[:mem_n])
            cand.append((mr, mc, mv, np.full(len(mr), age + 1, np.int32)))
        if not cand:
            z = np.zeros(0, np.int32)
            return z, z.copy(), np.zeros(0, np.float32)
        r = np.concatenate([x[0] for x in cand]).astype(np.int32)
        c = np.concatenate([x[1] for x in cand]).astype(np.int32)
        v = np.concatenate([x[2] for x in cand]).astype(np.float32)
        a = np.concatenate([x[3] for x in cand])
        return combine_triples(r, c, v, a, self.combiner)

    # --------------------------------------------------------- persistence
    def state_arrays(self) -> dict:
        """Flat name -> np.ndarray map of all run state (for snapshots)."""
        out = {
            "l0_rows": np.asarray(self.l0_rows),
            "l0_cols": np.asarray(self.l0_cols),
            "l0_vals": np.asarray(self.l0_vals),
            "l0_n": self.l0_n.copy(),
            "l0_used": self.l0_used.copy(),
        }
        for i, lv in enumerate(self.levels):
            out[f"lvl{i}_rows"] = np.asarray(lv["rows"])
            out[f"lvl{i}_cols"] = np.asarray(lv["cols"])
            out[f"lvl{i}_vals"] = np.asarray(lv["vals"])
            out[f"lvl{i}_n"] = lv["n"].copy()
        return out

    def load_state(self, arrs: dict) -> None:
        """Restore from ``state_arrays`` output; blooms and fences are
        derived data and get rebuilt (cheaper than persisting them)."""
        self._view_cache.clear()
        l0_rows_np = np.asarray(arrs["l0_rows"])
        self.l0_rows = jnp.asarray(l0_rows_np)
        self.l0_cols = jnp.asarray(arrs["l0_cols"])
        self.l0_vals = jnp.asarray(arrs["l0_vals"])
        self.l0_n = np.asarray(arrs["l0_n"]).astype(np.int64)
        lu = np.asarray(arrs["l0_used"])
        # pre-PR-3 snapshots persisted ONE scalar (lockstep slot counter);
        # broadcast it — every shard then reports the same used count, and
        # empty slots below it stay inert I32_MAX padding as before
        self.l0_used = (np.full((self.S,), int(lu), np.int64)
                        if lu.ndim == 0 else lu.astype(np.int64))
        self.l0_bloom = _bloom_rebuild_fn(self._w0, self._h0,
                                          nested=True)(self.l0_rows)
        self.l0_fence = self.l0_rows[:, :, ::self._b0]
        self.l0_min = l0_rows_np[:, :, 0].astype(np.int64)
        last = np.maximum(self.l0_n - 1, 0)
        self.l0_max = np.take_along_axis(
            l0_rows_np, last[:, :, None].astype(np.int64), axis=2
        )[:, :, 0].astype(np.int64)
        for i, lv in enumerate(self.levels):
            rows_np = np.asarray(arrs[f"lvl{i}_rows"])
            lv["rows"] = jnp.asarray(rows_np)
            lv["cols"] = jnp.asarray(arrs[f"lvl{i}_cols"])
            lv["vals"] = jnp.asarray(arrs[f"lvl{i}_vals"])
            lv["n"] = np.asarray(arrs[f"lvl{i}_n"]).astype(np.int64)
            lv["bloom"] = _bloom_rebuild_fn(lv["words"], lv["hashes"],
                                            nested=False)(lv["rows"])
            lv["fence"] = lv["rows"][:, ::lv["block"]]
            lv["minr"] = rows_np[:, 0].astype(np.int64)
            last = np.maximum(lv["n"] - 1, 0).astype(np.int64)
            lv["maxr"] = rows_np[np.arange(self.S), last].astype(np.int64)
