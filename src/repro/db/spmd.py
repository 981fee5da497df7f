"""SPMD ingest over a device mesh — the distributed BatchWriter.

The paper runs k SPMD ingest processes (pMatlab / DistributedArrays.SPMD)
against Accumulo tablet servers. Here both sides live on the mesh: every
shard along the ingest axis is simultaneously an ingestor (producing a local
triple batch) and a tablet server (owning a key range). One step =

  1. each shard buckets its local batch by owner (range pre-split),
  2. one `all_to_all` exchanges the buckets (BatchWriter -> tablet routing),
  3. each shard minor-compacts what it received (`tablet_insert`).

This is the piece that must *lower and compile* on the production meshes —
exercised by tests/test_spmd_db.py (8 fake devices) and launch/ingest.py
(512-device dry-run).
"""
from __future__ import annotations

import dataclasses
import functools
from time import perf_counter
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels.common import I32_MAX
from ..obs import default_registry, merge_snapshots
from .kvstore import Tablet, shard_of_dev, tablet_insert


def _instrumented(fn, op: str):
    """Host-side step instrumentation: per-process step counters + dispatch
    wall-time histograms (JAX dispatch is async; the histogram measures
    enqueue cost, not device compute). The raw jitted fn stays reachable as
    ``step.__wrapped__`` for callers that re-jit / AOT-lower the step
    (launch/ingest.py does).

    Compile/retrace telemetry: a jitted step's compile-cache growing after
    a call means a fresh input shape signature traced — counted into
    ``lsm_retraces{table=spmd}`` so the registry can assert steady-state
    steps never recompile (same guarantee the fused read path makes)."""
    reg = default_registry()
    c_steps = reg.counter("spmd_steps", op=op)
    c_retrace = reg.counter("lsm_retraces", table="spmd", op=op)
    g_shapes = reg.gauge("lsm_compiled_shapes", table="spmd", op=op)
    h_step = reg.histogram("db_op_latency_s", table="spmd", op=op)
    cache_size = getattr(fn, "_cache_size", None)
    state = {"n": cache_size() if cache_size else 0}

    def step(*args, **kw):
        if not reg.enabled:
            return fn(*args, **kw)
        t0 = perf_counter()
        out = fn(*args, **kw)
        c_steps.inc()
        h_step.observe(perf_counter() - t0)
        if cache_size is not None:
            n = cache_size()
            if n > state["n"]:
                c_retrace.inc(n - state["n"])
                g_shapes.set(n)
                state["n"] = n
        return out

    step.__wrapped__ = fn
    step.__name__ = f"spmd_{op}_step"
    return step


def merge_process_metrics(snapshots) -> dict:
    """Merge per-process ``Registry.snapshot()`` dicts at the host (SPMD
    launchers run one registry per process): counters sum, histograms
    bucket-merge with recomputed percentiles."""
    return merge_snapshots(snapshots)


def _bucket_local(br, bc, bv, num_shards: int, id_capacity: int):
    """Bucket one ingestor's batch into [S, batch_cap] send buffers."""
    bcap = br.shape[0]
    dest = jnp.where(br == I32_MAX, num_shards - 1,
                     shard_of_dev(br, num_shards, id_capacity))
    order = jnp.argsort(dest)  # stable
    dest, sr, sc, sv = dest[order], br[order], bc[order], bv[order]
    starts = jnp.searchsorted(dest, jnp.arange(num_shards, dtype=dest.dtype))
    slot = jnp.arange(bcap, dtype=jnp.int32) - starts[dest].astype(jnp.int32)
    send_r = jnp.full((num_shards, bcap), I32_MAX, jnp.int32).at[dest, slot].set(sr)
    send_c = jnp.full((num_shards, bcap), I32_MAX, jnp.int32).at[dest, slot].set(sc)
    send_v = jnp.zeros((num_shards, bcap), jnp.float32).at[dest, slot].set(sv)
    return send_r, send_c, send_v


def make_spmd_ingest_step(mesh, axis: str, num_shards: int, id_capacity: int,
                          combiner: str = "last", use_pallas: bool = False):
    """Build the jitted SPMD ingest step for ``mesh`` (S = mesh axis size)."""

    def shard_fn(tablet: Tablet, br, bc, bv):
        # local views: tablet leaves [1, cap], batch [1, bcap]
        t = jax.tree.map(lambda x: x[0], tablet)
        send = _bucket_local(br[0], bc[0], bv[0], num_shards, id_capacity)
        recv_r = jax.lax.all_to_all(send[0], axis, 0, 0)
        recv_c = jax.lax.all_to_all(send[1], axis, 0, 0)
        recv_v = jax.lax.all_to_all(send[2], axis, 0, 0)
        new = tablet_insert(t, recv_r.reshape(-1), recv_c.reshape(-1),
                            recv_v.reshape(-1), combiner=combiner,
                            use_pallas=use_pallas)
        return jax.tree.map(lambda x: x[None], new)

    spec_t = Tablet(rows=P(axis, None), cols=P(axis, None),
                    vals=P(axis, None), n=P(axis))
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(spec_t, P(axis, None), P(axis, None),
                                 P(axis, None)),
                       out_specs=spec_t, check_vma=False)
    return _instrumented(jax.jit(fn), "spmd_ingest")


def stacked_empty(num_shards: int, capacity: int) -> Tablet:
    from .kvstore import tablet_empty
    one = tablet_empty(capacity)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_shards,) + x.shape), one)


# --------------------------------------------------------------------------
# LSM write path on the mesh: ingest = all_to_all + L0 append (O(batch)),
# major compaction = shard-local k-way merge of the L0 stack into the level
# run. This is what makes per-step ingest cost independent of table size —
# the legacy step above re-merges the whole tablet every step.
# --------------------------------------------------------------------------
@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "cols", "vals", "k"], meta_fields=[])
@dataclasses.dataclass
class L0Stack:
    """Per-shard stack of L0 sorted runs: [slots, run_cap] + #used runs."""
    rows: jax.Array  # int32[slots, run_cap]
    cols: jax.Array  # int32[slots, run_cap]
    vals: jax.Array  # float32[slots, run_cap]
    k: jax.Array     # int32 number of used slots


def l0_stacked_empty(num_shards: int, slots: int, run_cap: int) -> L0Stack:
    return L0Stack(
        rows=jnp.full((num_shards, slots, run_cap), I32_MAX, jnp.int32),
        cols=jnp.full((num_shards, slots, run_cap), I32_MAX, jnp.int32),
        vals=jnp.zeros((num_shards, slots, run_cap), jnp.float32),
        k=jnp.zeros((num_shards,), jnp.int32),
    )


def _l0_spec(axis: str) -> L0Stack:
    return L0Stack(rows=P(axis, None, None), cols=P(axis, None, None),
                   vals=P(axis, None, None), k=P(axis))


def make_spmd_lsm_ingest_step(mesh, axis: str, num_shards: int,
                              id_capacity: int, combiner: str = "last"):
    """LSM ingest step: route a batch, sort + dedup it, append as one L0 run.

    Per-shard cost is O(S·bcap log) regardless of how much data the table
    already holds; compaction is deferred to ``make_spmd_lsm_compact_step``.
    The caller MUST compact when ``k`` reaches ``slots`` before the next
    step: a step against a full stack is a no-op for that shard (``k``
    saturates at ``slots`` so the host check keeps firing, and the batch
    is NOT ingested — re-submit it after compacting).
    """
    from .kvstore import _dedup_combine

    def shard_fn(l0: L0Stack, br, bc, bv):
        me = jax.tree.map(lambda x: x[0], l0)
        send = _bucket_local(br[0], bc[0], bv[0], num_shards, id_capacity)
        rr = jax.lax.all_to_all(send[0], axis, 0, 0).reshape(-1)
        rc = jax.lax.all_to_all(send[1], axis, 0, 0).reshape(-1)
        rv = jax.lax.all_to_all(send[2], axis, 0, 0).reshape(-1)
        sr, sc, sv = jax.lax.sort((rr, rc, rv), num_keys=2, is_stable=True)
        keep, out_v = _dedup_combine(sr, sc, sv, combiner)
        cap = sr.shape[0]
        pos = jnp.cumsum(keep) - 1
        idx = jnp.where(keep, pos, cap)
        run_r = jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(sr, mode="drop")
        run_c = jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(sc, mode="drop")
        run_v = jnp.zeros((cap,), jnp.float32).at[idx].set(out_v, mode="drop")
        slots = me.rows.shape[0]
        # full stack: the .at[slots] scatter drops (out of bounds) and k
        # saturates — see the driver contract in the docstring
        new = L0Stack(rows=me.rows.at[me.k].set(run_r, mode="drop"),
                      cols=me.cols.at[me.k].set(run_c, mode="drop"),
                      vals=me.vals.at[me.k].set(run_v, mode="drop"),
                      k=jnp.minimum(me.k + 1, slots))
        return jax.tree.map(lambda x: x[None], new)

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(_l0_spec(axis), P(axis, None), P(axis, None),
                                 P(axis, None)),
                       out_specs=_l0_spec(axis), check_vma=False)
    return _instrumented(jax.jit(fn), "spmd_lsm_ingest")


def _bucket_local_tablets(br, bc, bv, splits, owners, num_shards: int):
    """Tablet-map routing variant of ``_bucket_local``: the owner shard is
    ``owners[searchsorted(splits, id, 'right')]`` with ``splits``/``owners``
    as DEVICE OPERANDS (``TabletMap.device_routing`` pads them to a static
    max tablet count; padded split slots hold ``id_capacity``, which no
    valid id reaches). A split or move changes array VALUES, never shapes
    — rebalancing the mesh does not retrace the compiled ingest step."""
    bcap = br.shape[0]
    t = jnp.searchsorted(splits, br, side="right")
    dest = jnp.where(br == I32_MAX, num_shards - 1, owners[t])
    order = jnp.argsort(dest)  # stable
    dest, sr, sc, sv = dest[order], br[order], bc[order], bv[order]
    starts = jnp.searchsorted(dest, jnp.arange(num_shards, dtype=dest.dtype))
    slot = jnp.arange(bcap, dtype=jnp.int32) - starts[dest].astype(jnp.int32)
    send_r = jnp.full((num_shards, bcap), I32_MAX, jnp.int32).at[dest, slot].set(sr)
    send_c = jnp.full((num_shards, bcap), I32_MAX, jnp.int32).at[dest, slot].set(sc)
    send_v = jnp.zeros((num_shards, bcap), jnp.float32).at[dest, slot].set(sv)
    return send_r, send_c, send_v


def make_spmd_tablet_ingest_step(mesh, axis: str, num_shards: int,
                                 combiner: str = "last"):
    """LSM ingest step routed by a DYNAMIC tablet map instead of the
    static range hash: same shape as ``make_spmd_lsm_ingest_step``
    (bucket → all_to_all → sort/dedup → L0 append, same full-stack
    contract), but each call takes the map's current ``(splits, owners)``
    routing arrays as replicated operands. The host rebalances by calling
    ``TabletMap.device_routing(max_T)`` again and passing the new arrays
    — no recompile, because only values changed (see
    ``_bucket_local_tablets``)."""
    from .kvstore import _dedup_combine

    def shard_fn(l0: L0Stack, br, bc, bv, splits, owners):
        me = jax.tree.map(lambda x: x[0], l0)
        send = _bucket_local_tablets(br[0], bc[0], bv[0], splits, owners,
                                     num_shards)
        rr = jax.lax.all_to_all(send[0], axis, 0, 0).reshape(-1)
        rc = jax.lax.all_to_all(send[1], axis, 0, 0).reshape(-1)
        rv = jax.lax.all_to_all(send[2], axis, 0, 0).reshape(-1)
        sr, sc, sv = jax.lax.sort((rr, rc, rv), num_keys=2, is_stable=True)
        keep, out_v = _dedup_combine(sr, sc, sv, combiner)
        cap = sr.shape[0]
        pos = jnp.cumsum(keep) - 1
        idx = jnp.where(keep, pos, cap)
        run_r = jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(sr, mode="drop")
        run_c = jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(sc, mode="drop")
        run_v = jnp.zeros((cap,), jnp.float32).at[idx].set(out_v, mode="drop")
        slots = me.rows.shape[0]
        new = L0Stack(rows=me.rows.at[me.k].set(run_r, mode="drop"),
                      cols=me.cols.at[me.k].set(run_c, mode="drop"),
                      vals=me.vals.at[me.k].set(run_v, mode="drop"),
                      k=jnp.minimum(me.k + 1, slots))
        return jax.tree.map(lambda x: x[None], new)

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(_l0_spec(axis), P(axis, None), P(axis, None),
                                 P(axis, None), P(), P()),
                       out_specs=_l0_spec(axis), check_vma=False)
    return _instrumented(jax.jit(fn), "spmd_tablet_ingest")


def make_spmd_lsm_pair_ingest_step(mesh, axis: str, num_shards: int,
                                   id_capacity: int,
                                   combiner: str = "last"):
    """Dual-ingest step for an engine-maintained transpose pair: ONE jit
    routes the batch twice — forward triples by row owner into ``A``'s L0
    stack, swapped triples by col owner into ``A^T``'s — so both sides of
    the pair advance in the same dispatch (the mesh analogue of the local
    engine's pair-tagged WAL frame: one step, both siblings, or neither).

    Same full-stack contract as ``make_spmd_lsm_ingest_step``: when either
    stack's ``k`` hits ``slots``, compact BOTH (each via
    ``make_spmd_lsm_compact_step``) and re-submit the batch.
    """
    from .kvstore import _dedup_combine

    def routed_run(br, bc, bv):
        """all_to_all by row owner, then sort+dedup into one L0 run."""
        send = _bucket_local(br, bc, bv, num_shards, id_capacity)
        rr = jax.lax.all_to_all(send[0], axis, 0, 0).reshape(-1)
        rc = jax.lax.all_to_all(send[1], axis, 0, 0).reshape(-1)
        rv = jax.lax.all_to_all(send[2], axis, 0, 0).reshape(-1)
        sr, sc, sv = jax.lax.sort((rr, rc, rv), num_keys=2, is_stable=True)
        keep, out_v = _dedup_combine(sr, sc, sv, combiner)
        cap = sr.shape[0]
        pos = jnp.cumsum(keep) - 1
        idx = jnp.where(keep, pos, cap)
        return (jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(sr, mode="drop"),
                jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(sc, mode="drop"),
                jnp.zeros((cap,), jnp.float32).at[idx].set(out_v, mode="drop"))

    def append(me: L0Stack, run) -> L0Stack:
        slots = me.rows.shape[0]
        return L0Stack(rows=me.rows.at[me.k].set(run[0], mode="drop"),
                       cols=me.cols.at[me.k].set(run[1], mode="drop"),
                       vals=me.vals.at[me.k].set(run[2], mode="drop"),
                       k=jnp.minimum(me.k + 1, slots))

    def shard_fn(l0: L0Stack, l0t: L0Stack, br, bc, bv):
        me = jax.tree.map(lambda x: x[0], l0)
        met = jax.tree.map(lambda x: x[0], l0t)
        # rows and cols share one id space, so the SAME shard_of routes
        # both directions; the transpose leg just swaps the key roles
        fwd = routed_run(br[0], bc[0], bv[0])
        twd = routed_run(bc[0], br[0], bv[0])
        return (jax.tree.map(lambda x: x[None], append(me, fwd)),
                jax.tree.map(lambda x: x[None], append(met, twd)))

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(_l0_spec(axis), _l0_spec(axis), P(axis, None),
                                 P(axis, None), P(axis, None)),
                       out_specs=(_l0_spec(axis), _l0_spec(axis)),
                       check_vma=False)
    return _instrumented(jax.jit(fn), "spmd_lsm_pair_ingest")


def make_spmd_lsm_query_step(mesh, axis: str, combiner: str = "last",
                             max_return: int = 64, q_tile: int = None):
    """Fused point reads on the mesh: ONE shard_map'd jit searches each
    shard's level run plus its ENTIRE L0 stack and combines the candidates
    on-device — the distributed analogue of the local engine's
    ``query_shard_fused`` (no per-run dispatches, no host combine).

    Queries arrive owner-routed as ``q[S, Qb]`` (pad = -1, which matches
    no row id); each shard answers only its slice. Age order: level run
    (oldest) = 1, L0 slot k = 2 + k (slot k flushed before k + 1). Empty
    L0 slots are inert I32_MAX padding. Returns
    (cols[S, Qb, W], vals[S, Qb, W], keep[S, Qb, W]) with
    W = (slots + 1) * max_return: per query, kept entries are its combined
    (col, val) results, cols ascending.

    ``q_tile`` mirrors the local engine's query tiling: batches wider than
    it are split along the query axis into ``q_tile``-wide blocks (the
    last padded with -1), each served by the SAME compiled step (one jit
    cache entry regardless of batch width) and the per-tile outputs
    concatenated back to ``Qb``. ``None`` keeps one dispatch per batch.
    """
    from .kvstore import _dedup_combine

    def probe(rows, cols, vals, q):
        """Direct rank search of one sorted run (no fence metadata in the
        mesh-side state; the run is device-local so the full searchsorted
        is one vectorized pass)."""
        cap = rows.shape[0]
        start = jnp.searchsorted(rows, q, side="left").astype(jnp.int32)
        end = jnp.searchsorted(rows, q, side="right").astype(jnp.int32)
        idx = start[:, None] + jnp.arange(max_return, dtype=jnp.int32)
        ok = idx < end[:, None]
        idxc = jnp.clip(idx, 0, cap - 1)
        return cols[idxc], vals[idxc], ok

    def shard_fn(l0: L0Stack, level: Tablet, q):
        me = jax.tree.map(lambda x: x[0], l0)
        lv = jax.tree.map(lambda x: x[0], level)
        qq = q[0]
        n_q = qq.shape[0]
        slots = me.rows.shape[0]
        c_lv, v_lv, ok_lv = probe(lv.rows, lv.cols, lv.vals, qq)
        c_l0, v_l0, ok_l0 = jax.vmap(
            lambda r, c, v: probe(r, c, v, qq))(me.rows, me.cols, me.vals)
        seg_c = [c_lv] + [c_l0[k] for k in range(slots)]
        seg_v = [v_lv] + [v_l0[k] for k in range(slots)]
        seg_ok = [ok_lv] + [ok_l0[k] for k in range(slots)]
        seg_age = [jnp.full((n_q, max_return), a + 1, jnp.int32)
                   for a in range(slots + 1)]
        cols_all = jnp.concatenate(seg_c, axis=1)
        vals_all = jnp.concatenate(seg_v, axis=1)
        ok_all = jnp.concatenate(seg_ok, axis=1)
        age_all = jnp.concatenate(seg_age, axis=1)
        col_m = jnp.where(ok_all, cols_all, I32_MAX)
        col_s, _, val_s = jax.lax.sort(
            (col_m, age_all, vals_all), dimension=1, num_keys=2)
        keep, out_v = jax.vmap(
            lambda r, v: _dedup_combine(r, jnp.zeros_like(r), v, combiner)
        )(col_s, val_s)
        return (col_s[None], jnp.where(keep, out_v, 0.0)[None], keep[None])

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(_l0_spec(axis), Tablet(rows=P(axis, None),
                                                        cols=P(axis, None),
                                                        vals=P(axis, None),
                                                        n=P(axis)),
                                 P(axis, None)),
                       out_specs=(P(axis, None, None), P(axis, None, None),
                                  P(axis, None, None)), check_vma=False)
    base = jax.jit(fn)
    if q_tile is None:
        return _instrumented(base, "spmd_lsm_query")

    def tiled(l0, level, q):
        n_q = q.shape[1]
        if n_q <= q_tile:
            return base(l0, level, q)
        outs = []
        for t in range(0, n_q, q_tile):
            q_blk = q[:, t:t + q_tile]
            pad = q_tile - q_blk.shape[1]
            if pad:
                q_blk = jnp.pad(q_blk, ((0, 0), (0, pad)),
                                constant_values=-1)
            outs.append(base(l0, level, q_blk))
        cols = jnp.concatenate([o[0] for o in outs], axis=1)[:, :n_q]
        vals = jnp.concatenate([o[1] for o in outs], axis=1)[:, :n_q]
        keep = jnp.concatenate([o[2] for o in outs], axis=1)[:, :n_q]
        return cols, vals, keep

    tiled.__wrapped__ = base
    return _instrumented(tiled, "spmd_lsm_query")


def make_spmd_lsm_scan_step(mesh, axis: str, combiner: str = "last",
                            width: int = 128,
                            transpose_output: bool = False):
    """Fused range scans on the mesh: ONE shard_map'd jit answers a
    ``[lo, hi)`` row-range scan per shard over its level run plus its
    ENTIRE L0 stack, merged-deduped on-device — the distributed analogue
    of the local engine's ``scan_shard_fused`` (no id-list point
    expansion, no per-run dispatches, no host combine).

    Bounds arrive per shard as ``bounds[S, 2]`` (each shard answers its
    own ``[lo, hi)`` slice; a shard outside the global range passes an
    empty interval ``lo == hi``). Both endpoints rank with ``side='left'``
    (``hi`` exclusive). Age order matches the point step: level run
    (oldest) = 1, L0 slot k = 2 + k. Returns
    (rows[S, W], cols[S, W], vals[S, W], keep[S, W], cnt_max[S]) with
    W = (slots + 1) * width, kept entries sorted lex by (row, col);
    ``cnt_max`` > width means some run's slice overflowed the window —
    re-make the step wider (batch-scanner semantics).

    ``transpose_output=True`` serves COLUMN-range scans over a pair's
    transpose sibling stacks (see ``make_spmd_lsm_pair_ingest_step``):
    the scan ranks over the sibling's row axis (= ``A``'s columns) and
    the outputs come back swapped into ``A`` orientation — rows are the
    sibling's cols and vice versa, kept entries sorted by (col, row)."""
    from .kvstore import _dedup_combine

    def window(rows, cols, vals, lohi):
        cap = rows.shape[0]
        start = jnp.searchsorted(rows, lohi[0], side="left").astype(jnp.int32)
        end = jnp.searchsorted(rows, lohi[1], side="left").astype(jnp.int32)
        idx = start + jnp.arange(width, dtype=jnp.int32)
        idxc = jnp.clip(idx, 0, cap - 1)
        return rows[idxc], cols[idxc], vals[idxc], idx < end, end - start

    def shard_fn(l0: L0Stack, level: Tablet, bounds):
        me = jax.tree.map(lambda x: x[0], l0)
        lv = jax.tree.map(lambda x: x[0], level)
        lohi = bounds[0]
        slots = me.rows.shape[0]
        r_lv, c_lv, v_lv, ok_lv, n_lv = window(lv.rows, lv.cols, lv.vals,
                                               lohi)
        r_l0, c_l0, v_l0, ok_l0, n_l0 = jax.vmap(
            lambda r, c, v: window(r, c, v, lohi))(me.rows, me.cols, me.vals)
        rows_all = jnp.concatenate([r_lv] + [r_l0[k] for k in range(slots)])
        cols_all = jnp.concatenate([c_lv] + [c_l0[k] for k in range(slots)])
        vals_all = jnp.concatenate([v_lv] + [v_l0[k] for k in range(slots)])
        ok_all = jnp.concatenate([ok_lv] + [ok_l0[k] for k in range(slots)])
        ages = jnp.concatenate(
            [jnp.full((width,), a + 1, jnp.int32) for a in range(slots + 1)])
        row_m = jnp.where(ok_all, rows_all, I32_MAX)
        col_m = jnp.where(ok_all, cols_all, I32_MAX)
        row_s, col_s, _, val_s = jax.lax.sort(
            (row_m, col_m, ages, vals_all), dimension=0, num_keys=3)
        keep, out_v = _dedup_combine(row_s, col_s, val_s, combiner)
        cnt_max = jnp.maximum(jnp.max(n_l0), n_lv)
        if transpose_output:  # sibling rows ARE A's cols: swap back
            row_s, col_s = col_s, row_s
        return (row_s[None], col_s[None],
                jnp.where(keep, out_v, 0.0)[None], keep[None], cnt_max[None])

    spec_t = Tablet(rows=P(axis, None), cols=P(axis, None),
                    vals=P(axis, None), n=P(axis))
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(_l0_spec(axis), spec_t, P(axis, None)),
                       out_specs=(P(axis, None), P(axis, None), P(axis, None),
                                  P(axis, None), P(axis)), check_vma=False)
    return _instrumented(jax.jit(fn), "spmd_lsm_scan")


def make_spmd_lsm_compact_step(mesh, axis: str, combiner: str = "last",
                               use_pallas: bool = False):
    """Major compaction on the mesh: k-way merge each shard's L0 runs with
    its level run (Tablet) into a new level run; L0 empties."""
    from ..kernels.common import INTERPRET
    from ..kernels.merge_rank import kway_merge
    from .kvstore import _dedup_combine

    def shard_fn(l0: L0Stack, level: Tablet):
        me = jax.tree.map(lambda x: x[0], l0)
        lv = jax.tree.map(lambda x: x[0], level)
        slots = me.rows.shape[0]
        runs = [(lv.rows, lv.cols, lv.vals)]  # level run = oldest
        runs += [(me.rows[i], me.cols[i], me.vals[i]) for i in range(slots)]
        mr, mc, mv = kway_merge(runs, use_pallas=use_pallas,
                                interpret=INTERPRET)
        keep, out_v = _dedup_combine(mr, mc, mv, combiner)
        cap = lv.rows.shape[0]
        pos = jnp.cumsum(keep) - 1
        idx = jnp.where(keep, pos, cap)  # host checks n for overflow
        new_lv = Tablet(
            rows=jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(mr, mode="drop"),
            cols=jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(mc, mode="drop"),
            vals=jnp.zeros((cap,), jnp.float32).at[idx].set(out_v, mode="drop"),
            n=keep.sum().astype(jnp.int32),
        )
        empty = L0Stack(rows=jnp.full_like(me.rows, I32_MAX),
                        cols=jnp.full_like(me.cols, I32_MAX),
                        vals=jnp.zeros_like(me.vals),
                        k=jnp.zeros_like(me.k))
        return (jax.tree.map(lambda x: x[None], empty),
                jax.tree.map(lambda x: x[None], new_lv))

    spec_t = Tablet(rows=P(axis, None), cols=P(axis, None),
                    vals=P(axis, None), n=P(axis))
    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(_l0_spec(axis), spec_t),
                       out_specs=(_l0_spec(axis), spec_t), check_vma=False)
    return _instrumented(jax.jit(fn), "spmd_lsm_compact")
