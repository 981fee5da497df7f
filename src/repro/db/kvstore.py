"""Mesh-sharded sorted key-value store — the Accumulo analogue (DESIGN §2).

Each *tablet* holds (row_id, col_id) -> value entries on one mesh shard,
range-partitioned by row id (pre-split tablets, as in the 100M-inserts/s
Accumulo+D4M setup the paper cites). One storage engine serves every
table (see ``src/repro/db/README.md``): leveled sorted runs
(``repro.db.lsm``). Memtable flushes are O(memtable), major compactions
k-way merge runs (Pallas ``merge_rank`` under ``use_pallas``), reads go
through bloom filters + fence pointers in one fused dispatch per shard
without flushing, and a WAL + snapshots provide crash recovery.

Duplicate keys combine with Accumulo iterator semantics (last-wins
versioning, sum/min/max combiners — ``db.iterators``).

All device functions are jit-compatible (static capacities, explicit valid
counts, I32_MAX key padding). Two drivers exist:
  * ``ShardedTable``      — stacked [S, cap] shards on one device.
  * ``repro.db.spmd``     — shard_map driver with all_to_all mutation routing
                             for real meshes (and the multi-pod dry-run);
                             ``Tablet`` and ``tablet_insert`` below serve
                             its single-run ingest and level runs.
"""
from __future__ import annotations

import dataclasses
import functools
from time import perf_counter
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.common import I32_MAX, INTERPRET
from ..obs import default_registry, default_tracer
from ..kernels.merge_rank import merge_sorted
from ..kernels.merge_rank.ref import merge_sorted_ref
from ..kernels.segment_reduce import segment_sum

COMBINERS = ("last", "sum", "min", "max")


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    """Engine/topology configuration for one store.

    Built ONCE (``db.connector.dbsetup``) and passed by reference down
    the DBserver → Table → ShardedTable chain instead of the old
    per-layer kwargs relay; round-trips through the snapshot manifest
    (``lsm.manifest``) so recovery rebuilds stores from the same record
    without re-listing fields by hand. Per-table knobs that genuinely
    vary per table (combiner, bloom sizing, wal_dir) stay constructor
    arguments.

    ``transpose=True`` makes the store maintain its transpose ``A^T`` as
    an engine-level sibling shard set (``ShardedTable.t_store``): every
    ingest batch lands in both through ONE pair-tagged WAL record, and
    column selectors become fence-rangeable scans on the sibling.

    ``dynamic_tablets=True`` replaces the static ``shard_of`` range hash
    with a mutable ``TabletMap`` (``db.tablets``): hot row ranges split
    at fence-derived median keys and tablets migrate between shards to
    balance Zipfian load (``split_tablet`` / ``move_tablet`` /
    ``maybe_rebalance``). The map rides in the snapshot manifest
    (format 3) and splits/moves journal as WAL meta frames, so recovery
    rebuilds the exact topology. Off by default: the static path is
    byte-for-byte unchanged (WAL frames stay untagged).

    ``engine`` and ``fused_reads`` each have one legal value: the LSM
    engine and its fused read path are the only ones. They stay fields
    so configs and manifests that name them keep loading; any other
    value raises ``ValueError``.
    """
    num_shards: int = 4
    capacity_per_shard: int = 1 << 18
    batch_cap: int = 1 << 15
    id_capacity: int = 1 << 22
    use_pallas: bool = False
    engine: Literal["lsm"] = "lsm"
    fused_reads: Literal[True] = True
    fused_q_limit: int = 512
    l0_slots: int = 4
    fanout: int = 4
    memtable_cap: int = None
    transpose: bool = False
    dynamic_tablets: bool = False

    def __post_init__(self):
        if self.engine != "lsm":
            raise ValueError(
                f"engine={self.engine!r} is not supported: the LSM engine "
                "(engine='lsm') is the only storage engine")
        if self.fused_reads is not True:
            raise ValueError(
                f"fused_reads={self.fused_reads!r} is not supported: reads "
                "always take the fused path (fused_reads=True)")
        q = self.fused_q_limit
        if (not isinstance(q, (int, np.integer)) or isinstance(q, bool)
                or q <= 0):
            raise ValueError(
                f"fused_q_limit={q!r}: the fused read path's query tile "
                "must be a positive int")

    def replace(self, **kw) -> "StoreConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_manifest(cls, cfg: dict) -> "StoreConfig":
        """Build from a manifest config dict. Tolerates the legacy
        ``mem_cap`` key and ignores per-table fields stored alongside."""
        known = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in known}
        if "memtable_cap" not in kw and "mem_cap" in cfg:
            kw["memtable_cap"] = cfg["mem_cap"]
        return cls(**kw)


@functools.partial(
    jax.tree_util.register_dataclass, data_fields=["rows", "cols", "vals", "n"],
    meta_fields=[],
)
@dataclasses.dataclass
class Tablet:
    rows: jax.Array  # int32[cap]; valid prefix sorted lex by (row, col); pad I32_MAX
    cols: jax.Array  # int32[cap]
    vals: jax.Array  # float32[cap]
    n: jax.Array     # int32 valid count


def tablet_empty(capacity: int) -> Tablet:
    return Tablet(
        rows=jnp.full((capacity,), I32_MAX, jnp.int32),
        cols=jnp.full((capacity,), I32_MAX, jnp.int32),
        vals=jnp.zeros((capacity,), jnp.float32),
        n=jnp.zeros((), jnp.int32),
    )


def _dedup_combine(mr, mc, mv, combiner: str):
    """Collapse adjacent duplicate keys of a merged sorted run."""
    L = mr.shape[0]
    valid = mr != I32_MAX
    new = jnp.ones((L,), bool).at[1:].set((mr[1:] != mr[:-1]) | (mc[1:] != mc[:-1]))
    if combiner == "last":
        keep = valid & jnp.concatenate([new[1:], jnp.ones((1,), bool)])
        out_v = mv
    else:
        seg = jnp.cumsum(new) - 1
        contrib = jnp.where(valid, mv, 0.0 if combiner == "sum" else jnp.nan)
        if combiner == "sum":
            agg = jnp.zeros((L,), mv.dtype).at[seg].add(contrib)
        elif combiner == "min":
            agg = jnp.full((L,), jnp.inf, mv.dtype).at[seg].min(
                jnp.where(valid, mv, jnp.inf))
        elif combiner == "max":
            agg = jnp.full((L,), -jnp.inf, mv.dtype).at[seg].max(
                jnp.where(valid, mv, -jnp.inf))
        else:
            raise ValueError(f"unknown combiner {combiner!r}")
        keep = valid & new
        out_v = agg[seg]
    return keep, out_v


@functools.partial(jax.jit, static_argnames=("combiner", "use_pallas"))
def tablet_insert(t: Tablet, br, bc, bv, combiner: str = "last",
                  use_pallas: bool = True) -> Tablet:
    """Minor compaction: merge a batch (pads = I32_MAX keys) into the run.

    Returns the new tablet; ``new.n`` may exceed capacity — the host MUST
    check for overflow (Accumulo back-pressure analogue).
    """
    br, bc, bv = jax.lax.sort((br, bc, bv), num_keys=2, is_stable=True)
    if use_pallas:
        mr, mc, mv = merge_sorted(t.rows, t.cols, t.vals, br, bc, bv,
                                  interpret=INTERPRET)
    else:
        mr, mc, mv = merge_sorted_ref(t.rows, t.cols, t.vals, br, bc, bv)
    keep, out_v = _dedup_combine(mr, mc, mv, combiner)
    cap = t.rows.shape[0]
    pos = jnp.cumsum(keep) - 1
    idx = jnp.where(keep, pos, cap)  # dropped when not kept / overflowing
    return Tablet(
        rows=jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(mr, mode="drop"),
        cols=jnp.full((cap,), I32_MAX, jnp.int32).at[idx].set(mc, mode="drop"),
        vals=jnp.zeros((cap,), jnp.float32).at[idx].set(out_v, mode="drop"),
        n=keep.sum().astype(jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def degree_update(deg: jax.Array, ids: jax.Array, weights: jax.Array,
                  use_pallas: bool = True) -> jax.Array:
    """Combiner-iterator analogue: accumulate counts into a dense degree row."""
    if use_pallas:
        return deg + segment_sum(ids, weights, n_segments=deg.shape[0],
                                 interpret=INTERPRET)
    valid = ids >= 0
    return deg.at[jnp.where(valid, ids, 0)].add(jnp.where(valid, weights, 0.0))


# --------------------------------------------------------------------------
# Range partitioning (pre-split tablets)
# --------------------------------------------------------------------------
def shard_of(ids: np.ndarray, num_shards: int, id_capacity: int) -> np.ndarray:
    """Owner shard by range partition of the id space (uniform pre-split)."""
    return np.minimum(
        (ids.astype(np.int64) * num_shards) // id_capacity, num_shards - 1
    ).astype(np.int32)


def shard_of_dev(ids: jax.Array, num_shards: int, id_capacity: int) -> jax.Array:
    """Device-side owner computation (ids * S must fit int32: S * id_capacity
    < 2**31, enforced by the connector's capacity config)."""
    return jnp.minimum((ids * num_shards) // id_capacity,
                       num_shards - 1).astype(jnp.int32)


def _memtable_append(mem_r, mem_c, mem_v, counts, br, bc, bv):
    """Append routed batches [S, bcap] into per-shard memtables [S, mcap]
    at the current write offsets; returns new buffers + counts."""
    s, mcap = mem_r.shape
    valid = br != I32_MAX
    pos_in_row = jnp.cumsum(valid, axis=1) - 1
    target = jnp.where(valid, counts[:, None] + pos_in_row, mcap)
    rows_idx = jnp.broadcast_to(jnp.arange(s)[:, None], br.shape)
    mem_r = mem_r.at[rows_idx, target].set(br, mode="drop")
    mem_c = mem_c.at[rows_idx, target].set(bc, mode="drop")
    mem_v = mem_v.at[rows_idx, target].set(bv, mode="drop")
    return mem_r, mem_c, mem_v, counts + valid.sum(axis=1).astype(counts.dtype)


def _memtable_append_flat(mem_r, mem_c, mem_v, counts, dest, slot, r, c, v):
    """Flat append: entry i of the (dest-sorted) batch lands at
    memtable[dest_i, counts[dest_i] + slot_i]. Pads carry dest == S and are
    dropped — work is O(batch), not O(shards × batch_cap)."""
    s = mem_r.shape[0]
    valid = dest < s
    dsafe = jnp.where(valid, dest, 0)
    col = jnp.where(valid, counts[dsafe] + slot, mem_r.shape[1])
    mem_r = mem_r.at[dest, col].set(r, mode="drop")
    mem_c = mem_c.at[dest, col].set(c, mode="drop")
    mem_v = mem_v.at[dest, col].set(v, mode="drop")
    add = jnp.zeros_like(counts).at[dsafe].add(valid.astype(counts.dtype))
    return mem_r, mem_c, mem_v, counts + add


_APPEND = jax.jit(_memtable_append)
_APPEND_FLAT = jax.jit(_memtable_append_flat)


class ShardedTable:
    """Stacked-tablet driver: S tablet servers' state on the local device.

    Simulates S SPMD ingestors for the paper's Fig. 3 study; the distributed
    execution path with identical per-shard code is ``repro.db.spmd``.

    Writes land in a per-shard *memtable* (unsorted fixed buffer); a minor
    compaction happens only when the memtable fills. Under it sit leveled
    sorted runs (``db.lsm``): a flush costs O(memtable), major compactions
    k-way merge runs via the Pallas merge_rank kernel, and reads serve
    from memtable + runs through bloom filters and fence pointers in one
    fused dispatch per shard, WITHOUT flushing.

    With ``wal_dir`` set, every ``insert`` batch is logged to an
    append-only WAL before it reaches the memtable, ``checkpoint()``
    snapshots the runs, and ``db.lsm.recover(dir)`` rebuilds the table
    after a crash.
    """

    def __init__(self, name: str, num_shards: int = None,
                 capacity_per_shard: int = None, batch_cap: int = None,
                 id_capacity: int = None, combiner: str = "last",
                 use_pallas: bool = None, memtable_cap: int = None,
                 engine: str = None, l0_slots: int = None, fanout: int = None,
                 wal_dir: str = None, fused_reads: bool = None,
                 fused_q_limit: int = None, bloom_bits_per_key=None,
                 bloom_hashes=None, transpose: bool = None,
                 dynamic_tablets: bool = None,
                 config: StoreConfig = None):
        # use_pallas=True runs the TPU kernels (interpret-mode on CPU — for
        # validation only; the XLA path is the CPU-performance path)
        assert combiner in COMBINERS
        # config is the canonical record (StoreConfig defaults when absent);
        # explicit kwargs override it so existing call sites keep working
        cfg = config if config is not None else StoreConfig()
        overrides = {k: v for k, v in dict(
            num_shards=num_shards, capacity_per_shard=capacity_per_shard,
            batch_cap=batch_cap, id_capacity=id_capacity,
            use_pallas=use_pallas, memtable_cap=memtable_cap, engine=engine,
            l0_slots=l0_slots, fanout=fanout, fused_reads=fused_reads,
            fused_q_limit=fused_q_limit, transpose=transpose,
            dynamic_tablets=dynamic_tablets).items()
            if v is not None}
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        self.config = cfg
        self.name = name
        self.S = cfg.num_shards
        self.cap = cfg.capacity_per_shard
        self.batch_cap = cfg.batch_cap
        self.id_capacity = cfg.id_capacity
        self.combiner = combiner
        # fused_q_limit is the QUERY TILE of the fused read path: batches
        # beyond the tiny point bucket pad UP to it and larger ones split
        # into fixed-size tiles (one jit cache entry serves every batch
        # size, block bloom-gated per run)
        self.fused_q_limit = cfg.fused_q_limit
        self.mem_cap = cfg.memtable_cap or max(
            cfg.batch_cap * 4, min(cfg.capacity_per_shard, 1 << 18))
        self._closed = False
        # engine-maintained transpose sibling: rows and cols share one id
        # space (one keydict), so A^T routes through the same shard_of —
        # no second dictionary. The sibling has NO WAL of its own: the
        # primary logs each batch once, pair-tagged (see insert()).
        self.t_store = None
        if cfg.transpose:
            # the sibling keeps STATIC col routing even when the primary
            # runs dynamic tablets: the tablet map partitions the ROW id
            # space; the sibling's keys are our cols
            self.t_store = ShardedTable(
                name + "@T", combiner=combiner,
                bloom_bits_per_key=bloom_bits_per_key,
                bloom_hashes=bloom_hashes,
                config=dataclasses.replace(cfg, transpose=False,
                                           dynamic_tablets=False,
                                           memtable_cap=self.mem_cap))
        # dynamic tablets: mutable row-range → tablet → owner map replacing
        # the static shard_of hash; starts as its exact equivalent (one
        # tablet per shard, same boundaries) until the first split
        self.tablet_map = None
        self._migrating = False
        if cfg.dynamic_tablets:
            from .tablets import TabletMap
            self.tablet_map = TabletMap.uniform(cfg.num_shards,
                                                cfg.id_capacity)
        # per-batch latency histograms + per-shard op counters/histograms
        # (repro.obs; series reset here so a fresh table reads zeros)
        self._reg = default_registry()
        self._trace = default_tracer()
        self._h_ingest = self._reg.histogram("db_op_latency_s", table=name,
                                             op="ingest")
        self._h_query = self._reg.histogram("db_op_latency_s", table=name,
                                            op="query")
        self._h_scan = self._reg.histogram("db_op_latency_s", table=name,
                                           op="scan")
        # whole-table scans (the O(nnz) path selectors should AVOID —
        # the one-dispatch tests assert this stays flat on routed reads)
        self._c_full_scans = self._reg.counter("db_full_scans", table=name)
        self._c_shard_ingest = [
            self._reg.counter("db_ingest_entries", table=name, shard=s)
            for s in range(self.S)]
        self._c_shard_query = [
            self._reg.counter("db_point_queries", table=name, shard=s)
            for s in range(self.S)]
        self._c_shard_scan = [
            self._reg.counter("db_range_scans", table=name, shard=s)
            for s in range(self.S)]
        self._h_shard_query = [
            self._reg.histogram("db_shard_op_latency_s", table=name,
                                shard=s, op="query")
            for s in range(self.S)]
        self._h_shard_scan = [
            self._reg.histogram("db_shard_op_latency_s", table=name,
                                shard=s, op="scan")
            for s in range(self.S)]
        self._c_tablet_splits = self._reg.counter("lsm_tablet_splits",
                                                  table=name)
        self._c_tablet_moves = self._reg.counter("lsm_tablet_moves",
                                                 table=name)
        self._c_tablet_merges = self._reg.counter("lsm_tablet_merges",
                                                  table=name)
        for inst in ([self._h_ingest, self._h_query, self._h_scan,
                      self._c_full_scans, self._c_tablet_splits,
                      self._c_tablet_moves, self._c_tablet_merges]
                     + self._c_shard_ingest + self._c_shard_query
                     + self._c_shard_scan + self._h_shard_query
                     + self._h_shard_scan):
            inst.reset()
        from .lsm.bloom import BITS_PER_KEY, NUM_HASHES
        from .lsm.engine import LSMRuns
        self._runs = LSMRuns(
            cfg.num_shards, cfg.capacity_per_shard, self.mem_cap, combiner,
            cfg.use_pallas, l0_slots=cfg.l0_slots, fanout=cfg.fanout,
            bloom_bits_per_key=(BITS_PER_KEY if bloom_bits_per_key is None
                                else bloom_bits_per_key),
            bloom_hashes=(NUM_HASHES if bloom_hashes is None
                          else bloom_hashes),
            id_capacity=cfg.id_capacity, name=name)
        self._mem_r = jnp.full((self.S, self.mem_cap), I32_MAX, jnp.int32)
        self._mem_c = jnp.full((self.S, self.mem_cap), I32_MAX, jnp.int32)
        self._mem_v = jnp.zeros((self.S, self.mem_cap), jnp.float32)
        self._mem_n = np.zeros((self.S,), np.int64)
        # host mirror of memtable appends (per shard): LSM reads serve the
        # unflushed tail without pulling device buffers. insert_routed()
        # bypasses the host, which invalidates the mirror until next flush.
        self._mem_mirror = [[] for _ in range(self.S)]
        self._mirror_ok = True
        # (row, col)-sorted + combiner-deduped mirror per shard, computed
        # lazily for the fused read path (saves an in-dispatch sort) and
        # reused until the next insert touches the shard
        self._mem_sorted: dict = {}
        self._append = _APPEND
        self._append_flat = _APPEND_FLAT
        self._wal = None
        self._wal_dir = None
        self._wal_ckpt_offset = 0
        if wal_dir is not None:
            self.attach_wal(wal_dir)

    # ------------------------------------------------------- durability
    def attach_wal(self, wal_dir: str):
        """Open (or re-open) the write-ahead log under ``wal_dir``."""
        import os
        from .lsm.manifest import wal_path
        from .lsm.wal import WriteAheadLog
        os.makedirs(wal_dir, exist_ok=True)
        if self._wal is not None:
            self._wal.close()
        self._wal_dir = wal_dir
        self._wal = WriteAheadLog(wal_path(wal_dir))
        # WAL backlog baseline: everything currently in the log predates
        # this process's appends, so a fresh attach owes a full replay
        self._wal_ckpt_offset = 0

    def checkpoint(self) -> str:
        """Flush the memtable, snapshot the runs, mark the WAL offset.
        Returns the manifest path; ``db.lsm.recover`` consumes it."""
        if self._wal_dir is None:
            raise ValueError("checkpoint() needs a wal_dir")
        from .lsm.manifest import write_snapshot
        self.flush()
        path = write_snapshot(self, self._wal_dir)
        self._wal_ckpt_offset = self._wal.tell() if self._wal else 0
        return path

    def close(self) -> None:
        """Release buffers and refuse further use (connector delete())."""
        if self._closed:
            return
        if self._wal is not None:
            self._wal.close()
            self._wal = None
        if self.t_store is not None:
            self.t_store.close()
        self._runs = None
        self._mem_r = self._mem_c = self._mem_v = None
        self._mem_n = np.zeros((self.S,), np.int64)
        self._closed = True

    def _check_open(self):
        if self._closed:
            raise RuntimeError(f"table {self.name!r} has been deleted")

    def warmup(self) -> None:
        """Precompile the flush/compaction graphs (no state mutation) so
        benchmark windows measure steady-state throughput, not jit time."""
        self._check_open()
        self._runs.warmup(self._mem_r, self._mem_c, self._mem_v)
        if self.t_store is not None:
            self.t_store.warmup()

    def warm_reads(self) -> None:
        """Precompile the read path's static serving shapes against the
        CURRENT resident state (runs/levels/memtable geometry is baked
        into the fused query graph, so this must run at serving time, not
        ingest time). The fused path has exactly two shapes — the point
        bucket and the ``fused_q_limit`` query tile — and the tile serves
        EVERY batch size, so one warm call here means no novel batch size
        ever retraces. Queries probe spread-out absent ids: every shard
        dispatches, and ``lax.cond`` bloom gates compile both branches at
        trace time."""
        self._check_open()
        self.query_rows(np.zeros(1, np.int32))  # point bucket
        if self.tablet_map is not None:
            # skew-aware probe: a split/moved map can hand a shard a
            # NARROW slice of the id space — a uniform linspace would
            # give it <= 8 ids (point-bucket shape only) and the tile
            # would compile lazily on the first real batch. Sample each
            # shard's OWNED ranges instead, so both serving shapes
            # re-warm after every topology change.
            parts = [self.tablet_map.sample_shard_ids(s)
                     for s in range(self.S)]
            parts = [p for p in parts if len(p)]
            probe = (np.concatenate(parts) if parts
                     else np.zeros(1, np.int32))
        else:
            probe = np.linspace(0, self.id_capacity - 1,
                                2 * self.S * 8 + 2).astype(np.int32)
        self.query_rows(np.unique(probe))   # > 8 ids/shard: the tile
        if self.t_store is not None:  # column selectors serve from A^T
            self.t_store.warm_reads()

    def engine_stats(self) -> dict:
        """Observability: the engine counters (``lsm.engine.STAT_KEYS``)
        plus per-shard used L0 slots and per-level resident entries."""
        st = dict(self._runs.stats)
        st["l0_used"] = [int(x) for x in self._runs.l0_used]
        st["level_entries"] = [int(lv["n"].sum())
                               for lv in self._runs.levels]
        return st

    def refresh_health_gauges(self, bloom_probes: int = 0) -> None:
        """Recompute the derived health gauges for this table (and its
        transpose sibling): memtable occupancy per shard, WAL backlog,
        resident runs, compaction debt, read/write amplification, and
        (``bloom_probes > 0``) the observed-vs-theoretical bloom fp
        rate."""
        self._check_open()
        for s in range(self.S):
            self._reg.gauge("db_memtable_occupancy", table=self.name,
                            shard=s).set(int(self._mem_n[s]) / self.mem_cap)
        if self._wal is not None:
            self._wal.refresh_backlog_gauge(self._wal_ckpt_offset)
        self._runs.refresh_health_gauges(bloom_probes=bloom_probes)
        if self.tablet_map is not None:
            self._reg.gauge("lsm_tablets", table=self.name).set(
                self.tablet_map.n)
            self._reg.gauge("lsm_tablet_balance", table=self.name).set(
                self.tablet_map.shard_balance())
        if self.t_store is not None:
            self.t_store.refresh_health_gauges(bloom_probes=bloom_probes)

    def nnz(self) -> int:
        self._check_open()
        return sum(len(self.scan_shard(s)[0]) for s in range(self.S))

    # ------------------------------------------------------------- ingest
    def route(self, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray):
        """Host-side BatchWriter routing: bucket triples by owner shard into
        fixed [S, batch_cap] buffers (pads = I32_MAX)."""
        dest = shard_of(rows, self.S, self.id_capacity)
        order = np.argsort(dest, kind="stable")
        rows, cols, vals, dest = rows[order], cols[order], vals[order], dest[order]
        counts = np.bincount(dest, minlength=self.S)
        if counts.max() > self.batch_cap:
            raise OverflowError(
                f"shard batch overflow: {counts.max()} > {self.batch_cap}")
        br = np.full((self.S, self.batch_cap), I32_MAX, np.int32)
        bc = np.full((self.S, self.batch_cap), I32_MAX, np.int32)
        bv = np.zeros((self.S, self.batch_cap), np.float32)
        ends = np.cumsum(counts)
        starts = ends - counts
        slot = np.arange(len(rows)) - starts[dest]
        br[dest, slot] = rows
        bc[dest, slot] = cols
        bv[dest, slot] = vals
        return br, bc, bv

    def insert(self, rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
               _log: bool = True):
        """Host-side BatchWriter: bucket by owner + flat memtable append.
        With a WAL attached, the batch is journaled first (write-ahead);
        ``_log=False`` is for WAL replay during recovery.

        Transpose-enabled stores dual-ingest: the batch lands in the
        primary (routed by row) AND the transpose sibling (routed by
        col, rows/cols swapped) behind ONE pair-tagged WAL record — one
        fsync, and replay rebuilds both or neither (pair atomicity)."""
        self._check_open()
        rows = np.asarray(rows, np.int32)
        cols = np.asarray(cols, np.int32)
        vals = np.asarray(vals, np.float32)
        n = len(rows)
        if n == 0:
            return
        if n > self.mem_cap:
            raise OverflowError(f"batch {n} exceeds memtable {self.mem_cap}")
        with self._trace.span("ingest", self._h_ingest, table=self.name,
                              n=n):
            if _log and self._wal is not None:
                pair = self.t_store is not None
                if self.tablet_map is None:
                    self._wal.append(rows, cols, vals, pair=pair)
                else:
                    # one TAGGED frame per tablet touched: a recovering
                    # process replays only its own tablets' suffix by
                    # skipping foreign frames. Duplicates of one
                    # (row, col) share a tablet, so per-tablet framing
                    # preserves within-key order (combiner semantics).
                    tidx = self.tablet_map.tablet_of(rows)
                    tids = self.tablet_map.tablet_ids
                    for t in np.unique(tidx):
                        sel = np.flatnonzero(tidx == t)
                        self._wal.append(rows[sel], cols[sel], vals[sel],
                                         pair=pair, tablet=int(tids[t]))
            self._insert_batch(rows, cols, vals)
            if self.t_store is not None:
                self.t_store._insert_batch(cols, rows, vals)

    def _insert_batch(self, rows, cols, vals):
        n = len(rows)
        if n > self.mem_cap:
            raise OverflowError(f"batch {n} exceeds memtable {self.mem_cap}")
        if self.tablet_map is not None:
            tidx = self.tablet_map.tablet_of(rows)
            dest = self.tablet_map.owners[tidx].astype(np.int32)
            if not self._migrating:  # migration re-inserts aren't load
                self.tablet_map.record_load(tidx)
        else:
            dest = shard_of(rows, self.S, self.id_capacity)
        order = np.argsort(dest, kind="stable")
        dest, rows, cols, vals = dest[order], rows[order], cols[order], vals[order]
        counts_b = np.bincount(dest, minlength=self.S)
        if self._reg.enabled and not self._migrating:
            for s in np.nonzero(counts_b)[0]:
                self._c_shard_ingest[s].inc(int(counts_b[s]))
        if (self._mem_n + counts_b > self.mem_cap).any():
            self.flush()
        ends = np.cumsum(counts_b)
        if self._mirror_ok:
            starts_m = ends - counts_b
            for s in np.nonzero(counts_b)[0]:
                self._mem_mirror[s].append(
                    (rows[starts_m[s]:ends[s]], cols[starts_m[s]:ends[s]],
                     vals[starts_m[s]:ends[s]]))
                self._mem_sorted.pop(int(s), None)
        slot = np.arange(n, dtype=np.int32) - (ends - counts_b)[dest]
        pad = (1 << max(n - 1, 1).bit_length()) - n  # bucket jit shapes
        if pad:
            dest = np.pad(dest, (0, pad), constant_values=self.S)
            slot = np.pad(slot, (0, pad))
            rows = np.pad(rows, (0, pad), constant_values=I32_MAX)
            cols = np.pad(cols, (0, pad), constant_values=I32_MAX)
            vals = np.pad(vals, (0, pad))
        self._mem_r, self._mem_c, self._mem_v, cnt = self._append_flat(
            self._mem_r, self._mem_c, self._mem_v,
            jnp.asarray(self._mem_n, jnp.int32), jnp.asarray(dest),
            jnp.asarray(slot), jnp.asarray(rows), jnp.asarray(cols),
            jnp.asarray(vals))
        self._mem_n = np.asarray(cnt, np.int64)

    def insert_routed(self, br, bc, bv):
        """Memtable append of already-routed [S, batch_cap] buffers; minor
        compaction when a shard's memtable would overflow. (Not journaled —
        the routed path is the SPMD benchmark path, not the durable one.)"""
        self._check_open()
        if self.t_store is not None:
            raise ValueError(
                "insert_routed() does not maintain the transpose sibling; "
                "use insert() on a transpose-enabled store (or "
                "spmd.make_spmd_lsm_pair_ingest_step under shard_map)")
        incoming = np.asarray((np.asarray(br) != I32_MAX).sum(axis=1))
        if (self._mem_n + incoming > self.mem_cap).any():
            self.flush()
        self._mirror_ok = False  # device-side append: host mirror is stale
        for m in self._mem_mirror:
            m.clear()
        self._mem_r, self._mem_c, self._mem_v, counts = self._append(
            self._mem_r, self._mem_c, self._mem_v,
            jnp.asarray(self._mem_n, jnp.int32), br, bc, bv)
        self._mem_n = np.asarray(counts, np.int64)

    def flush(self) -> None:
        """Minor compaction: memtable -> one L0 run per shard,
        O(memtable)."""
        self._check_open()
        if self._mem_n.max(initial=0) == 0:
            return
        self._runs.flush_memtable(self._mem_r, self._mem_c, self._mem_v)
        self._mem_r = jnp.full((self.S, self.mem_cap), I32_MAX, jnp.int32)
        self._mem_c = jnp.full((self.S, self.mem_cap), I32_MAX, jnp.int32)
        self._mem_v = jnp.zeros((self.S, self.mem_cap), jnp.float32)
        self._mem_n = np.zeros((self.S,), np.int64)
        self._mem_mirror = [[] for _ in range(self.S)]
        self._mirror_ok = True
        self._mem_sorted.clear()
        if self.t_store is not None:
            self.t_store.flush()

    def _mem_host(self, s: int):
        """Host mirror of shard ``s``'s memtable, or None if stale."""
        if not self._mirror_ok:
            return None
        if not self._mem_mirror[s]:
            return (np.zeros(0, np.int32), np.zeros(0, np.int32),
                    np.zeros(0, np.float32))
        return tuple(np.concatenate([b[i] for b in self._mem_mirror[s]])
                     for i in range(3))

    def _mem_host_sorted(self, s: int):
        """The mirror, (row, col)-sorted and pre-combined for the fused
        read path (commutes with the cross-run combine, exactly like a
        flush would); cached until the next insert touches the shard."""
        got = self._mem_sorted.get(s)
        if got is not None:
            return got
        mh = self._mem_host(s)
        if mh is None or len(mh[0]) == 0:
            return mh
        from .lsm.engine import combine_triples
        got = combine_triples(mh[0].astype(np.int32),
                              mh[1].astype(np.int32),
                              mh[2].astype(np.float32),
                              np.arange(len(mh[0]), dtype=np.int32),
                              self.combiner)
        self._mem_sorted[s] = got
        return got

    def major_compact(self) -> None:
        """Force a major compaction: flush, then merge all runs."""
        self._check_open()
        self.flush()
        self._runs.major_compact()
        if self.t_store is not None:
            self.t_store.major_compact()

    # ------------------------------------------------------------ tablets
    def _require_tablets(self):
        if self.tablet_map is None:
            raise ValueError(
                f"table {self.name!r} was not built with "
                "dynamic_tablets=True")
        return self.tablet_map

    def split_tablet(self, tablet_id: int = None, key: int = None):
        """Split one tablet's row range in two (metadata only — both
        halves stay on the owning shard until a move rebalances them).

        Defaults pick the hottest tablet by recorded load and split at
        the owner shard's fence-derived median key inside the range (the
        engine's fence pointers uniformly sample each sorted run, so the
        median fence approximates the median data key for free). The op
        is journaled as a WAL meta frame BEFORE the map changes, with the
        new tablet id pinned, so replay reproduces the identical map.
        Returns the new right-half tablet id, or None when the tablet
        cannot split (range width 1)."""
        self._check_open()
        tm = self._require_tablets()
        if tablet_id is None:
            tablet_id = int(tm.tablet_ids[int(np.argmax(tm.loads))])
        lo, hi = tm.range_of(tablet_id)
        if hi - lo <= 1:
            return None
        if key is None:
            self.flush()  # fences only see flushed data
            s = int(tm.owners[tm.index_of(tablet_id)])
            key = self._runs.fence_median(s, lo, hi)
        key = int(key)
        if not lo < key < hi:
            return None
        new_id = tm.next_id
        if self._wal is not None:
            self._wal.append_meta({"op": "split", "tablet": int(tablet_id),
                                   "key": key, "new": new_id})
        tm.split(tablet_id, key, new_id=new_id)
        self._c_tablet_splits.inc()
        return new_id

    def move_tablet(self, tablet_id: int, dst: int) -> bool:
        """Migrate one tablet to shard ``dst``: journal a WAL meta frame,
        update the map, then physically re-route the SOURCE shard (scan
        its combined triples, clear its runs, re-insert through the new
        map). Re-inserting combined values once each is a no-op under all
        four combiners, so reads are unchanged modulo placement. Returns
        False when ``dst`` already owns the tablet."""
        self._check_open()
        tm = self._require_tablets()
        dst = int(dst)
        if not 0 <= dst < self.S:
            raise ValueError(f"destination shard {dst} out of range")
        src = int(tm.owners[tm.index_of(tablet_id)])
        if src == dst:
            return False
        if self._wal is not None:
            self._wal.append_meta({"op": "move", "tablet": int(tablet_id),
                                   "to": dst})
        tm.move(tablet_id, dst)
        self._migrate_shard(src)
        self._c_tablet_moves.inc()
        return True

    def merge_tablet(self, tablet_id: int) -> bool:
        """Merge a tablet with its right neighbor (the inverse of
        ``split_tablet`` — Accumulo's range coalescing for gone-cold
        ranges). If the neighbor lives on a different shard it is first
        moved to this tablet's owner (journaled like any move); the merge
        itself is metadata only. Returns False when there is no right
        neighbor."""
        self._check_open()
        tm = self._require_tablets()
        i = tm.index_of(tablet_id)
        if i + 1 >= tm.n:
            return False
        if tm.owners[i] != tm.owners[i + 1]:
            self.move_tablet(int(tm.tablet_ids[i + 1]), int(tm.owners[i]))
        if self._wal is not None:
            self._wal.append_meta({"op": "merge", "tablet": int(tablet_id)})
        tm.merge(tablet_id)
        self._c_tablet_merges.inc()
        return True

    def _migrate_shard(self, src: int) -> None:
        """Re-route everything resident on shard ``src`` through the
        CURRENT tablet map: flush, scan the shard's combined triples,
        clear its runs, and re-insert in memtable-sized chunks. Entries
        whose tablet still lives on ``src`` land back; moved tablets'
        entries land on their new owner. Not WAL-logged (the data is
        already durable before the move's meta frame) and not counted as
        ingest (``_migrating`` guards the load/ingest counters)."""
        self.flush()
        r, c, v = self.scan_shard(src)
        self._runs.clear_shard(src)
        if len(r) == 0:
            return
        self._migrating = True
        try:
            step = self.mem_cap
            for i in range(0, len(r), step):
                self._insert_batch(r[i:i + step], c[i:i + step],
                                   v[i:i + step])
        finally:
            self._migrating = False
        self.flush()

    def maybe_rebalance(self, split_threshold: float = 1.5,
                        max_tablets: int = None, min_load: float = 1.0):
        """One round of the tablet balance policy (the Accumulo master
        analogue, driven by the obs-recorded per-tablet loads):

        1. SPLIT any tablet whose load exceeds ``split_threshold`` times
           the mean per-shard load (bounded by ``max_tablets``, default
           ``8 * S``) — a hot range becomes two movable halves;
        2. LPT-assign tablets to shards (heaviest tablet to the least
           loaded shard, current owner preferred on ties so a balanced
           map never thrashes) and migrate the changed assignments;
        3. decay the load signal by half so the policy tracks the recent
           workload.

        Returns ``{"splits", "moves", "balance"}`` where balance is the
        post-rebalance max/mean per-shard load (1.0 = perfect). Greedy
        LPT bounds it by (4/3 - 1/(3S)) whenever no single tablet
        dominates, comfortably under the ≤ 2.0 acceptance bar."""
        self._check_open()
        tm = self._require_tablets()
        out = {"splits": 0, "moves": 0}
        total = float(tm.loads.sum())
        if total >= min_load:
            cap = 8 * self.S if max_tablets is None else int(max_tablets)
            mean_shard = total / self.S
            for _ in range(self.S):  # bounded split rounds per call
                i = int(np.argmax(tm.loads))
                if (tm.loads[i] <= split_threshold * mean_shard
                        or tm.n >= cap):
                    break
                if self.split_tablet(int(tm.tablet_ids[i])) is None:
                    break
                out["splits"] += 1
            order = np.argsort(tm.loads, kind="stable")[::-1]
            shard_load = np.zeros(self.S)
            assign = np.empty(tm.n, np.int32)
            for i in order:
                d = int(np.argmin(shard_load))
                cur = int(tm.owners[i])
                if shard_load[cur] <= shard_load[d] + 1e-9:
                    d = cur  # tie: keep the tablet where it lives
                assign[i] = d
                shard_load[d] += tm.loads[i]
            for i in np.flatnonzero(assign != tm.owners):
                if self.move_tablet(int(tm.tablet_ids[i]), int(assign[i])):
                    out["moves"] += 1
        tm.decay()
        out["balance"] = tm.shard_balance()
        self._reg.gauge("lsm_tablet_balance", table=self.name).set(
            out["balance"])
        self._reg.gauge("lsm_tablets", table=self.name).set(tm.n)
        return out

    def _apply_replayed_meta(self, op: dict) -> None:
        """Apply one WAL meta frame during recovery: the map mutates at
        the SAME log point it did live — including the physical move
        migration — so data frames replayed after the op route to the
        identical shards (``lsm.manifest.recover``)."""
        if self.tablet_map is None:
            return
        tm = self.tablet_map
        kind = op.get("op")
        if kind == "split":
            tm.split(int(op["tablet"]), int(op["key"]),
                     new_id=int(op["new"]))
        elif kind == "move":
            src = int(tm.owners[tm.index_of(int(op["tablet"]))])
            dst = int(op["to"])
            if src != dst:
                tm.move(int(op["tablet"]), dst)
                self._migrate_shard(src)
        elif kind == "merge":
            tm.merge(int(op["tablet"]))

    # -------------------------------------------------------------- query
    def _mem_fused(self, s: int):
        """Shard ``s``'s unflushed tail for a fused read, as
        ``(mem_host, mem_sorted)``: None when empty; the sorted, combined
        host mirror when it is current; else lazy slices of the device
        buffers (``insert_routed`` left the mirror stale), which the
        dispatch sorts itself."""
        mem_n = int(self._mem_n[s])
        if mem_n == 0:
            return None, False
        if self._mirror_ok:
            return self._mem_host_sorted(s), True
        return (self._mem_r[s, :mem_n], self._mem_c[s, :mem_n],
                self._mem_v[s, :mem_n]), False

    def query_rows(self, row_ids: np.ndarray, max_return: int = 256,
                   col_filter: np.ndarray = None):
        """Point queries; returns (row_id, col_id, val) numpy triples.

        Served from memtable + runs by one fused dispatch per shard and
        query tile (bloom/fence read path) — point reads never trigger a
        flush. ``col_filter`` restricts results to the given column id
        set; the membership test runs ON DEVICE inside the dispatch.
        """
        self._check_open()
        t_call = perf_counter()
        if col_filter is not None:
            col_filter = np.asarray(col_filter, np.int32)
        row_ids = np.asarray(row_ids, np.int32)
        if self.tablet_map is not None:
            tidx = self.tablet_map.tablet_of(row_ids)
            self.tablet_map.record_load(tidx)  # queries drive splits too
            owner = self.tablet_map.owners[tidx].astype(np.int32)
        else:
            owner = shard_of(row_ids, self.S, self.id_capacity)
        out_r, out_c, out_v = [], [], []
        for s in np.unique(owner):
            s = int(s)
            q = row_ids[owner == s]
            self._c_shard_query[s].inc(len(q))
            t_sh = perf_counter()
            # duplicate query ids return duplicate results: query unique
            # ids, then re-expand
            uq, ucnt = np.unique(q, return_counts=True)
            fmem, mem_sorted = self._mem_fused(s)
            if fmem is None and not self._runs.resident_runs(s):
                # empty shard: nothing to dispatch — still observed
                self._h_shard_query[s].observe(perf_counter() - t_sh)
                continue
            r, c, v = self._runs.query_shard_fused(
                s, uq, self.fused_q_limit, mem_host=fmem,
                max_return=max_return, mem_sorted=mem_sorted,
                col_filter=col_filter)
            if len(r) and (ucnt > 1).any():
                rep = ucnt[np.searchsorted(uq, r)]
                r, c, v = (np.repeat(r, rep), np.repeat(c, rep),
                           np.repeat(v, rep))
            self._h_shard_query[s].observe(perf_counter() - t_sh)
            out_r.append(r); out_c.append(c); out_v.append(v)
        if len(row_ids):
            self._h_query.observe(perf_counter() - t_call)
        if not out_r:
            z = np.zeros(0, np.int32)
            return z, z.copy(), np.zeros(0, np.float32)
        return (np.concatenate(out_r), np.concatenate(out_c),
                np.concatenate(out_v))

    def scan_range(self, lo: int, hi: int, width: int = 64,
                   col_filter: np.ndarray = None):
        """Row-range scan: all (row, col, val) with ``lo <= row < hi``,
        sorted lex by (row, col) per shard — the server-side analogue of an
        Accumulo tablet range scan.

        Each overlapping shard is answered by ONE fused fence-to-fence
        dispatch (``scan_shard_fused``) — no id-list point expansion.
        ``col_filter`` restricts results to the given column id set,
        masked on-device inside the scan dispatch."""
        self._check_open()
        t_call = perf_counter()
        lo, hi = int(lo), int(hi)
        if col_filter is not None:
            col_filter = np.asarray(col_filter, np.int32)
        out_r, out_c, out_v = [], [], []
        if hi > lo:
            if self.tablet_map is not None:
                # per-owner sub-ranges in KEY order (adjacent same-owner
                # tablets coalesced): concatenated segment outputs stay
                # globally (row, col)-sorted even under a skewed map
                segs = self.tablet_map.segments(lo, hi)
                self.tablet_map.touch_range(lo, hi)
            else:
                s_lo = int(shard_of(np.asarray([lo]), self.S,
                                    self.id_capacity)[0])
                s_hi = int(shard_of(np.asarray([max(hi - 1, lo)]), self.S,
                                    self.id_capacity)[0])
                # each shard clips the full range itself (fence ranks)
                segs = [(s, lo, hi) for s in range(s_lo, s_hi + 1)]
            for s, seg_lo, seg_hi in segs:
                self._c_shard_scan[s].inc()
                t_sh = perf_counter()
                fmem, mem_sorted = self._mem_fused(s)
                r, c, v = self._runs.scan_shard_fused(
                    s, seg_lo, seg_hi, mem_host=fmem, width=width,
                    mem_sorted=mem_sorted, col_filter=col_filter)
                self._h_shard_scan[s].observe(perf_counter() - t_sh)
                if len(r):
                    out_r.append(r); out_c.append(c); out_v.append(v)
            self._h_scan.observe(perf_counter() - t_call)
        if not out_r:
            z = np.zeros(0, np.int32)
            return z, z.copy(), np.zeros(0, np.float32)
        return (np.concatenate(out_r), np.concatenate(out_c),
                np.concatenate(out_v))

    # ------------------------------------------------ column-axis reads
    def query_cols(self, col_ids: np.ndarray, max_return: int = 256):
        """Point COLUMN queries via the transpose sibling: all
        (row, col, val) whose col is in ``col_ids`` — same bloom/fence
        fused path a row query gets, axes swapped back on return."""
        self._check_open()
        if self.t_store is None:
            raise ValueError(
                f"table {self.name!r} has no transpose sibling "
                "(ShardedTable(transpose=True))")
        tr, tc, tv = self.t_store.query_rows(col_ids, max_return=max_return)
        return tc, tr, tv  # sibling rows ARE our cols (and vice versa)

    def scan_col_range(self, lo: int, hi: int, width: int = 64,
                       row_filter: np.ndarray = None):
        """Column-range scan ``lo <= col < hi`` via the transpose
        sibling's fused fence-to-fence scan — O(selectivity), not the
        O(nnz) full-scan-and-filter a plain table needs. Returns
        (rows, cols, vals) sorted lex by (col, row); ``row_filter``
        pushes a residual row id set into the sibling's dispatch."""
        self._check_open()
        if self.t_store is None:
            raise ValueError(
                f"table {self.name!r} has no transpose sibling "
                "(ShardedTable(transpose=True))")
        tr, tc, tv = self.t_store.scan_range(lo, hi, width=width,
                                             col_filter=row_filter)
        return tc, tr, tv  # sibling rows ARE our cols (and vice versa)

    def scan_shard(self, s: int):
        """One shard's combined sorted triples (no flush)."""
        self._check_open()
        mem_n = int(self._mem_n[s])
        mh = self._mem_host(s)
        if mh is None and mem_n:
            mem = (self._mem_r[s], self._mem_c[s], self._mem_v[s])
        else:
            mem = (None, None, None)
        return self._runs.scan_shard(s, *mem, mem_n, mem_host=mh)

    def scan(self):
        """Full-table scan -> (row_ids, col_ids, vals), sorted per shard."""
        self._check_open()
        self._c_full_scans.inc()
        parts = [self.scan_shard(s) for s in range(self.S)]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]),
                np.concatenate([p[2] for p in parts]))
