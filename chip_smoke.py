"""Bring-up check: the D4M store's main path on a TPU, end to end.

    python chip_smoke.py              # one chip: phases 1 and 2
    python chip_smoke.py --chips 4    # four chips: the SPMD path only

Phase 1 (deployment size, default configuration): a Graph500 scale-20
Kronecker graph (2^20 vertices, 2^24 edges) goes through ``dbsetup`` and
``EdgeSchema.put_triple`` into the D4M 2.0 edge, transpose and degree
tables with a write-ahead log, and row, column, row-range and degree
queries are checked against a numpy reference built from the generated
edge arrays.

Phase 2 (the Pallas kernels on the chip, Graph500 scale 14): the same
ingest and queries with the default configuration and with
``use_pallas=True`` must give identical answers; the Pallas store then
checkpoints, is recovered with ``recover_connector`` and answers again.
The compiled text of one of its fused reads must hold a ``tpu_custom_call``.

``--chips 4``: k SPMD ingestors (``db.spmd``) over a 4-chip mesh ingest,
compact, query and scan integer-id Kronecker triples; every answer is
compared with a one-device ``ShardedTable(num_shards=4)`` fed the same
triples, and with numpy.

Everything runs in this one process. The platform is pinned to the TPU
before JAX starts, so a machine without one fails at start-up instead of
running on the CPU. Walls printed here are smoke walls, not benchmark
numbers. The last line of output is the JSON result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

SCALE = 20             # phase 1: 2^20 vertices, 16 * 2^20 edges
PALLAS_SCALE = 14      # phase 2: the Pallas rank kernels sweep whole runs
EDGE_FACTOR = 16       # Graph500 edges per vertex
SKEW_HEADROOM = 1.3    # capacity over the fullest shard's edge count
# a row range whose keys the dictionary interned contiguously (the eight
# hubs all arrive in the first ingest batch), so the planner serves it with
# the fused scan; wider ranges hold keys first seen in later batches, whose
# ids scatter, and are planned as point reads instead
RANGE_SEL, RANGE_LAST = "v00000000,:,v00000007,", 7


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: MISMATCH: {what}")


def key(i: int) -> str:
    return f"v{int(i):08d}"


def skew_capacity(counts: np.ndarray) -> int:
    """Per-shard capacity from the counted shard skew (unpermuted power-law
    graphs pile the hubs into the low-id shard)."""
    return max(1 << 12, int(counts.max() * SKEW_HEADROOM))


def shard_counts(u, v, rows, cols, vals, cfg, char_budget) -> np.ndarray:
    """Edges per shard, for the edge table and its transpose, under the ids
    the connector's key dictionary will assign: each ingest batch interns
    its row keys, then its column keys, new keys in sorted order. The keys
    are fixed-width vertex strings, so sorted order is numeric order."""
    from repro.db import batching
    from repro.db.kvstore import shard_of
    ids = np.full(int(max(u.max(), v.max())) + 1, -1, np.int64)
    nxt = 0
    for sl in batching.batch_slices(batching.triple_chars(rows, cols, vals),
                                    char_budget):
        for part in (u[sl], v[sl]):
            uq = np.unique(part)
            new = uq[ids[uq] < 0]
            ids[new] = np.arange(nxt, nxt + len(new))
            nxt += len(new)
    S = cfg.num_shards
    return np.maximum(
        np.bincount(shard_of(ids[u], S, cfg.id_capacity), minlength=S),
        np.bincount(shard_of(ids[v], S, cfg.id_capacity), minlength=S))


# ------------------------------------------------------------ references
class Reference:
    """Plain numpy answers from the generated edge arrays. The edge table's
    combiner is last-wins over all-ones values, so every distinct edge
    reads 1.0; the degree table counts every ingested edge."""

    def __init__(self, u: np.ndarray, v: np.ndarray):
        pairs = np.unique(u.astype(np.int64) << 32 | v.astype(np.int64))
        self.src = (pairs >> 32).astype(np.int64)
        self.dst = (pairs & 0xFFFFFFFF).astype(np.int64)
        n = int(max(u.max(), v.max())) + 1
        self.out_deg = np.bincount(u, minlength=n)
        self.in_deg = np.bincount(v, minlength=n)

    def row(self, x):
        return self._ones(self.src == x)

    def col(self, x):
        return self._ones(self.dst == x)

    def rows_upto(self, x):
        return self._ones(self.src <= x)

    def _ones(self, m):
        return self.src[m], self.dst[m], np.ones(int(m.sum()))


def triples(a):
    """An Assoc's triples as (row ints, col ints, vals), sorted."""
    r, c, v = a.triples()
    r = np.asarray([int(s[1:]) for s in r], np.int64)
    c = np.asarray([int(s[1:]) for s in c], np.int64)
    o = np.lexsort((c, r))
    return r[o], c[o], np.asarray(v, np.float64)[o]


def same(got, want) -> bool:
    return all(len(g) == len(w) and np.array_equal(g, w)
               for g, w in zip(got, want))


def registry_counts() -> tuple:
    from repro.obs import default_registry
    reg = default_registry()
    retraces = sum(int(c.value) for c in reg.series("lsm_retraces"))
    shapes = sum(int(g.value) for g in reg.series("lsm_compiled_shapes"))
    return retraces, shapes


def memory_line(device) -> str:
    st = device.memory_stats() or {}
    return (f"bytes_in_use={st.get('bytes_in_use')} "
            f"peak_bytes_in_use={st.get('peak_bytes_in_use')} "
            f"bytes_limit={st.get('bytes_limit')}")


def ask(tag: str, E, ref: Reference, seed: int) -> dict:
    """Row, column, row-range and degree queries through the connector,
    each checked against ``ref``; returns the answers by name."""
    store = E.pair.table.store
    rng = np.random.default_rng(seed)
    hub_out = int(np.argmax(ref.out_deg))
    hub_in = int(np.argmax(ref.in_deg))
    srcs = np.flatnonzero(ref.out_deg)
    dsts = np.flatnonzero(ref.in_deg)
    row_q = [hub_out] + sorted(rng.choice(srcs, 5, replace=False).tolist())
    col_q = [hub_in] + sorted(rng.choice(dsts, 5, replace=False).tolist())

    answers = {}
    t0 = time.perf_counter()
    for x in row_q:
        answers[f"row {key(x)}"] = got = triples(E[f"{key(x)},", :])
        check(same(got, ref.row(x)), f"[{tag}] row query {key(x)}")
    for x in col_q:
        answers[f"col {key(x)}"] = got = triples(E[:, f"{key(x)},"])
        check(same(got, ref.col(x)), f"[{tag}] column query {key(x)}")
    scans0 = store.engine_stats()["scan_dispatches"]
    answers["range"] = got = triples(E[RANGE_SEL, :])
    check(store.engine_stats()["scan_dispatches"] > scans0,
          f"[{tag}] the row range did not take the fused scan")
    check(same(got, ref.rows_upto(RANGE_LAST)),
          f"[{tag}] row range {RANGE_SEL}")
    sel = "".join(f"{key(x)}," for x in row_q)
    r, c, d = E.deg.degrees(sel).triples()
    deg = {(int(a[1:]), b): float(x) for a, b, x in zip(r, c, d)}
    want = {}
    for x in row_q:
        for name, arr in (("OutDeg", ref.out_deg), ("InDeg", ref.in_deg)):
            if arr[x]:
                want[(x, name)] = float(arr[x])
    check(deg == want, f"[{tag}] degree query")
    answers["degree"] = sorted(deg.items())
    t_query = time.perf_counter() - t0
    n_answers = sum(len(a[0]) for k, a in answers.items() if k != "degree")
    log(f"[{tag}] queries: {len(row_q)} row, {len(col_q)} column, 1 range "
        f"({len(answers['range'][0])} entries), 1 degree; {n_answers} "
        f"triples in {t_query:.3f} s (smoke wall); all equal the reference")
    return answers


# ---------------------------------------------------- one connector store
def run_store(tag: str, scale: int, seed: int, wal_root: str, ref: Reference,
              edges, use_pallas: bool = False) -> dict:
    """Ingest the graph through the connector and answer the query set.
    Every answer is checked against ``ref``; returns them by name."""
    import jax
    from repro.db import EdgeSchema, batching, dbsetup
    from repro.db.kvstore import StoreConfig

    u, v, rows, cols, vals = edges
    cfg = StoreConfig()
    counts = shard_counts(u, v, rows, cols, vals, cfg,
                          batching.DEFAULT_CHAR_BUDGET)
    cap = skew_capacity(counts)
    log(f"[{tag}] scale={scale} edges={len(u)} use_pallas={use_pallas} "
        f"shard_edges={counts.tolist()} skew={counts.max() / counts.mean():.3f}"
        f" capacity_per_shard={cap}")
    server = dbsetup(f"smoke_{tag}", capacity_per_shard=cap,
                     use_pallas=use_pallas, wal_root=wal_root)
    E = EdgeSchema(server, tag)
    store = E.pair.table.store

    t0 = time.perf_counter()
    E.put_triple(rows, cols, vals)
    jax.block_until_ready((E.deg.out_deg, E.deg.in_deg, store._mem_r,
                           store.t_store._mem_r))
    t_ingest = time.perf_counter() - t0
    log(f"[{tag}] ingest wall {t_ingest:.3f} s (smoke wall, "
        f"{len(u) / t_ingest:,.0f} edges/s)")
    log(f"[{tag}] after ingest: {memory_line(jax.devices()[0])}")

    answers = ask(tag, E, ref, seed)

    nnz = E.nnz()
    nnz_t = store.t_store.nnz()
    nnz_deg = (int(np.count_nonzero(np.asarray(E.deg.out_deg)))
               + int(np.count_nonzero(np.asarray(E.deg.in_deg))))
    check(nnz == nnz_t == len(ref.src), f"[{tag}] nnz {nnz}/{nnz_t} vs "
          f"{len(ref.src)} distinct edges")
    check(nnz_deg == np.count_nonzero(ref.out_deg)
          + np.count_nonzero(ref.in_deg), f"[{tag}] degree table nnz")
    retraces, shapes = registry_counts()
    log(f"[{tag}] nnz: Tedge={nnz} TedgeT={nnz_t} TedgeDeg={nnz_deg}; "
        f"engine={store.engine_stats()}; lsm_retraces={retraces} "
        f"lsm_compiled_shapes={shapes}")
    return {"server": server, "schema": E, "answers": answers}


def graph(scale: int, seed: int):
    from repro.data.graph500 import kronecker_edges, vertex_strings
    t0 = time.perf_counter()
    u, v = kronecker_edges(scale, EDGE_FACTOR, seed)
    edges = (u, v, vertex_strings(u), vertex_strings(v),
             np.ones(len(u), np.float32))
    ref = Reference(u, v)
    log(f"[scale {scale}] generated {len(u)} edges, {len(ref.src)} distinct,"
        f" and the numpy reference in {time.perf_counter() - t0:.3f} s")
    return edges, ref


def phase1(seed: int) -> None:
    edges, ref = graph(SCALE, seed)
    with tempfile.TemporaryDirectory(prefix="d4m_smoke_") as wal_root:
        out = run_store("g20", SCALE, seed, wal_root, ref, edges)
        out["schema"].delete()


def fused_read_text(store, row_id: int) -> str:
    """Compiled text of the fused point read that serves ``row_id`` (called
    right after a checkpoint, so the memtable tail is empty)."""
    from repro.db.kvstore import shard_of
    from repro.db.lsm.engine import _fused_query_fn
    runs = store._runs
    s = int(shard_of(np.asarray([row_id]), store.S, store.id_capacity)[0])
    levels, blocks, hashes, _, l0 = runs._fused_views(s)
    n_runs = len(levels) + int(l0[0].shape[0])
    fn = _fused_query_fn(store.combiner, blocks, hashes, runs._b0, runs._h0,
                         4, "none", store.id_capacity <= (1 << 24)
                         and n_runs + 2 < 64, True, False)
    q = np.full(8, -1, np.int32)
    q[0] = row_id
    return fn.lower(q, levels, l0, None, None).compile().as_text()


def phase2(seed: int) -> None:
    from repro.db import recover_connector
    edges, ref = graph(PALLAS_SCALE, seed)
    hub, col = int(np.argmax(ref.out_deg)), int(np.argmax(ref.in_deg))

    def same_answers(got: dict, want: dict, when: str) -> None:
        check(got.keys() == want.keys(), f"query sets differ {when}")
        for name, a in got.items():
            b = want[name]
            check(a == b if name == "degree" else same(a, b),
                  f"use_pallas=True answer to {name} {when} differs from "
                  f"the default path's")

    with tempfile.TemporaryDirectory(prefix="d4m_smoke_") as wal_root:
        base = run_store("g14", PALLAS_SCALE, seed, f"{wal_root}/xla", ref,
                         edges)
        base["schema"].delete()
        pal = run_store("g14p", PALLAS_SCALE, seed, f"{wal_root}/pallas", ref,
                        edges, use_pallas=True)
        same_answers(pal["answers"], base["answers"], "after ingest")
        E = pal["schema"]
        t0 = time.perf_counter()
        E.pair.checkpoint()   # the memtable tail becomes an L0 run
        same_answers(ask("g14p", E, ref, seed), base["answers"],
                     "from flushed runs")
        text = fused_read_text(E.pair.table.store,
                               int(E.server.keydict.get(key(hub))))
        check("tpu_custom_call" in text,
              "no Pallas kernel in the use_pallas=True fused read")
        log(f"[g14p] every use_pallas=True answer equals the default path's, "
            f"before and after the checkpoint; the fused read compiles with "
            f"{text.count('tpu_custom_call')} tpu_custom_call site(s)")
        E.delete()
        _, pair = recover_connector(f"{wal_root}/pallas",
                                    ("g14p_Tedge", "g14p_TedgeT"))
        check(same(triples(pair[f"{key(hub)},", :]), ref.row(hub)),
              "row query after recovery")
        check(same(triples(pair[:, f"{key(col)},"]), ref.col(col)),
              "column query after recovery")
        log(f"[g14p] checkpoint + recover_connector in "
            f"{time.perf_counter() - t0:.3f} s; row and column queries after "
            f"recovery equal the reference; nnz={pair.nnz()}")
        last_wins(edges, f"{wal_root}/last")


def last_wins(edges, wal_root: str) -> None:
    """The edge table's last-wins combiner over DISTINCT values (the edge
    index), through a memtable flush and a major compaction: with all-ones
    values no read could tell which duplicate survived."""
    from repro.db import batching, dbsetup
    from repro.db.kvstore import StoreConfig
    u, v, rows, cols, _ = edges
    vals = np.arange(1, len(u) + 1, dtype=np.float32)  # exact below 2^24
    pairs = u.astype(np.int64) << 32 | v.astype(np.int64)
    uniq, first_rev = np.unique(pairs[::-1], return_index=True)
    want = ((uniq >> 32), (uniq & 0xFFFFFFFF),
            (len(u) - first_rev).astype(np.float64))
    cap = skew_capacity(shard_counts(u, v, rows, cols, vals, StoreConfig(),
                                     batching.DEFAULT_CHAR_BUDGET))
    server = dbsetup("smoke_last", capacity_per_shard=cap, wal_root=wal_root)
    T = server["last_T"]
    T.put_triple(rows, cols, vals)
    check(same(triples(T[:, :]), want), "last-wins after ingest")
    T.store.major_compact()
    check(same(triples(T[:, :]), want), "last-wins after major compaction")
    log(f"[last] last-wins over {len(u)} distinct values: {len(uniq)} "
        f"entries equal the reference after ingest and after compaction; "
        f"engine={T.store.engine_stats()}")
    server.drop("last_T")


# --------------------------------------------------------- four chips
def spmd_phase(devices, seed: int) -> None:
    """k SPMD ingestors against tablet servers, one shard per chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.data.graph500 import kronecker_edges
    from repro.db.kvstore import ShardedTable, StoreConfig, Tablet, shard_of
    from repro.db.spmd import (L0Stack, l0_stacked_empty,
                               make_spmd_lsm_compact_step,
                               make_spmd_lsm_ingest_step,
                               make_spmd_lsm_query_step,
                               make_spmd_lsm_scan_step, stacked_empty)

    S = len(devices)
    cfg = StoreConfig()
    slots, bcap = cfg.l0_slots, 1 << 16
    t0 = time.perf_counter()
    u, v = kronecker_edges(SCALE, EDGE_FACTOR, seed)
    m = len(u)
    id_cap = 1 << SCALE   # the vertex space, range-split over the chips
    # per-chip level capacity: phase 1's rule on the same graph under the
    # connector's default id space (where every vertex lands on shard 0)
    cap = skew_capacity(np.bincount(shard_of(u, S, cfg.id_capacity),
                                    minlength=S))
    pairs, mult = np.unique(u.astype(np.int64) << 32 | v, return_counts=True)
    ref_r = (pairs >> 32).astype(np.int32)
    ref_c = (pairs & 0xFFFFFFFF).astype(np.int32)
    ref_v = mult.astype(np.float32)         # sum combiner over ones
    owner = shard_of(ref_r, S, id_cap)
    log(f"[spmd] devices={S} scale={SCALE} edges={m} distinct={len(pairs)} "
        f"entries_per_shard={np.bincount(owner, minlength=S).tolist()} "
        f"capacity_per_shard={cap} ({time.perf_counter() - t0:.3f} s)")

    mesh = jax.make_mesh((S,), ("data",), devices=devices,
                         axis_types=(AxisType.Auto,))
    sh1 = NamedSharding(mesh, P("data"))
    sh2 = NamedSharding(mesh, P("data", None))
    sh3 = NamedSharding(mesh, P("data", None, None))
    # state is born sharded: each chip materialises only its own shard
    l0 = jax.jit(lambda: l0_stacked_empty(S, slots, S * bcap),
                 out_shardings=L0Stack(rows=sh3, cols=sh3, vals=sh3,
                                       k=sh1))()
    level = jax.jit(lambda: stacked_empty(S, cap),
                    out_shardings=Tablet(rows=sh2, cols=sh2, vals=sh2,
                                         n=sh1))()
    ingest = make_spmd_lsm_ingest_step(mesh, "data", S, id_cap,
                                       combiner="sum")
    compact = make_spmd_lsm_compact_step(mesh, "data", combiner="sum")

    # ingestor i streams its contiguous quarter of the edge list
    per = m // S
    steps = -(-per // bcap)
    ones = np.ones((S, bcap), np.float32)
    t0 = time.perf_counter()
    for t in range(steps):
        if int(np.asarray(l0.k).max()) == slots:   # full L0: compact first
            l0, level = compact(l0, level)
            check(int(np.asarray(level.n).max()) <= cap, "SPMD level overflow")
        lo, hi = t * bcap, min((t + 1) * bcap, per)
        br = np.full((S, bcap), np.iinfo(np.int32).max, np.int32)
        bc = np.full((S, bcap), np.iinfo(np.int32).max, np.int32)
        for i in range(S):
            br[i, :hi - lo] = u[i * per + lo:i * per + hi]
            bc[i, :hi - lo] = v[i * per + lo:i * per + hi]
        l0 = ingest(l0, jax.device_put(br, sh2), jax.device_put(bc, sh2),
                    jax.device_put(ones, sh2))
    jax.block_until_ready((l0, level))
    log(f"[spmd] ingest: {steps} steps of {S} x {bcap} edges in "
        f"{time.perf_counter() - t0:.3f} s (smoke wall); "
        f"L0 runs per chip={np.asarray(l0.k).tolist()} "
        f"level entries per chip={np.asarray(level.n).tolist()}")
    for d in devices:
        log(f"[spmd] {d}: {memory_line(d)}")
    holders = {s.device for leaf in jax.tree.leaves((l0, level))
               for s in leaf.addressable_shards}
    check(holders == set(devices), "SPMD state is not on every chip")

    t0 = time.perf_counter()
    table = ShardedTable("spmd_ref", num_shards=S, capacity_per_shard=cap,
                         id_capacity=id_cap, combiner="sum")
    chunk = table.mem_cap // 2
    for a in range(0, m, chunk):
        table.insert(u[a:a + chunk], v[a:a + chunk],
                     np.ones(len(u[a:a + chunk]), np.float32))
    log(f"[spmd] one-device ShardedTable(num_shards={S}) ingest in "
        f"{time.perf_counter() - t0:.3f} s (smoke wall)")

    # point reads before the final compaction: level run + full L0 stack
    QB, R = 16, 64
    deg = np.bincount(ref_r, minlength=id_cap)
    rng = np.random.default_rng(seed)
    q = np.full((S, QB), -1, np.int32)
    for s in range(S):
        mine = np.flatnonzero((deg > 0) & (deg <= R)
                              & (shard_of(np.arange(id_cap), S, id_cap) == s))
        pick = np.sort(rng.choice(mine, min(QB, len(mine)), replace=False))
        q[s, :len(pick)] = pick
    query = make_spmd_lsm_query_step(mesh, "data", combiner="sum",
                                     max_return=R)
    qc, qv, qk = (np.asarray(x) for x in query(l0, level,
                                               jax.device_put(q, sh2)))
    si, qi, ki = np.nonzero(qk)
    got = {(int(q[s, i]), int(qc[s, i, k])): float(qv[s, i, k])
           for s, i, k in zip(si, qi, ki)}
    ids = q[q >= 0]
    tr, tc, tv = table.query_rows(ids)
    want = {(int(a), int(b)): float(x) for a, b, x in zip(tr, tc, tv)}
    sel = np.isin(ref_r, ids)
    check(want == {(int(a), int(b)): float(x) for a, b, x in
                   zip(ref_r[sel], ref_c[sel], ref_v[sel])},
          "one-device table point reads vs numpy")
    check(got == want, f"SPMD point reads ({len(got)} vs {len(want)})")
    log(f"[spmd] query step: {len(ids)} rows, {len(got)} entries equal the "
        f"one-device table and numpy")

    # range scan: a global range across the shard 1 / shard 2 boundary
    lo_g, hi_g = 2 * id_cap // S - 300, 2 * id_cap // S + 300
    bounds = np.zeros((S, 2), np.int32)
    for s in range(S):
        slo, shi = s * id_cap // S, (s + 1) * id_cap // S
        if max(lo_g, slo) < min(hi_g, shi):
            bounds[s] = (max(lo_g, slo), min(hi_g, shi))
    in_range = (ref_r >= lo_g) & (ref_r < hi_g)
    width = 1 << int(max(np.bincount(owner[in_range], minlength=S).max(),
                         16) - 1).bit_length()
    scan = make_spmd_lsm_scan_step(mesh, "data", combiner="sum", width=width)
    sr, sc, sv, sk, scnt = scan(l0, level, jax.device_put(bounds, sh2))
    sr, sc, sv, sk = (np.asarray(x) for x in (sr, sc, sv, sk))
    check(int(np.asarray(scnt).max()) <= width, "SPMD scan window overflow")
    got = [(int(a), int(b), float(x)) for s in range(S)
           for a, b, x in zip(sr[s][sk[s]], sc[s][sk[s]], sv[s][sk[s]])]
    tr, tc, tv = table.scan_range(lo_g, hi_g)
    want = [(int(a), int(b), float(x)) for a, b, x in zip(tr, tc, tv)]
    check(want == [(int(a), int(b), float(x)) for a, b, x in
                   zip(ref_r[in_range], ref_c[in_range], ref_v[in_range])],
          "one-device table range scan vs numpy")
    check(got == want, f"SPMD range scan ({len(got)} vs {len(want)})")
    log(f"[spmd] scan step: [{lo_g}, {hi_g}) -> {len(got)} entries equal the "
        f"one-device table and numpy")

    # final compaction: every chip's level run is its whole shard
    t0 = time.perf_counter()
    l0, level = compact(l0, level)
    n = np.asarray(level.n)
    rows, cols, vals = (np.asarray(x) for x in (level.rows, level.cols,
                                                level.vals))
    got = tuple(np.concatenate([x[s, :n[s]] for s in range(S)])
                for x in (rows, cols, vals))
    want = table.scan()
    check(all(np.array_equal(g, w) for g, w in zip(got, want)),
          "SPMD compacted levels vs one-device table")
    check(all(np.array_equal(g, w) for g, w in zip(got, (ref_r, ref_c,
                                                          ref_v))),
          "SPMD compacted levels vs numpy")
    log(f"[spmd] final compaction: {int(n.sum())} entries "
        f"(per chip {n.tolist()}) equal the one-device table and numpy "
        f"({time.perf_counter() - t0:.3f} s)")
    for d in devices:
        log(f"[spmd] {d}: {memory_line(d)}")


# ------------------------------------------------------------------ main
def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the SPMD path over a 4-chip mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_platforms", "tpu")   # no silent CPU fallback
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    devices = jax.devices()
    check(devices[0].platform == "tpu", f"platform {devices[0].platform}")
    check(len(devices) >= args.chips,
          f"{args.chips} chips asked for, {len(devices)} found")
    from repro.kernels import common
    check(common.INTERPRET is False, "Pallas kernels would run interpreted")
    log(f"devices: {len(devices)} x {devices[0].device_kind}; compile cache "
        f"{cache_dir} ({len(list(Path(cache_dir).glob('*')))} entries)")

    t_all = time.perf_counter()
    if args.chips == 4:
        phases = [("spmd", lambda: spmd_phase(devices[:4], args.seed))]
    else:
        phases = [("phase 1", lambda: phase1(args.seed)),
                  ("phase 2", lambda: phase2(args.seed))]
    for name, run in phases:
        t0 = time.perf_counter()
        run()
        gc.collect()
        log(f"== {name} passed in {time.perf_counter() - t0:.3f} s "
            f"(smoke wall); compile cache hits={cache_events['hits']} "
            f"misses={cache_events['misses']}")
    log(f"total {time.perf_counter() - t_all:.3f} s (smoke wall)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
