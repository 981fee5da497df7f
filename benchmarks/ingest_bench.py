"""Paper Fig. 3: ingest rate (edges/s) vs #ingest processes and graph scale.

Protocol mirrors §IV-A: k SPMD ingestors each generate a Graph500
unpermuted power-law graph (scale s, degree 16) and ingest adjacency
triples simultaneously in ~500k-char batches; the optimized connector
(sorted tablets + routing + merge compaction) is compared against the
naive reference connector (the Matlab-D4M stand-in). CPU scales are
reduced vs the paper (12-18 -> 10-14); the shapes of the curves are the
reproduction target, not absolute rates.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List

import numpy as np

from repro.data.graph500 import graph500_triples
from repro.db.batching import batch_triples
from repro.db.kvstore import ShardedTable, shard_of
from repro.db.naive import NaiveTable
from repro.compile_cache import enable_compile_cache
from repro.core.dictionary import StringDict
from repro.kernels.common import I32_MAX
from repro.obs import Histogram, default_registry
from repro.train.elastic import WorkQueue

import jax
import jax.numpy as jnp


def _prepare(k: int, scale: int, char_budget: int):
    """Per-ingestor batch lists (string triples already batched)."""
    per_ingestor = []
    for i in range(k):
        r, c, v = graph500_triples(scale, 16, seed=100 + i)
        per_ingestor.append(list(batch_triples(r, c, v, char_budget)))
    return per_ingestor


def run_optimized(k: int, scale: int, char_budget: int = 500_000,
                  use_pallas: bool = False, steal: bool = False,
                  engine: str = "lsm") -> dict:
    """k simulated SPMD ingestors submitting one ~500k-char batch each per
    step. One CPU executes the k ingestors' work SERIALLY, so the measured
    wall is Σ-of-workers; ``parallel_edges_per_s`` (= serial rate × k) is
    the perfect-SPMD projection the shard_map path realizes on a real mesh
    (each ingestor's batch is an independent route+append, flushes are
    per-shard local — no cross-worker serialization)."""
    batches = _prepare(k, scale, char_budget)
    total_edges = sum(sum(len(b[0]) for b in bl) for bl in batches)
    # size tablet capacity from the ACTUAL shard skew (unpermuted power-law
    # graphs pile the hubs into the low-id shard) — Accumulo pre-split
    # planning from a sample
    probe = StringDict()
    counts = np.zeros(k, np.int64)
    bmax = 1
    for bl in batches:
        for b in bl:
            ids = probe.encode(b[0])
            counts += np.bincount(shard_of(ids, k, 1 << 22), minlength=k)
            bmax = max(bmax, len(b[0]))
    cap = max(1 << 12, int(counts.max() * 1.3))
    bcap = 1 << (bmax - 1).bit_length()
    # single engine: bulk-load mode, memtable sized to the tablet -> O(1)
    # compactions total (repeated merges into one run are quadratic).
    # lsm engine: memtable stays batch-sized — leveling amortizes instead.
    mem = max(cap, 4 * bcap) if engine == "single" else max(4 * bcap, cap // 8)
    mk = lambda name: ShardedTable(
        name, num_shards=k, capacity_per_shard=cap, batch_cap=bcap,
        id_capacity=1 << 22, use_pallas=use_pallas, memtable_cap=mem,
        engine=engine)
    # warmup on a throwaway store: compiles append (dominant padded batch
    # shape) + the flush path; jit caches are module-level, so the timed
    # store reuses them
    warm = mk("bench_warm")
    warm.insert(np.zeros(bcap, np.int32), np.zeros(bcap, np.int32),
                np.ones(bcap, np.float32))
    warm.flush()
    store = mk("bench")
    keydict = StringDict()

    t0 = time.time()
    if steal:  # straggler-mitigation mode: batches pulled from a work queue
        flat = [b for bl in batches for b in bl]
        q = WorkQueue(flat)
        while not q.complete():
            for w in range(k):
                bid, b = q.claim(w)
                if bid is None:
                    continue
                rid = keydict.encode(b[0])
                cid = keydict.encode(b[1])
                store.insert(rid, cid, b[2])
                q.ack(bid)
    else:
        step = 0
        while any(step < len(bl) for bl in batches):
            for bl in batches:           # each ingestor submits its batch
                if step < len(bl):
                    store.insert(keydict.encode(bl[step][0]),
                                 keydict.encode(bl[step][1]),
                                 bl[step][2].astype(np.float32))
            step += 1
    store.flush()
    if store.engine == "lsm":
        store._runs.l0_rows.block_until_ready()
    else:
        store.tablets.rows.block_until_ready()
    wall = time.time() - t0
    return {"k": k, "scale": scale, "engine": engine, "edges": total_edges,
            "wall_s": wall, "edges_per_s": total_edges / wall,
            "parallel_edges_per_s": total_edges / wall * k,
            "nnz": store.nnz()}


def run_naive(k: int, scale: int, char_budget: int = 500_000) -> dict:
    batches = _prepare(k, scale, char_budget)
    total_edges = sum(sum(len(b[0]) for b in bl) for bl in batches)
    tab = NaiveTable("bench")
    t0 = time.time()
    step = 0
    while any(step < len(bl) for bl in batches):
        for bl in batches:
            if step < len(bl):
                tab.put_triple(*bl[step])
        step += 1
    wall = time.time() - t0
    return {"k": k, "scale": scale, "edges": total_edges, "wall_s": wall,
            "edges_per_s": total_edges / wall}


def fig3(ks=(1, 2, 4, 8, 16), scales=(10, 12, 14), char_budget=500_000):
    rows = []
    for scale in scales:
        for k in ks:
            opt = run_optimized(k, scale, char_budget)
            nai = run_naive(k, scale, char_budget)
            rows.append({
                "scale": scale, "k": k, "edges": opt["edges"],
                "opt_edges_per_s": opt["edges_per_s"],
                "naive_edges_per_s": nai["edges_per_s"],
                "speedup": opt["edges_per_s"] / nai["edges_per_s"],
            })
            print(f"scale={scale} k={k:2d} edges={opt['edges']:>9,} "
                  f"opt={opt['edges_per_s']:>12,.0f} e/s "
                  f"naive={nai['edges_per_s']:>12,.0f} e/s")
    return rows


def batch_sweep(scale=12, k=4, budgets=(50_000, 200_000, 500_000, 2_000_000)):
    """The paper's 500k-char batch knob (§V crossover discussion)."""
    rows = []
    for b in budgets:
        r = run_optimized(k, scale, char_budget=b)
        rows.append({"char_budget": b, "edges_per_s": r["edges_per_s"]})
        print(f"budget={b:>9,} -> {r['edges_per_s']:>12,.0f} e/s")
    return rows


def engine_compare(entries_per_shard: int = 1 << 18, shards: int = 2,
                   batch: int = 1 << 14, memtable: int = 1 << 15,
                   n_queries: int = 2048, seed: int = 0,
                   repeats: int = 1) -> dict:
    """A/B the storage engines on identical int-triple streams.

    Demonstrates the LSM claim: flush cost scales with MEMTABLE size, not
    table capacity — the single-run engine re-merges the whole O(capacity)
    tablet on every memtable fill, so its ingest rate decays as the table
    grows, while the LSM engine's minor compactions stay O(memtable) with
    amortized leveling. The query phase measures point reads and verifies
    the LSM path never flushes (memtable untouched).

    ``repeats`` interleaves that many (single, lsm) ingest runs — fresh
    store each — and reports the MEDIAN per-repeat lsm/single wall ratio:
    shared-runner load hits both engines of a repeat pair alike, so the
    ratio the CI bench gate tracks stays stable even when absolute walls
    swing. Per-engine rates report the best wall (one-sided noise
    filter). The same repeats double as the tail NOISE FLOOR probe: each
    repeat yields one p99/p50 amplification per (engine, op) family —
    ingest from that repeat's store histograms, query from a per-round
    sampling pass — and the max-min spread across repeats lands in
    ``tail_noise``, which the CI gate uses as the jitter allowance when
    gating tail ratios (see ``benchmarks.gate.compare_tails``).

    A final query-batch sweep (64..4096 ids) times the FIRST call at each
    size — the one-shot serving semantics ``queries_per_s`` has always
    used — for the LSM tiled fused path, its per-run baseline, AND the
    legacy engine (steady-state rates ride along as advisory columns);
    ``lsm_query_speedup`` — the WORST lsm/single ratio across the sweep —
    is the large-batch read claim the CI gate tracks (pre-tiling, batches
    past ``fused_q_limit`` fell back to one launch per resident run and
    lost ~6x to the legacy engine even before its per-size retrace cost).
    """
    id_cap = 1 << 22
    total = entries_per_shard * shards
    cap = int(entries_per_shard * 1.25)
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, id_cap, total).astype(np.int32)
    cols = rng.integers(0, 1 << 16, total).astype(np.int32)
    vals = rng.normal(size=total).astype(np.float32)
    out = {"config": {"entries_per_shard": entries_per_shard,
                      "shards": shards, "batch": batch,
                      "memtable": memtable, "n_queries": n_queries,
                      "repeats": repeats},
           "engines": {}}
    q = rng.choice(rows, n_queries).astype(np.int32)

    def mk(engine, name):
        return ShardedTable(
            name, num_shards=shards, capacity_per_shard=cap,
            batch_cap=batch, id_capacity=id_cap, memtable_cap=memtable,
            engine=engine)

    # ---- phase 1: interleaved ingest timing (single/lsm back-to-back
    # within each repeat, so load noise cancels in the per-repeat ratio)
    walls = {"single": [], "lsm": []}
    stores = {}
    for engine in ("single", "lsm"):
        warm = mk(engine, f"warm_{engine}")  # compile appends off-clock
        warm.insert(rows[:batch], cols[:batch], vals[:batch])
        warm.flush()
    for rep in range(max(repeats, 1)):
        for engine in ("single", "lsm"):
            store = mk(engine, f"cmp_{engine}_{rep}")
            store.warmup()  # compile flush + every compaction depth
            t0 = time.time()
            for i in range(0, total, batch):
                store.insert(rows[i:i + batch], cols[i:i + batch],
                             vals[i:i + batch])
            store.flush()
            walls[engine].append(time.time() - t0)
            stores[engine] = store
    ratios = sorted(s / l for s, l in zip(walls["single"], walls["lsm"]))

    # ---- phase 2: flush-cost probe + query phase per engine
    reg = default_registry()
    mem_pre_read = {}
    tail_noise: dict = {}

    def _amp(h):
        p = h.percentiles()
        return p["p99"] / p["p50"] if p["p50"] else None

    for engine in ("single", "lsm"):
        store = stores[engine]
        ingest_wall = min(walls[engine])
        # per-batch ingest latency percentiles, pooled across every repeat's
        # store (repro.obs histograms populated by ShardedTable.insert
        # during the timed phase — tail latency beside the throughput rows).
        # Per-repeat p99/p50 amps feed the tail noise floor.
        h_ing = Histogram(reg, "pooled_ingest", {})
        ing_amps = []
        for rep in range(max(repeats, 1)):
            h_rep = Histogram(reg, "rep_ingest", {})
            for h in reg.series("db_op_latency_s",
                                table=f"cmp_{engine}_{rep}", op="ingest"):
                h_rep.merge(h)
            h_ing.merge(h_rep)
            a = _amp(h_rep)
            if a:
                ing_amps.append(a)
        if ing_amps:
            tail_noise[f"{engine}_ingest_p99_over_p50"] = {
                "repeats": ing_amps,
                "spread": max(ing_amps) - min(ing_amps)}
        # explicit flush-cost probe at FULL table size: the single-run
        # engine pays O(capacity) to absorb one memtable, the LSM engine
        # O(memtable) — the core scaling claim, measured directly
        half = memtable // 2
        store.insert(rows[:half], cols[:half], vals[:half])
        t0 = time.time()
        store.flush()
        if engine == "lsm":
            store._runs.l0_rows.block_until_ready()
        else:
            store.tablets.rows.block_until_ready()
        flush_wall = time.time() - t0
        # leave fresh writes in the memtable so the query path must merge
        # memtable + runs (the no-flush read claim), then compile the
        # engine's STATIC serving shapes off-clock: the LSM fused path has
        # exactly two (point bucket + query tile) and the tile serves any
        # batch size; the legacy engine has no size-independent shape to
        # warm — its first read also absorbs the tail into the tablet
        store.insert(rows[:256], cols[:256], vals[:256])
        store.warm_reads()
        # per-call query latency sampling: repeated SMALL batches (the
        # tracked queries_per_s protocol lives in the sweep below and
        # stays one-shot) so p50/p99 reflect per-dispatch read latency.
        # One sampling round per repeat: reported percentiles pool every
        # round, per-round amps feed the tail noise floor.
        qb = 16
        store.query_rows(q[:qb])  # warm the small-batch jit off the clock
        h_q = Histogram(reg, "pooled_query", {})
        q_amps = []
        for _rnd in range(max(repeats, 1)):
            store._h_query.reset()
            for i in range(64):
                j = (i * qb) % max(n_queries - qb, 1)
                store.query_rows(q[j:j + qb])
            h_q.merge(store._h_query)
            a = _amp(store._h_query)
            if a:
                q_amps.append(a)
        if q_amps:
            tail_noise[f"{engine}_query_p99_over_p50"] = {
                "repeats": q_amps,
                "spread": max(q_amps) - min(q_amps)}
        lat_q = h_q.percentiles()
        mem_pre_read[engine] = store._mem_n.copy()
        out["engines"][engine] = {
            "ingest_wall_s": ingest_wall,
            "entries_per_s": total / ingest_wall,
            "ingest_batch_p50_ms": h_ing.quantile(0.50) * 1e3,
            "ingest_batch_p99_ms": h_ing.quantile(0.99) * 1e3,
            "flush_at_full_table_s": flush_wall,
            "query_p50_ms": lat_q["p50"] * 1e3,
            "query_p99_ms": lat_q["p99"] * 1e3,
        }
        print(f"engine={engine:6s} ingest={total / ingest_wall:>12,.0f} e/s "
              f"full-table flush={flush_wall * 1e3:>8.1f} ms")
    # ---- phase 3: query batch-size sweep — the tiled fused read claim.
    # Protocol: FIRST-CALL wall per batch size, the same one-shot
    # semantics the tracked ``queries_per_s`` has always had ("a fresh
    # batch size arrives at the serving process"). Each engine pays what
    # its architecture charges on that first call: the legacy engine's
    # query shape follows the batch, so every novel size retraces; the
    # per-run baseline additionally launches once per resident run; the
    # tiled fused path serves ANY size from the one tile shape
    # ``warm_reads()`` precompiled. Steady-state rates (best of 3 warm
    # repeats) ride along as advisory columns: on this multi-run mixed
    # state the single tablet's warm read stays ahead at large batches
    # (classic LSM read amplification) — the gated claim is the serving
    # trajectory, where shape-churn dominates, and the regression this
    # metric guards is the old per-run fallback losing ~6x even there.
    def timed(store, qq, reps=3):
        t0 = time.time()
        res = store.query_rows(qq)
        first = time.time() - t0
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            store.query_rows(qq)
            best = min(best, time.time() - t0)
        return first, best, res

    sweep = []
    q_pool = rng.choice(rows, max(4096, n_queries)).astype(np.int32)
    lsm_store = stores["lsm"]
    for size in sorted({64, 256, 1024, 2048, 4096} | {n_queries}):
        qq = q_pool[:size]
        s_first, s_steady, s_res = timed(stores["single"], qq)
        lsm_store.fused_reads = True
        f_first, f_steady, f_res = timed(lsm_store, qq)
        lsm_store.fused_reads = False
        p_first, p_steady, _ = timed(lsm_store, qq)
        lsm_store.fused_reads = True
        sweep.append({"batch": size,
                      "single_qps": size / s_first,
                      "lsm_qps": size / f_first,
                      "lsm_perrun_qps": size / p_first,
                      "lsm_vs_single": s_first / f_first,
                      "fused_vs_perrun": p_first / f_first,
                      "single_steady_qps": size / s_steady,
                      "lsm_steady_qps": size / f_steady,
                      "lsm_perrun_steady_qps": size / p_steady,
                      "lsm_vs_single_steady": s_steady / f_steady})
        if size == n_queries:
            for eng, first, res in (("single", s_first, s_res),
                                    ("lsm", f_first, f_res)):
                out["engines"][eng].update(
                    query_wall_s=first, queries_per_s=size / first,
                    query_hits=int(len(res[0])))
        print(f"query batch={size:5d} single={size / s_first:>10,.0f} q/s "
              f"lsm={size / f_first:>10,.0f} q/s "
              f"perrun={size / p_first:>10,.0f} q/s "
              f"lsm/single={s_first / f_first:.2f}x "
              f"fused/perrun={p_first / f_first:.2f}x "
              f"(steady lsm/single={s_steady / f_steady:.2f}x)")
    # serving reads must merge the memtable tail on-device, never flush
    # (the single engine absorbed its tail at warm_reads, off-clock)
    for engine in ("single", "lsm"):
        out["engines"][engine]["flushed_on_read"] = bool(
            (stores[engine]._mem_n != mem_pre_read[engine]).any())
        out["engines"][engine]["stats"] = stores[engine].engine_stats()
    out["query_sweep"] = sweep
    out["tail_noise"] = tail_noise
    # worst-case first-call ratio across the sweep: the gate metric — LSM
    # reads must beat the legacy engine at EVERY batch size it serves
    out["lsm_query_speedup"] = min(r["lsm_vs_single"] for r in sweep)

    # median of the per-repeat interleaved ratios (== best-wall ratio
    # when repeats == 1): the trajectory metric the CI bench gate tracks
    out["lsm_ingest_speedup"] = ratios[len(ratios) // 2]
    out["lsm_ingest_speedup_all"] = ratios
    print(f"LSM ingest speedup over single-run: "
          f"{out['lsm_ingest_speedup']:.2f}x "
          f"at {entries_per_shard:,} entries/shard "
          f"(median of {len(ratios)} interleaved repeats)")
    return out


def pair_ingest_advisory(entries_per_shard: int = 1 << 14, shards: int = 2,
                         batch: int = 1 << 12, memtable: int = 1 << 13,
                         seed: int = 5) -> dict:
    """Dual-ingest write-amplification advisory for transpose pairs: the
    same triple stream into a single table vs an engine-maintained pair
    (``transpose=True``). The pair writes every entry to BOTH sibling
    memtables (~2x device write amplification) but logs ONE pair-tagged
    WAL record per batch (1x log bytes, one fsync — NOT 2x). Advisory
    only, never gated: absolute walls on shared runners are noisy and the
    pair cost model is structural."""
    import os
    import tempfile

    id_cap = 1 << 22
    total = entries_per_shard * shards
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, id_cap, total).astype(np.int32)
    cols = rng.integers(0, id_cap, total).astype(np.int32)
    vals = rng.normal(size=total).astype(np.float32)
    reg = default_registry()
    out = {"config": {"entries_per_shard": entries_per_shard,
                      "shards": shards, "batch": batch,
                      "memtable": memtable}}
    with tempfile.TemporaryDirectory() as td:
        walls = {}

        def mk(name, transpose, wal):
            return ShardedTable(
                f"adv_{name}", num_shards=shards,
                capacity_per_shard=int(entries_per_shard * 2.5),
                batch_cap=batch, id_capacity=id_cap,
                memtable_cap=memtable, engine="lsm",
                wal_dir=os.path.join(td, wal) if wal else None,
                transpose=transpose)

        # off-clock warm pass per CONFIG (not just per store): both runs
        # must hit fully compiled paths or the first config eats every
        # first-compile cost and the ratio flips
        for name, transpose in (("single", False), ("pair", True)):
            warm = mk(f"warm_{name}", transpose, None)
            warm.warmup()
            for i in range(0, min(total, 4 * batch), batch):
                warm.insert(rows[i:i + batch], cols[i:i + batch],
                            vals[i:i + batch])
            warm.flush()
            warm.close()
        for name, transpose in (("single", False), ("pair", True)):
            st = mk(name, transpose, name)
            st.warmup()
            t0 = time.time()
            for i in range(0, total, batch):
                st.insert(rows[i:i + batch], cols[i:i + batch],
                          vals[i:i + batch])
            st.flush()
            walls[name] = time.time() - t0
            out[f"wal_bytes_{name}"] = sum(
                c.value for c in reg.series("wal_append_bytes", log=name))
            st.close()
    out.update({
        "ingest_s_single": walls["single"],
        "ingest_s_pair": walls["pair"],
        "pair_ingest_slowdown": walls["pair"] / walls["single"],
        "wal_write_amp": out["wal_bytes_pair"] / out["wal_bytes_single"],
    })
    print(f"pair ingest advisory: slowdown="
          f"{out['pair_ingest_slowdown']:.2f}x "
          f"wal_write_amp={out['wal_write_amp']:.2f}x "
          f"({total:,} entries)")
    return {"pair_ingest": out}


def zipf_skew_advisory(s: float, entries_per_shard: int = 1 << 14,
                       shards: int = 4, batch: int = 1 << 12,
                       memtable: int = 1 << 13, seed: int = 7) -> dict:
    """Skewed-ingest A/B: static hash routing vs dynamic tablets under a
    Zipf(s) row stream over a CONTIGUOUS hot range (unpermuted power-law
    keys pile into the low-id shard — the Fig. 3 graph500 shape, worst
    case for a fixed pre-split). The dynamic table runs
    ``maybe_rebalance()`` every few batches, splitting the hot range and
    spreading tablets across shards; the static table keeps the uniform
    map. Reports the HOT-SHARD serving rate (queries/s on a Zipf-drawn id
    batch, whose traffic the static map concentrates on one shard) and
    the routed load balance (max/mean per-shard share of a fresh Zipf
    window) for both, plus ``zipf_split_vs_static`` — the balance
    improvement ratio the CI gate can track once a baseline carries it.
    Advisory: single-host walls don't show the mesh-level win; the
    balance ratio is the structural claim."""
    id_cap = 1 << 22
    total = entries_per_shard * shards
    rng = np.random.default_rng(seed)
    rows = (rng.zipf(s, total) % id_cap).astype(np.int32)
    cols = rng.integers(0, 1 << 16, total).astype(np.int32)
    vals = np.ones(total, np.float32)
    cap = int(total * 1.25)  # static piles ~everything onto shard 0

    def mk(name, dynamic):
        return ShardedTable(name, num_shards=shards,
                            capacity_per_shard=cap, batch_cap=batch,
                            id_capacity=id_cap, memtable_cap=memtable,
                            engine="lsm", dynamic_tablets=dynamic)

    out = {"config": {"zipf_s": s, "entries_per_shard": entries_per_shard,
                      "shards": shards, "batch": batch,
                      "memtable": memtable}}
    walls, qps, balance = {}, {}, {}
    for name, dynamic in (("static", False), ("dynamic", True)):
        warm = mk(f"zwarm_{name}", dynamic)  # compile off-clock
        warm.warmup()
        warm.insert(rows[:batch], cols[:batch], vals[:batch])
        warm.flush()
        st = mk(f"zipf_{name}", dynamic)
        st.warmup()
        t0 = time.time()
        for step, i in enumerate(range(0, total, batch)):
            st.insert(rows[i:i + batch], cols[i:i + batch],
                      vals[i:i + batch])
            if dynamic and step % 4 == 3:
                st.maybe_rebalance()
        st.flush()
        st._runs.l0_rows.block_until_ready()
        walls[name] = time.time() - t0
        # hot-shard serving rate: Zipf-drawn query batches, first call
        # warmed off-clock then best-of-3 (per-call dispatch cost is the
        # signal; the static map funnels every dispatch to one shard)
        q = (rng.zipf(s, 2048) % id_cap).astype(np.int32)
        st.warm_reads()
        st.query_rows(q)
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            st.query_rows(q)
            best = min(best, time.time() - t0)
        qps[name] = len(q) / best
        fresh = (rng.zipf(s, 1 << 15) % id_cap).astype(np.int64)
        routed = (st.tablet_map.owner_of(fresh) if dynamic
                  else shard_of(fresh.astype(np.int32), shards, id_cap))
        per = np.bincount(routed, minlength=shards)
        balance[name] = float(per.max() / per.mean())
        if dynamic:
            out["tablets"] = st.tablet_map.to_manifest()
    out.update({
        "ingest_s_static": walls["static"],
        "ingest_s_dynamic": walls["dynamic"],
        "hot_queries_per_s_static": qps["static"],
        "hot_queries_per_s_dynamic": qps["dynamic"],
        "load_balance_static": balance["static"],
        "load_balance_dynamic": balance["dynamic"],
        "zipf_split_vs_static": balance["static"] / balance["dynamic"],
    })
    print(f"zipf(s={s}) advisory: balance static="
          f"{balance['static']:.2f} dynamic={balance['dynamic']:.2f} "
          f"({out['zipf_split_vs_static']:.2f}x better) "
          f"hot q/s static={qps['static']:>10,.0f} "
          f"dynamic={qps['dynamic']:>10,.0f}")
    return {"zipf": out}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small fast engine A/B + JSON artifact (CI mode)")
    ap.add_argument("--out", default="BENCH_ingest.json",
                    help="JSON output path for --smoke/--compare")
    ap.add_argument("--compare", action="store_true",
                    help="full-size engine A/B (2^18 entries/shard)")
    ap.add_argument("--entries-per-shard", type=int, default=None)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=1,
                    help="interleave N (single, lsm) ingest runs; the "
                         "reported lsm_ingest_speedup is the MEDIAN "
                         "per-repeat ratio (noise-robust CI gate metric)")
    ap.add_argument("--zipf", type=float, default=None, metavar="S",
                    help="also run the Zipf(S) skew A/B (static hash vs "
                         "dynamic tablets): hot-shard queries/s + routed "
                         "load balance, advisory zipf_split_vs_static "
                         "ratio in the JSON artifact")
    ap.add_argument("--metrics-out", default=None,
                    help="also dump the full repro.obs registry snapshot "
                         "(counters + latency histograms) as JSON")
    ap.add_argument("--bundle-out", default=None,
                    help="also write a debug bundle (zip: metrics + "
                         "Prometheus text + slow traces/flight recordings "
                         "+ bench result) — the CI diagnostic artifact")
    args = ap.parse_args()
    if args.smoke or args.compare:
        eps = args.entries_per_shard or (1 << 14 if args.smoke else 1 << 18)
        mem = max(1 << 12, min(1 << 15, eps // 8))
        result = engine_compare(entries_per_shard=eps, shards=args.shards,
                                batch=max(1 << 10, mem // 2), memtable=mem,
                                repeats=args.repeats)
        result.update(pair_ingest_advisory(entries_per_shard=min(eps, 1 << 14),
                                           shards=args.shards))
        if args.zipf:
            result.update(zipf_skew_advisory(args.zipf,
                                             entries_per_shard=min(eps,
                                                                   1 << 14)))
        result["mode"] = "smoke" if args.smoke else "compare"
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {args.out}")
        if args.metrics_out:
            default_registry().dump(args.metrics_out)
            print(f"wrote {args.metrics_out}")
        if args.bundle_out:
            from repro.obs.export import write_debug_bundle
            write_debug_bundle(args.bundle_out,
                               extra={"bench_result": result})
            print(f"wrote {args.bundle_out}")
        return
    fig3()
    batch_sweep()


if __name__ == "__main__":
    main()
