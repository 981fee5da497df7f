"""Paper Fig. 4: query rate (edges returned/s) vs queried-vertex degree.

Protocol mirrors §IV-B: ingest a large power-law graph + degree table
(D4M 2.0 schema, 8 ingestors), pick vertices with out/in degree near
{1, 10, 100, 1000} via the degree table, run the four query types —
single-vertex row (SVR), single-vertex column (SVC), multi-vertex row
(MVR, 5 vertices), multi-vertex column (MVC) — and measure edges/s.
Column queries exercise the transpose-table routing.

``fused_read_compare`` is the read-path A/B behind ``BENCH_query.json``:
point-read latency of the fused single-dispatch LSM path vs the per-run
baseline as the number of resident runs per shard grows (fig4 SVR/SVC
latency is dispatch-bound, so fused wins once several runs are resident).
``scan_read_compare`` is the range-scan A/B: one fused fence-to-fence
dispatch per shard vs expanding the range into an id list of point
queries (the pre-scan selector path), swept over range lengths.
"""
from __future__ import annotations

import json
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.data.graph500 import graph500_triples
from repro.db import EdgeSchema, NaiveTable, dbsetup
from repro.db.kvstore import ShardedTable


def build_graph(scale: int = 13, ingestors: int = 8, use_pallas: bool = False):
    server = dbsetup("querybench", num_shards=4,
                     capacity_per_shard=1 << 21, batch_cap=1 << 16,
                     id_capacity=1 << 22, use_pallas=use_pallas)
    g = EdgeSchema(server, "g")
    naive = NaiveTable("naive")
    for i in range(ingestors):
        r, c, v = graph500_triples(scale, 16, seed=300 + i)
        g.put_triple(r, c, v)
        naive.put_triple(r, c, v)
    return g, naive


def _measure(fn, reps: int) -> tuple:
    t0 = time.time()
    edges = 0
    for _ in range(reps):
        a = fn()
        edges += a.nnz()
    return edges, time.time() - t0


def fig4(scale: int = 13, degrees=(1, 10, 100, 1000), reps: int = 5):
    g, naive = build_graph(scale)
    rng = np.random.default_rng(0)
    rows = []
    for target in degrees:
        for kind, sel in (("out", "row"), ("in", "col")):
            vs = g.deg.vertices_with_degree(target, kind=kind)
            if len(vs) == 0:
                continue
            single = str(rng.choice(vs)) + ","
            multi = "".join(str(v) + "," for v in
                            rng.choice(vs, size=min(5, len(vs)),
                                       replace=False))
            for qname, q in (("SV", single), ("MV", multi)):
                if sel == "row":
                    fn = lambda q=q: g[q, :]
                    fn_n = lambda q=q: naive[q, :]
                else:
                    fn = lambda q=q: g[:, q]
                    fn_n = lambda q=q: naive[:, q]
                fn()  # warmup (compile)
                edges, wall = _measure(fn, reps)
                edges_n, wall_n = _measure(fn_n, max(reps // 5, 1))
                label = f"{qname}{'R' if sel == 'row' else 'C'}"
                rows.append({
                    "degree": target, "query": label,
                    "edges_returned": edges // reps,
                    "opt_edges_per_s": edges / wall,
                    "naive_edges_per_s": edges_n / wall_n if edges_n else 0.0,
                })
                print(f"deg~{target:>5} {label}: {edges // reps:>7,} edges "
                      f"opt={edges / wall:>12,.0f} e/s "
                      f"naive={(edges_n / wall_n if edges_n else 0):>12,.0f} e/s")
    return rows


def _build_lsm_serving_state(n_l0_runs: int, with_levels: bool,
                             shards: int = 2, mem: int = 4096,
                             tail: int = 256, seed: int = 0,
                             transpose: bool = False,
                             col_space: int = 1 << 10):
    """An LSM table in point-read serving shape: ``n_l0_runs`` resident L0
    runs (plus two leveled runs when ``with_levels``) and a small unflushed
    memtable tail. Key ranges overlap across runs so blooms mostly hit —
    the per-run baseline gets no cheap range-skips. ``transpose=True``
    builds an engine-maintained pair (column-selector benches);
    ``col_space`` widens the col universe so col ranges behave like row
    ranges."""
    st = ShardedTable("qbench" + ("_pair" if transpose else ""),
                      num_shards=shards,
                      capacity_per_shard=1 << 18, batch_cap=mem,
                      id_capacity=1 << 22, memtable_cap=mem,
                      l0_slots=max(8, n_l0_runs + 2), engine="lsm",
                      transpose=transpose)
    rng = np.random.default_rng(seed)

    def fill(n):
        st.insert(rng.integers(0, 1 << 22, n).astype(np.int32),
                  rng.integers(0, col_space, n).astype(np.int32),
                  rng.normal(size=n).astype(np.float32))

    if with_levels:
        for _ in range(16):  # two L0 fills -> auto-majors land in L2
            fill(mem)
            st.flush()
        fill(mem)            # small merge -> resident L1 as well
        st.flush()
        st.major_compact()
    for _ in range(n_l0_runs):
        fill(mem)
        st.flush()
    fill(tail)              # unflushed memtable tail
    return st


def fused_read_compare(reps: int = 100, q_rows: int = 4,
                       out: str = None) -> dict:
    """Point-read latency A/B: fused single-dispatch vs per-run baseline,
    sweeping resident runs per shard (fig4 SVR-shaped tiny queries, where
    the per-run path is dispatch-bound). Writes ``BENCH_query.json``."""
    rng = np.random.default_rng(3)
    result = {"config": {"reps": reps, "q_rows": q_rows}, "rows": []}
    scenarios = [(2, False), (4, False), (6, False), (2, True)]
    for n_l0, with_levels in scenarios:
        st = _build_lsm_serving_state(n_l0, with_levels)
        resident = max(st._runs.resident_runs(s) for s in range(st.S))
        present = np.asarray(st.scan_shard(0)[0])
        qs = [np.unique(rng.choice(present, q_rows)).astype(np.int32)
              for _ in range(8)]
        timings = {}
        tails = {}
        for mode, fused in (("fused", True), ("per_run", False)):
            st.fused_reads = fused
            for q in qs:
                st.query_rows(q)  # warm both jit caches off the clock
            st._h_query.reset()  # per-mode latency histogram (obs registry)
            t0 = time.time()
            for i in range(reps):
                st.query_rows(qs[i % len(qs)])
            timings[mode] = (time.time() - t0) / reps * 1e6
            tails[mode] = st._h_query.percentiles()
        st.fused_reads = True
        row = {"resident_runs_per_shard": resident,
               "with_levels": with_levels,
               "fused_us_per_query": timings["fused"],
               "per_run_us_per_query": timings["per_run"],
               "fused_speedup": timings["per_run"] / timings["fused"],
               "fused_p50_us": tails["fused"]["p50"] * 1e6,
               "fused_p99_us": tails["fused"]["p99"] * 1e6,
               "per_run_p50_us": tails["per_run"]["p50"] * 1e6,
               "per_run_p99_us": tails["per_run"]["p99"] * 1e6,
               "fused_dispatches": st.engine_stats()["fused_dispatches"]}
        result["rows"].append(row)
        print(f"runs/shard={resident:2d} levels={with_levels} "
              f"fused={timings['fused']:8.1f}us "
              f"per-run={timings['per_run']:8.1f}us "
              f"speedup={row['fused_speedup']:.2f}x")
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out}")
    return result


def scan_read_compare(reps: int = 30, lengths=(64, 256, 1024),
                      out: str = None) -> dict:
    """Range-scan A/B: the fused fence-to-fence scan dispatch
    (``ShardedTable.scan_range``) vs id-list point expansion of the same
    ``[lo, lo+len)`` range (``query_rows(arange(lo, hi))`` — exactly what
    range selectors compiled to before the scan path existed). Emits
    ``scan_rows`` for ``BENCH_query.json``; the CI gate tracks the
    scan/point-expansion ratio."""
    rng = np.random.default_rng(11)
    st = _build_lsm_serving_state(4, True)   # levels + L0 runs + mem tail
    resident = max(st._runs.resident_runs(s) for s in range(st.S))
    present = np.asarray(st.scan_shard(0)[0])
    result = {"scan_config": {"reps": reps,
                              "resident_runs_per_shard": resident},
              "scan_rows": []}
    for length in lengths:
        los = [int(present[int(i)]) for i in
               rng.integers(0, max(len(present) - 1, 1), 8)]
        los = [min(lo, (1 << 22) - length) for lo in los]
        st.scan_range(los[0], los[0] + length)      # warm the jit caches
        st.query_rows(np.arange(los[0], los[0] + length, dtype=np.int32))
        d0 = st.engine_stats()["scan_dispatches"]
        st._h_scan.reset()  # per-mode latency histogram (obs registry)
        t0 = time.time()
        for i in range(reps):
            lo = los[i % len(los)]
            st.scan_range(lo, lo + length)
        scan_us = (time.time() - t0) / reps * 1e6
        scan_tail = st._h_scan.percentiles()
        dispatches = (st.engine_stats()["scan_dispatches"] - d0) / reps
        st._h_query.reset()
        t0 = time.time()
        for i in range(reps):
            lo = los[i % len(los)]
            st.query_rows(np.arange(lo, lo + length, dtype=np.int32))
        point_us = (time.time() - t0) / reps * 1e6
        point_tail = st._h_query.percentiles()
        row = {"range_len": length, "scan_us": scan_us,
               "point_expansion_us": point_us,
               "scan_speedup": point_us / scan_us,
               "scan_p50_us": scan_tail["p50"] * 1e6,
               "scan_p99_us": scan_tail["p99"] * 1e6,
               "point_expansion_p50_us": point_tail["p50"] * 1e6,
               "point_expansion_p99_us": point_tail["p99"] * 1e6,
               "scan_dispatches_per_call": dispatches}
        result["scan_rows"].append(row)
        print(f"range_len={length:5d} scan={scan_us:9.1f}us "
              f"point-expansion={point_us:10.1f}us "
              f"speedup={row['scan_speedup']:6.2f}x "
              f"dispatches/scan={dispatches:.2f}")
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out}")
    return result


def colsel_read_compare(reps: int = 30, lengths=(64, 256, 1024),
                        out: str = None) -> dict:
    """Column-selector A/B on an engine-maintained transpose PAIR: the
    transpose-routed fused scan (``scan_col_range``, a fence-bracketed
    range scan over ``A^T``) vs the O(nnz) full-scan-and-host-filter
    baseline (what column selectors execute on single tables), with the
    same-length ROW range scan as reference — the design target is column
    selectors within ~1.5x of row range scans, not O(nnz). Emits
    ``colsel_rows`` for ``BENCH_query.json``; the CI gate tracks the
    worst colsel/filter ratio (``colsel_vs_filter``)."""
    rng = np.random.default_rng(13)
    st = _build_lsm_serving_state(4, True, transpose=True,
                                  col_space=1 << 22)
    resident = max(st.t_store._runs.resident_runs(s)
                   for s in range(st.t_store.S))
    present_cols = np.sort(np.asarray(st.t_store.scan_shard(0)[0]))
    result = {"colsel_config": {"reps": reps,
                                "sibling_resident_runs_per_shard": resident},
              "colsel_rows": []}
    filter_reps = max(reps // 5, 3)
    for length in lengths:
        los = [int(present_cols[int(i)]) for i in
               rng.integers(0, max(len(present_cols) - 1, 1), 8)]
        los = [min(lo, (1 << 22) - length) for lo in los]
        st.scan_col_range(los[0], los[0] + length)   # warm the jit caches
        st.scan_range(los[0], los[0] + length)
        st.scan()
        d0 = st.t_store.engine_stats()["scan_dispatches"]
        st.t_store._h_scan.reset()
        t0 = time.time()
        for i in range(reps):
            lo = los[i % len(los)]
            st.scan_col_range(lo, lo + length)
        colsel_us = (time.time() - t0) / reps * 1e6
        colsel_tail = st.t_store._h_scan.percentiles()
        dispatches = (st.t_store.engine_stats()["scan_dispatches"] - d0) \
            / reps
        t0 = time.time()
        for i in range(filter_reps):  # O(nnz) full scan + host isin
            lo = los[i % len(los)]
            r, c, v = st.scan()
            keep = (c >= lo) & (c < lo + length)
            r, c, v = r[keep], c[keep], v[keep]
        filter_us = (time.time() - t0) / filter_reps * 1e6
        t0 = time.time()
        for i in range(reps):  # row-scan reference (same range length)
            lo = los[i % len(los)]
            st.scan_range(lo, lo + length)
        rowscan_us = (time.time() - t0) / reps * 1e6
        row = {"range_len": length, "colsel_us": colsel_us,
               "full_scan_filter_us": filter_us,
               "rowscan_us": rowscan_us,
               "colsel_speedup": filter_us / colsel_us,
               "colsel_vs_rowscan": colsel_us / rowscan_us,
               "colsel_p50_us": colsel_tail["p50"] * 1e6,
               "colsel_p99_us": colsel_tail["p99"] * 1e6,
               "sibling_scan_dispatches_per_call": dispatches}
        result["colsel_rows"].append(row)
        print(f"range_len={length:5d} colsel={colsel_us:9.1f}us "
              f"full-scan+filter={filter_us:10.1f}us "
              f"speedup={row['colsel_speedup']:6.2f}x "
              f"vs-rowscan={row['colsel_vs_rowscan']:.2f}x "
              f"dispatches/scan={dispatches:.2f}")
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {out}")
    return result


def main() -> None:
    import argparse
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fused-compare", action="store_true",
                    help="point-read A/B (BENCH_query.json artifact)")
    ap.add_argument("--scan-compare", action="store_true",
                    help="range-scan vs point-expansion A/B "
                         "(scan_rows in BENCH_query.json)")
    ap.add_argument("--colsel-compare", action="store_true",
                    help="column selector via transpose pair vs "
                         "full-scan-and-filter A/B "
                         "(colsel_rows in BENCH_query.json)")
    ap.add_argument("--out", default="BENCH_query.json")
    ap.add_argument("--reps", type=int, default=100)
    args = ap.parse_args()
    if args.fused_compare or args.scan_compare or args.colsel_compare:
        result = {}
        if args.fused_compare:
            result.update(fused_read_compare(reps=args.reps))
        if args.scan_compare:
            result.update(scan_read_compare(reps=max(args.reps // 2, 10)))
        if args.colsel_compare:
            result.update(colsel_read_compare(reps=max(args.reps // 2, 10)))
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        print(f"wrote {args.out}")
    else:
        fig4()


if __name__ == "__main__":
    main()
