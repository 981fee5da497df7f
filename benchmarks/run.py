"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = edges/s or the
figure-specific rate). Reduced sizes keep the whole suite CPU-friendly;
pass --full for the paper-scale grid.

  PYTHONPATH=src python -m benchmarks.run [--full] [--json out.json]

``--json`` additionally writes the rows as structured records (name, rate,
engine, shard count, entries/sec where applicable) so successive PRs can
diff performance trajectories mechanically. A benchmark that raises is
recorded under ``errors`` (the artifact stays complete and parseable) and
the process exits nonzero so CI flags the run.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

import numpy as np

from repro.compile_cache import enable_compile_cache

ROWS = []


def emit(name: str, us_per_call: float, derived: str, **meta) -> None:
    """Record one benchmark row; ``meta`` (engine=, shards=, entries_per_s=,
    ...) rides into the --json artifact for mechanical perf diffing."""
    ROWS.append({"name": name, "us_per_call": us_per_call,
                 "derived": derived, **meta})
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


# ------------------------------------------------------- Fig 3 (ingest)
def bench_fig3_ingest(full: bool) -> None:
    from .ingest_bench import run_naive, run_optimized
    ks = (1, 2, 4, 8, 16) if full else (1, 4, 16)
    scales = (10, 12, 14) if full else (10, 12)
    for scale in scales:
        for k in ks:
            opt = run_optimized(k, scale)
            nai = run_naive(k, scale)
            emit(f"fig3_ingest_opt_s{scale}_k{k}",
                 opt["wall_s"] * 1e6,
                 f"{opt['edges_per_s']:.0f} edges/s serial; "
                 f"{opt['parallel_edges_per_s']:.0f} projected-parallel",
                 engine=opt.get("engine", "lsm"), shards=k,
                 entries_per_s=opt["edges_per_s"])
            emit(f"fig3_ingest_naive_s{scale}_k{k}",
                 nai["wall_s"] * 1e6,
                 f"{nai['edges_per_s']:.0f} edges/s (single stream, "
                 f"no partitioning)",
                 engine="naive", shards=1,
                 entries_per_s=nai["edges_per_s"])


def bench_fig3_batch_knob(full: bool) -> None:
    from .ingest_bench import batch_sweep
    budgets = (50_000, 200_000, 500_000, 2_000_000) if full \
        else (100_000, 500_000)
    for row in batch_sweep(scale=11, k=4, budgets=budgets):
        emit(f"fig3_batch_{row['char_budget']}", 0.0,
             f"{row['edges_per_s']:.0f} edges/s",
             engine="lsm", shards=4, entries_per_s=row["edges_per_s"])


def bench_fig3_straggler(full: bool) -> None:
    from .ingest_bench import run_optimized
    base = run_optimized(4, 11)
    steal = run_optimized(4, 11, steal=True)
    emit("fig3_straggler_worksteal", steal["wall_s"] * 1e6,
         f"{steal['edges_per_s']:.0f} edges/s vs {base['edges_per_s']:.0f} push",
         engine="lsm", shards=4, entries_per_s=steal["edges_per_s"])


# ------------------------------------------- engine A/B (LSM vs single)
def bench_engine_compare(full: bool) -> None:
    from .ingest_bench import engine_compare
    eps = 1 << 18 if full else 1 << 15
    mem = max(1 << 12, min(1 << 15, eps // 8))
    res = engine_compare(entries_per_shard=eps, shards=2,
                         batch=max(1 << 10, mem // 2), memtable=mem)
    for engine, r in res["engines"].items():
        emit(f"engine_{engine}_ingest_{eps}", r["ingest_wall_s"] * 1e6,
             f"{r['entries_per_s']:.0f} entries/s",
             engine=engine, shards=2, entries_per_s=r["entries_per_s"])
        emit(f"engine_{engine}_query_{eps}", r["query_wall_s"] * 1e6,
             f"{r['queries_per_s']:.0f} queries/s "
             f"flushed_on_read={r['flushed_on_read']}",
             engine=engine, shards=2)
    emit("engine_lsm_speedup", 0.0,
         f"{res['lsm_ingest_speedup']:.2f}x ingest vs single-run",
         engine="lsm", shards=2)


# -------------------------------------------------------- Fig 4 (query)
def bench_fig4_query(full: bool) -> None:
    from .query_bench import fig4
    rows = fig4(scale=13 if full else 11,
                degrees=(1, 10, 100, 1000) if full else (1, 10, 100),
                reps=5 if full else 3)
    for r in rows:
        emit(f"fig4_{r['query']}_deg{r['degree']}", 0.0,
             f"{r['opt_edges_per_s']:.0f} edges/s "
             f"(naive {r['naive_edges_per_s']:.0f})",
             engine="lsm", entries_per_s=r["opt_edges_per_s"])


# ------------------------------------- fused vs per-run LSM point reads
# NOTE: neither query bench writes BENCH_query.json here — that file at
# the repo root is the COMMITTED bench-gate baseline, regenerated only
# deliberately via `python -m benchmarks.query_bench --fused-compare
# --scan-compare --out BENCH_query.json` (a partial overwrite from an
# `--only` run would silently drop the other section from the gate).
# The speedup ratios still ride into --json via the emitted row meta.
def bench_query_fused(full: bool) -> None:
    """Read-path A/B: the fused single-dispatch query vs one bloom-gated
    launch per resident run."""
    from .query_bench import fused_read_compare
    res = fused_read_compare(reps=200 if full else 100)
    for r in res["rows"]:
        tag = "lvl" if r["with_levels"] else "l0"
        emit(f"query_fused_{tag}_runs{r['resident_runs_per_shard']}",
             r["fused_us_per_query"],
             f"{r['fused_speedup']:.2f}x vs per-run "
             f"({r['per_run_us_per_query']:.0f}us)",
             engine="lsm", shards=2,
             fused_speedup=r["fused_speedup"])


# ------------------------------- fused range scans vs point expansion
def bench_query_scan(full: bool) -> None:
    """Range-scan A/B: one fused fence-to-fence dispatch per shard vs
    expanding the range selector into an id list of point queries."""
    from .query_bench import scan_read_compare
    res = scan_read_compare(reps=50 if full else 20)
    for r in res["scan_rows"]:
        emit(f"query_scan_len{r['range_len']}", r["scan_us"],
             f"{r['scan_speedup']:.2f}x vs point expansion "
             f"({r['point_expansion_us']:.0f}us)",
             engine="lsm", shards=2, scan_speedup=r["scan_speedup"])


# ------------------------------------------- DB micro (compiled paths)
def bench_db_micro(full: bool) -> None:
    from repro.db.kvstore import ShardedTable

    n = 1 << 18
    for engine in ("single", "lsm"):
        store = ShardedTable(f"micro_{engine}", num_shards=1,
                             capacity_per_shard=n * 2, batch_cap=n,
                             id_capacity=1 << 22, use_pallas=False,
                             engine=engine, memtable_cap=n)
        rng = np.random.default_rng(0)
        rows = rng.integers(0, 1 << 22, n).astype(np.int32)
        cols = rng.integers(0, 1 << 16, n).astype(np.int32)
        vals = rng.normal(size=n).astype(np.float32)
        store.warmup()
        t0 = time.time()
        store.insert(rows, cols, vals)
        store.flush()
        dt = time.time() - t0
        emit(f"db_minor_compaction_262k_{engine}", dt * 1e6,
             f"{n / dt:.0f} triples/s", engine=engine, shards=1,
             entries_per_s=n / dt)

        q = rng.choice(rows, 4096).astype(np.int32)
        store.query_rows(q[:16])  # warmup
        t0 = time.time()
        store.query_rows(q)
        dt = time.time() - t0
        emit(f"db_rank_query_4096_{engine}", dt * 1e6,
             f"{4096 / dt:.0f} queries/s", engine=engine, shards=1)


# ------------------------------------------------- roofline (from dry-run)
def bench_roofline_summary(full: bool) -> None:
    import os
    from .roofline import load_records
    if not os.path.isdir("experiments/dryrun"):
        print("# roofline: experiments/dryrun missing — run "
              "`python -m repro.launch.dryrun --all --mesh both "
              "--out experiments/dryrun` first")
        return
    recs = [r for r in load_records() if "error" not in r
            and "skipped" not in r]
    for r in recs:
        dom = max(r["compute_s"], r["memory_s"], r["collective_s"])
        emit(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}", 0.0,
             f"bottleneck={r['bottleneck']} dominant={dom * 1e3:.1f}ms "
             f"useful={r['useful_ratio']:.2f}")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write structured rows to PATH")
    args = ap.parse_args()
    benches = {
        "fig3": bench_fig3_ingest,
        "fig3_batch": bench_fig3_batch_knob,
        "fig3_straggler": bench_fig3_straggler,
        "engine": bench_engine_compare,
        "fig4": bench_fig4_query,
        "query_fused": bench_query_fused,
        "query_scan": bench_query_scan,
        "db_micro": bench_db_micro,
        "roofline": bench_roofline_summary,
    }
    print("name,us_per_call,derived")
    failures = []
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---", flush=True)
        try:
            fn(args.full)
        except Exception as exc:  # keep the artifact complete + parseable
            traceback.print_exc()
            failures.append({"bench": name, "error": repr(exc)})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": ROWS, "full": args.full,
                       "errors": failures}, f, indent=1)
        print(f"# wrote {args.json}", flush=True)
    if failures:
        print(f"# {len(failures)} benchmark(s) FAILED: "
              + ", ".join(f["bench"] for f in failures), flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
