"""Differential property tests for the storage engine.

Random interleavings of ingest/flush/compact/query/scan are applied to
the LSM engine and to a sequential dict oracle (the reference); the
fused point query, the fused range scan and the full scan must agree
with the oracle for every combiner. Range scans are additionally checked
against id-list point expansion of the same range (a second procedure
over the same read path). ``FUZZ_BUDGET`` (env, CI's weekly deep lane)
adds that many extra examples per property.

Also home to the fused read paths' structural guarantees: the
one-dispatch assertions for point queries AND range scans (memtable + L0
runs + leveled runs answered by exactly one compiled-function invocation,
every other entry point poisoned), reads of a memtable whose host mirror
is stale, and the batched Pallas rank kernel's equivalence to its
reference.
"""
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.db.kvstore import COMBINERS, ShardedTable, shard_of
from repro.db.lsm import engine as lsm_engine
from repro.obs import default_registry
from repro.kernels.common import I32_MAX
from repro.kernels.sorted_search import (sorted_search_batched,
                                         sorted_search_batched_ref)

# weekly CI deep lane: FUZZ_BUDGET=N adds N examples to every property
FUZZ_BUDGET = int(os.environ.get("FUZZ_BUDGET", "0"))

# one tiny fixed geometry for EVERY example: jit caches stay warm across
# examples, so each draw costs milliseconds, not a recompile
CFG = dict(num_shards=2, capacity_per_shard=2048, batch_cap=256,
           id_capacity=1 << 8, memtable_cap=32, l0_slots=3)


def _oracle_apply(oracle, r, c, v, combiner):
    for a, b, x in zip(r, c, v):
        k = (int(a), int(b))
        if k in oracle:
            oracle[k] = {"last": float(x), "sum": oracle[k] + float(x),
                         "min": min(oracle[k], float(x)),
                         "max": max(oracle[k], float(x))}[combiner]
        else:
            oracle[k] = float(x)


def _as_dict(r, c, v):
    return {(int(a), int(b)): float(x) for a, b, x in zip(r, c, v)}


def _check_close(got, want, label, ctx):
    assert set(got) == set(want), (label, ctx,
                                   set(got) ^ set(want))
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-4, abs=1e-5), \
            (label, ctx, k, got[k], want[k])


@settings(max_examples=10 + FUZZ_BUDGET, deadline=None)
@given(st.integers(0, 2 ** 31 - 1),
       st.sampled_from(COMBINERS),
       st.lists(st.sampled_from(["ins", "ins", "ins", "flush", "compact",
                                 "query", "scan"]), min_size=4, max_size=12))
def test_engines_and_read_paths_agree(seed, combiner, ops):
    """insert/flush/compact in random order; every query op must return
    the oracle's answer from the fused point query, and every scan op
    the oracle's range from both the fused scan and id-list point
    expansion. Ends with a full-scan comparison."""
    rng = np.random.default_rng(seed)
    lsm = ShardedTable("prop_lsm", combiner=combiner, **CFG)
    oracle = {}

    def check_query():
        keys = np.asarray(sorted({k[0] for k in oracle}), np.int32)
        if len(keys) == 0:
            return
        pick = rng.choice(keys, size=min(12, len(keys)), replace=False)
        absent = rng.integers(0, CFG["id_capacity"], 3).astype(np.int32)
        q = np.unique(np.concatenate([pick, absent])).astype(np.int32)
        want = {k: v for k, v in oracle.items() if k[0] in set(q.tolist())}
        fused = _as_dict(*lsm.query_rows(q))
        _check_close(fused, want, "fused", (seed, combiner))

    def check_scan():
        # random [lo, hi): sometimes empty (hi == lo), sometimes past the
        # id space, and — mid-sequence — often spanning data that sits on
        # both sides of a flush/compaction boundary
        lo = int(rng.integers(0, CFG["id_capacity"]))
        hi = min(lo + int(rng.integers(0, 64)), CFG["id_capacity"] + 4)
        want = {k: v for k, v in oracle.items() if lo <= k[0] < hi}
        ctx = (seed, combiner, lo, hi)
        fused = _as_dict(*lsm.scan_range(lo, hi))
        # id-list point expansion of the same range (the pre-scan path)
        ids = np.arange(lo, min(hi, CFG["id_capacity"]), dtype=np.int32)
        expanded = _as_dict(*lsm.query_rows(ids)) if len(ids) else {}
        _check_close(fused, want, "fused-scan", ctx)
        _check_close(expanded, want, "point-expansion", ctx)

    for op in ops:
        if op == "ins":
            n = int(rng.integers(1, 28))
            r = rng.integers(0, CFG["id_capacity"], n).astype(np.int32)
            c = rng.integers(0, 4, n).astype(np.int32)
            v = (rng.integers(-4, 5, n).astype(np.float32)
                 if combiner == "sum" else
                 rng.normal(size=n).astype(np.float32))
            lsm.insert(r, c, v)
            _oracle_apply(oracle, r, c, v, combiner)
        elif op == "flush":
            lsm.flush()
        elif op == "compact":
            lsm.major_compact()
        elif op == "scan":
            check_scan()
        else:
            check_query()
    check_query()
    check_scan()
    got = _as_dict(*lsm.scan())
    _check_close(got, oracle, "scan", (seed, combiner))


def _ctr(name: str, table: str) -> int:
    """Read one labeled counter straight from the obs registry — the
    ground truth the engine's ``.stats`` view is derived from."""
    series = default_registry().series(name, table=table)
    assert len(series) == 1, (name, table, series)
    return int(series[0].value)


def test_fused_point_query_is_one_dispatch(monkeypatch):
    """The acceptance bar: a point query against a shard holding a
    non-empty memtable, >=2 L0 runs, and >=2 leveled runs runs exactly ONE
    compiled-function invocation — counted via the obs registry's
    dispatch counter, with the scan entry points poisoned so a point
    query answered by a scan fails loudly."""
    st_ = ShardedTable("one_dispatch", num_shards=1,
                       capacity_per_shard=4096, batch_cap=256,
                       id_capacity=1 << 10, combiner="sum",
                       memtable_cap=64, l0_slots=4, engine="lsm")
    rng = np.random.default_rng(0)
    oracle = {}

    def put(n, base):
        r = (base + rng.integers(0, 200, n)).astype(np.int32)
        c = rng.integers(0, 4, n).astype(np.int32)
        v = rng.normal(size=n).astype(np.float32)
        st_.insert(r, c, v)
        for a, b, x in zip(r, c, v):
            oracle[(int(a), int(b))] = oracle.get((int(a), int(b)), 0.0) \
                + float(x)

    # two leveled runs: a deep compaction, then a shallow one
    for _ in range(8):       # 8 x 64 fills L0 twice -> deepest level
        put(64, 0)
    st_.major_compact()
    for _ in range(2):
        put(64, 200)
    st_.major_compact()      # smaller merge -> shallower level
    levels_live = sum(1 for lv in st_._runs.levels if lv["n"][0] > 0)
    assert levels_live >= 2, [int(lv["n"][0]) for lv in st_._runs.levels]
    put(64, 400)             # L0 run 1
    st_.flush()
    put(64, 600)             # L0 run 2
    st_.flush()
    put(20, 800)             # non-empty memtable tail
    assert int(st_._runs.l0_used[0]) >= 2 and int(st_._mem_n[0]) > 0

    # poison every other read entry point
    def boom(*a, **k):
        raise AssertionError("a scan path answered a point query")
    monkeypatch.setattr(lsm_engine.LSMRuns, "scan_shard_fused", boom)
    monkeypatch.setattr(lsm_engine.LSMRuns, "scan_shard", boom)

    keys = np.asarray(sorted({k[0] for k in oracle}), np.int32)
    q = rng.choice(keys, 8, replace=False).astype(np.int32)
    before = _ctr("lsm_fused_dispatches", "one_dispatch")
    retries0 = _ctr("lsm_fused_widen_retries", "one_dispatch")
    qr, qc, qv = st_.query_rows(np.unique(q))
    after = _ctr("lsm_fused_dispatches", "one_dispatch")
    assert after - before == 1, (before, after)
    assert _ctr("lsm_fused_widen_retries", "one_dispatch") == retries0
    # the legacy .stats view must mirror the registry counter exactly
    assert st_.engine_stats()["fused_dispatches"] == after
    # and the answer is still exactly right
    want = {k: v for k, v in oracle.items() if k[0] in set(q.tolist())}
    got = _as_dict(qr, qc, qv)
    _check_close(got, want, "one-dispatch", ())
    # reads never flushed anything
    assert int(st_._mem_n[0]) > 0 and int(st_._runs.l0_used[0]) >= 2


def test_fused_range_scan_is_one_dispatch(monkeypatch):
    """The scan acceptance bar: a [lo, hi) range scan against a shard
    holding a non-empty memtable, >=2 L0 runs, and >=2 leveled runs runs
    exactly ONE compiled-function invocation — counted via the engine's
    scan-dispatch counter, with the point-query and full-scan entry
    points poisoned so any id-list point expansion fails loudly."""
    st_ = ShardedTable("one_scan", num_shards=1,
                       capacity_per_shard=4096, batch_cap=256,
                       id_capacity=1 << 10, combiner="sum",
                       memtable_cap=64, l0_slots=4, engine="lsm")
    rng = np.random.default_rng(1)
    oracle = {}

    def put(n, base):
        r = (base + rng.integers(0, 200, n)).astype(np.int32)
        c = rng.integers(0, 4, n).astype(np.int32)
        v = rng.normal(size=n).astype(np.float32)
        st_.insert(r, c, v)
        for a, b, x in zip(r, c, v):
            oracle[(int(a), int(b))] = oracle.get((int(a), int(b)), 0.0) \
                + float(x)

    for _ in range(8):       # deep compaction, then a shallow one
        put(64, 0)
    st_.major_compact()
    for _ in range(2):
        put(64, 200)
    st_.major_compact()
    levels_live = sum(1 for lv in st_._runs.levels if lv["n"][0] > 0)
    assert levels_live >= 2, [int(lv["n"][0]) for lv in st_._runs.levels]
    put(64, 400)             # L0 run 1
    st_.flush()
    put(64, 600)             # L0 run 2
    st_.flush()
    put(20, 800)             # non-empty memtable tail
    assert int(st_._runs.l0_used[0]) >= 2 and int(st_._mem_n[0]) > 0

    # poison every other read entry point: the scan must not expand the
    # range into point reads or filter a full scan
    def boom(*a, **k):
        raise AssertionError("point-query path was dispatched for a scan")
    monkeypatch.setattr(lsm_engine.LSMRuns, "query_shard_fused", boom)
    monkeypatch.setattr(lsm_engine.LSMRuns, "scan_shard", boom)

    lo, hi = 150, 700        # spans both levels, both L0 runs
    before = _ctr("lsm_scan_dispatches", "one_scan")
    retries0 = _ctr("lsm_scan_widen_retries", "one_scan")
    fused0 = _ctr("lsm_fused_dispatches", "one_scan")
    r, c, v = st_.scan_range(lo, hi, width=1024)
    after = _ctr("lsm_scan_dispatches", "one_scan")
    assert after - before == 1, (before, after)
    assert _ctr("lsm_scan_widen_retries", "one_scan") == retries0
    assert _ctr("lsm_fused_dispatches", "one_scan") == fused0
    # the legacy .stats view must mirror the registry counter exactly
    assert st_.engine_stats()["scan_dispatches"] == after
    want = {k: x for k, x in oracle.items() if lo <= k[0] < hi}
    _check_close(_as_dict(r, c, v), want, "one-dispatch-scan", (lo, hi))
    # scans never flushed anything
    assert int(st_._mem_n[0]) > 0 and int(st_._runs.l0_used[0]) >= 2
    # widen retry: a deliberately tiny window must re-dispatch ONCE wider
    # and still return the identical result
    r2, c2, v2 = st_.scan_range(lo, hi, width=16)
    assert _ctr("lsm_scan_widen_retries", "one_scan") == retries0 + 1
    _check_close(_as_dict(r2, c2, v2), want, "widen-retry-scan", (lo, hi))


def test_tiled_large_batch_matches_all_paths():
    """Batches far above ``fused_q_limit`` are split into query tiles:
    exactly ceil(unique/tile) dispatches per shard (plus any widen
    retries), ``fused_tiles`` accounting for the split — and results
    identical to the oracle, with duplicate query ids re-expanded."""
    tile = 32
    mk = dict(num_shards=2, capacity_per_shard=4096, batch_cap=256,
              id_capacity=1 << 10, combiner="sum", memtable_cap=64)
    lsm = ShardedTable("tiled_lsm", l0_slots=3, fused_q_limit=tile, **mk)
    rng = np.random.default_rng(7)
    oracle = {}

    # level + L0 runs + memtable tail on both shards
    for i in range(6):
        r = rng.integers(0, 1 << 10, 48).astype(np.int32)
        c = rng.integers(0, 4, 48).astype(np.int32)
        v = rng.integers(-4, 5, 48).astype(np.float32)
        lsm.insert(r, c, v)
        _oracle_apply(oracle, r, c, v, "sum")
        if i in (0, 2, 3):
            lsm.flush()
        if i == 3:
            lsm.major_compact()
    assert int(lsm._mem_n.max()) > 0  # a tail rides along in-dispatch

    keys = np.asarray(sorted({k[0] for k in oracle}), np.int32)
    absent = np.setdiff1d(np.arange(1 << 10, dtype=np.int32), keys)[:50]
    q = np.concatenate([keys, keys[: len(keys) // 2], absent])
    rng.shuffle(q)
    q = q.astype(np.int32)
    owner = shard_of(q, mk["num_shards"], mk["id_capacity"])
    exp_disp, exp_tiles = 0, 0
    for s in np.unique(owner):
        u = len(np.unique(q[owner == s]))
        t = -(-u // tile) if u > tile else 1
        exp_disp += t
        exp_tiles += t if t > 1 else 0
    assert exp_tiles >= 4, exp_tiles  # the batch genuinely tiles

    def deltas(fn):
        names = ("fused_dispatches", "fused_widen_retries", "fused_tiles")
        b = {n: _ctr("lsm_" + n, "tiled_lsm") for n in names}
        out = fn()
        return out, {n: _ctr("lsm_" + n, "tiled_lsm") - b[n] for n in names}

    (fr, fc, fv), d = deltas(lambda: lsm.query_rows(q))
    # ceil(unique/tile) dispatches per shard; ONE extra allowed per widen
    assert d["fused_dispatches"] == exp_disp + d["fused_widen_retries"], \
        (d, exp_disp)
    assert d["fused_tiles"] == exp_tiles, (d, exp_tiles)

    want_r, want_c, want_v = [], [], []
    by_row: dict = {}
    for (a, b), x in oracle.items():
        by_row.setdefault(a, []).append((b, x))
    for qid in q.tolist():
        for b, x in by_row.get(qid, ()):
            want_r.append(qid)
            want_c.append(b)
            want_v.append(x)

    def norm(r, c, v):
        r, c, v = (np.asarray(r, np.int64), np.asarray(c, np.int64),
                   np.asarray(v, np.float64))
        order = np.lexsort((v, c, r))
        return r[order], c[order], v[order]

    want = norm(want_r, want_c, want_v)
    gr, gc, gv = norm(fr, fc, fv)
    np.testing.assert_array_equal(gr, want[0])
    np.testing.assert_array_equal(gc, want[1])
    np.testing.assert_allclose(gv, want[2], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("with_filter", [False, True],
                         ids=["all_cols", "col_filter"])
@pytest.mark.parametrize("read", ["query_rows", "scan_range"])
def test_stale_mirror_reads_match_oracle(read, with_filter):
    """``insert_routed`` appends on the device and leaves the host mirror
    of the memtable stale: the fused read then takes the tail as device
    slices and sorts it inside its dispatch. Point queries and range
    scans, with and without a column filter, must equal the oracle
    across leveled runs, an L0 run and that tail, and flush nothing."""
    st_ = ShardedTable("stale_" + read + str(with_filter), num_shards=2,
                       capacity_per_shard=2048, batch_cap=128,
                       id_capacity=1 << 8, combiner="last",
                       memtable_cap=64, l0_slots=3)
    rng = np.random.default_rng(11)
    oracle = {}

    def batch(n):
        r = rng.integers(0, 1 << 8, n).astype(np.int32)
        c = rng.integers(0, 6, n).astype(np.int32)
        v = rng.normal(size=n).astype(np.float32)
        _oracle_apply(oracle, r, c, v, "last")
        return r, c, v

    for _ in range(4):       # a leveled run
        st_.insert(*batch(40))
        st_.flush()
    st_.major_compact()
    st_.insert(*batch(40))   # an L0 run
    st_.flush()
    st_.insert(*batch(20))   # a tail the mirror holds ...
    st_.insert_routed(*st_.route(*batch(30)))   # ... then made stale
    assert not st_._mirror_ok and int(st_._mem_n.sum()) == 50
    mem_n0 = st_._mem_n.copy()
    flushes0 = st_.engine_stats()["flushes"]

    cf = np.asarray([0, 2, 3], np.int32) if with_filter else None
    keep_col = (lambda b: b in {0, 2, 3}) if with_filter else (lambda b: True)
    if read == "query_rows":
        q = np.unique(rng.integers(0, 1 << 8, 60)).astype(np.int32)
        got = _as_dict(*st_.query_rows(q, col_filter=cf))
        sel = set(q.tolist())
        want = {k: x for k, x in oracle.items()
                if k[0] in sel and keep_col(k[1])}
    else:
        lo, hi = 40, 200     # spans both shards
        got = _as_dict(*st_.scan_range(lo, hi, col_filter=cf))
        want = {k: x for k, x in oracle.items()
                if lo <= k[0] < hi and keep_col(k[1])}
    assert want  # the reads have something to find
    _check_close(got, want, "stale-mirror-" + read, with_filter)
    # the reads flushed nothing and left the tail where it was
    np.testing.assert_array_equal(st_._mem_n, mem_n0)
    assert st_.engine_stats()["flushes"] == flushes0


def test_empty_shard_fused_query_observes_latency():
    """An empty shard's early return must still observe the per-shard
    query latency histogram (pre-fix, the ``continue`` skipped it and the
    shard's p99 silently excluded its cheapest reads)."""
    st_ = ShardedTable("emptyobs", num_shards=1, capacity_per_shard=256,
                       batch_cap=64, id_capacity=1 << 8, engine="lsm")
    h = st_._h_shard_query[0]
    before = h.count
    r, _, _ = st_.query_rows(np.asarray([3, 9], np.int32))
    assert len(r) == 0
    assert st_.engine_stats()["fused_dispatches"] == 0  # no dispatch...
    assert h.count == before + 1                        # ...still timed


def test_major_compaction_only_compacts_full_shards():
    """Per-shard compaction scheduling: a hot shard filling ITS L0 must
    not drag a cold peer's L0 runs into a level merge (pre-fix, any
    shard's full L0 compacted every shard in lockstep)."""
    st_ = ShardedTable("selcomp", num_shards=2, capacity_per_shard=2048,
                       batch_cap=128, id_capacity=1 << 10, combiner="last",
                       memtable_cap=32, l0_slots=3, engine="lsm")
    # one L0 run for the cold shard (ids >= 512 live on shard 1)
    st_.insert(512 + np.arange(20, dtype=np.int32), np.zeros(20, np.int32),
               np.ones(20, np.float32))
    st_.flush()
    assert [int(x) for x in st_._runs.l0_used] == [0, 1]
    # fill the hot shard's L0 to the brim -> automatic major compaction
    for i in range(3):
        st_.insert(np.arange(24, dtype=np.int32) + 24 * i,
                   np.zeros(24, np.int32),
                   np.full(24, float(i), np.float32))
        st_.flush()
    assert st_.engine_stats()["major_compactions"] >= 1
    # hot shard compacted into a level; cold shard's L0 run UNTOUCHED
    assert int(st_._runs.l0_used[0]) == 0
    assert int(st_._runs.l0_used[1]) == 1
    assert sum(int(lv["n"][0]) for lv in st_._runs.levels) == 72
    assert sum(int(lv["n"][1]) for lv in st_._runs.levels) == 0
    # both shards still answer reads exactly
    got = _as_dict(*st_.query_rows(np.asarray([0, 30, 512, 531], np.int32)))
    assert got == {(0, 0): 0.0, (30, 0): 1.0, (512, 0): 1.0, (531, 0): 1.0}
    # an explicit full compaction still sweeps everything
    st_.major_compact()
    assert int(st_._runs.l0_used[1]) == 0
    assert sum(int(lv["n"][1]) for lv in st_._runs.levels) == 20


def test_fused_handles_empty_runs_and_absent_keys():
    """Static stacked shapes mean empty L0 slots/levels ride along as
    I32_MAX padding — they must contribute nothing, including for queries
    that match nothing anywhere."""
    st_ = ShardedTable("empt", num_shards=2, capacity_per_shard=1024,
                       batch_cap=128, id_capacity=1 << 8, combiner="last",
                       memtable_cap=32, engine="lsm")
    # memtable only (no runs at all)
    st_.insert(np.asarray([5], np.int32), np.asarray([1], np.int32),
               np.asarray([2.0], np.float32))
    r, c, v = st_.query_rows(np.asarray([5, 77], np.int32))
    assert _as_dict(r, c, v) == {(5, 1): 2.0}
    # runs only (flushed), absent keys
    st_.flush()
    r, c, v = st_.query_rows(np.asarray([5], np.int32))
    assert _as_dict(r, c, v) == {(5, 1): 2.0}
    r, c, v = st_.query_rows(np.asarray([77, 99], np.int32))
    assert len(r) == 0
    # fully empty shard: no dispatch needed, no crash
    empty = ShardedTable("empt2", num_shards=1, capacity_per_shard=1024,
                         batch_cap=128, id_capacity=1 << 8,
                         memtable_cap=32, engine="lsm")
    r, c, v = empty.query_rows(np.asarray([3], np.int32))
    assert len(r) == 0 and empty.engine_stats()["fused_dispatches"] == 0


def test_fused_duplicate_query_ids_parity():
    st_ = ShardedTable("dupf", num_shards=1, capacity_per_shard=256,
                       batch_cap=64, id_capacity=1 << 10, engine="lsm")
    st_.insert(np.asarray([7, 7], np.int32), np.asarray([1, 2], np.int32),
               np.asarray([1.0, 2.0], np.float32))
    r, c, v = st_.query_rows(np.asarray([7, 7], np.int32))
    assert len(r) == 4


@settings(max_examples=6 + FUZZ_BUDGET, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 4),
       st.integers(1, 40))
def test_batched_rank_search_matches_ref(seed, n_runs, n_q):
    """The fused path's batched Pallas rank kernel == vmapped searchsorted
    for ragged stacked runs (interpret mode on CPU)."""
    rng = np.random.default_rng(seed)
    cap = 128
    tabs = np.full((n_runs, cap), I32_MAX, np.int32)
    for k in range(n_runs):
        n = int(rng.integers(0, cap + 1))
        tabs[k, :n] = np.sort(rng.integers(0, 500, n)).astype(np.int32)
    q = rng.integers(0, 500, n_q).astype(np.int32)
    for side in ("left", "right"):
        got = np.asarray(sorted_search_batched(tabs, q, side,
                                               interpret=True))
        ref = np.asarray(sorted_search_batched_ref(tabs, q, side))
        np.testing.assert_array_equal(got, ref, err_msg=f"{side}")
