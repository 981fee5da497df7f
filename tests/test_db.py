"""Database layer tests: Listing-1 workflow, roundtrips, schema, batching,
combiners, overflow back-pressure, naive-baseline equivalence."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import Assoc
from repro.data.graph500 import graph500_triples
from repro.db import (DBserver, EdgeSchema, NaiveTable, dbinit, dbsetup,
                      delete, put)
from repro.db.batching import batch_triples, triple_chars
from repro.db.kvstore import ShardedTable


def small_server(**kw):
    cfg = dict(num_shards=4, capacity_per_shard=4096, batch_cap=2048,
               id_capacity=1 << 16, use_pallas=True)
    cfg.update(kw)
    return dbsetup("testdb", cfg)


# ------------------------------------------------------- paper Listing 1
def test_listing1_workflow():
    dbinit()
    DB = small_server()
    Tedge = DB["my_Tedge", "my_TedgeT"]
    TedgeDeg = DB["my_TedgeDeg"]

    a = Assoc("e1,e1,e2,", "v1,v2,v1,", [1.0, 1.0, 1.0])
    put(Tedge, a)

    arow = Tedge["e1,", :]
    assert set(arow.col) == {"v1", "v2"}
    acol = Tedge[:, "v1,"]  # auto-routes to the transpose table
    assert set(acol.row) == {"e1", "e2"}
    assert set(acol.col) == {"v1"}

    delete(Tedge)
    delete(TedgeDeg)
    assert "my_Tedge" not in DB.ls()


def test_put_query_roundtrip_matches_assoc():
    DB = small_server()
    T = DB["t1"]
    rng = np.random.default_rng(3)
    n = 500
    rows = np.asarray([f"r{int(i):04d}" for i in rng.integers(0, 60, n)], object)
    cols = np.asarray([f"c{int(i):04d}" for i in rng.integers(0, 60, n)], object)
    vals = rng.integers(1, 100, n).astype(np.float64)
    a = Assoc(rows, cols, vals, func="last")
    T.put(a)
    assert T.nnz() == a.nnz()
    for key in ["r0000,", "r0031,", "r0005,r0007,"]:
        assert T[key, :].same_as(a[key, :]), key


def test_range_and_prefix_queries():
    DB = small_server()
    T = DB["t2"]
    T.put_triple(np.asarray(["alice", "bob", "carl", "dan"], object),
                 np.asarray(["x", "x", "x", "x"], object),
                 np.asarray([1.0, 2.0, 3.0, 4.0]))
    assert set(T["alice,:,carl,", :].row) == {"alice", "bob", "carl"}
    assert set(T["b*,", :].row) == {"bob"}
    assert T[:, :].nnz() == 4  # full scan


def test_string_values_roundtrip():
    DB = small_server()
    T = DB["t3"]
    T.put(Assoc("alice,", "bob,", "cited,"))
    out = T["alice,", :]
    r, c, v = out.triples()
    assert v[0] == "cited"


def test_last_wins_versioning():
    DB = small_server()
    T = DB["t4"]
    T.put_triple(np.asarray(["a"], object), np.asarray(["b"], object),
                 np.asarray([1.0]))
    T.put_triple(np.asarray(["a"], object), np.asarray(["b"], object),
                 np.asarray([9.0]))
    assert T.nnz() == 1
    _, _, v = T["a,", :].triples()
    assert v[0] == 9.0


def test_sum_combiner_table():
    store = ShardedTable("sumtab", num_shards=2, capacity_per_shard=256,
                         batch_cap=128, id_capacity=1 << 10, combiner="sum")
    for _ in range(3):
        store.insert(np.asarray([5, 5, 900], np.int32),
                     np.asarray([1, 1, 2], np.int32),
                     np.asarray([1.0, 2.0, 4.0], np.float32))
    r, c, v = store.query_rows(np.asarray([5, 900], np.int32))
    got = {(int(a), int(b)): float(x) for a, b, x in zip(r, c, v)}
    assert got == {(5, 1): 9.0, (900, 2): 12.0}


def test_overflow_backpressure():
    store = ShardedTable("tiny", num_shards=1, capacity_per_shard=64,
                         batch_cap=64, id_capacity=1 << 10)
    with pytest.raises(OverflowError):
        for i in range(4):
            store.insert(np.arange(64, dtype=np.int32) + 64 * i,
                         np.zeros(64, np.int32), np.ones(64, np.float32))
            store.flush()  # minor compaction surfaces the back-pressure


def test_query_widens_past_max_return():
    store = ShardedTable("wide", num_shards=1, capacity_per_shard=4096,
                         batch_cap=4096, id_capacity=1 << 10)
    n = 600  # one row with 600 entries > default max_return=256
    store.insert(np.full(n, 7, np.int32), np.arange(n, dtype=np.int32),
                 np.ones(n, np.float32))
    r, c, v = store.query_rows(np.asarray([7], np.int32), max_return=256)
    assert len(c) == n and set(c) == set(range(n))


# ------------------------------------------------------------- batching
def test_batching_respects_budget():
    rows = np.asarray(["r" * 50] * 100, object)
    cols = np.asarray(["c" * 49] * 100, object)
    vals = np.ones(100)
    batches = list(batch_triples(rows, cols, vals, char_budget=1000))
    assert sum(len(b[0]) for b in batches) == 100
    costs = triple_chars(rows, cols, vals)
    for br, _, _ in batches[:-1]:
        assert costs[: len(br)].sum() <= 1000 + costs[0]
    assert len(batches) > 5  # actually split


# ------------------------------------------------------- D4M 2.0 schema
def test_edge_schema_degrees():
    DB = small_server(capacity_per_shard=1 << 15, batch_cap=1 << 14)
    g = EdgeSchema(DB, "g")
    rows, cols, vals = graph500_triples(scale=6, edges_per_vertex=4, seed=1)
    g.put_triple(rows, cols, vals)
    # degree table must match a numpy bincount oracle over raw edges
    out_oracle = {}
    for r in rows:
        out_oracle[r] = out_oracle.get(r, 0) + 1
    deg = g.deg.degrees(":")
    dd = {k: v for (k, c), v in zip(zip(*deg.triples()[:2]), deg.triples()[2])
          if c == "OutDeg"}
    for k, v in out_oracle.items():
        assert dd[k] == v, k
    # degree-bucket vertex selection (paper Fig. 4 procedure)
    vs = g.deg.vertices_with_degree(max(out_oracle.values()), "out", tol=1.001)
    assert len(vs) >= 1
    # row query against the Assoc oracle (duplicate edges -> last-wins)
    a = Assoc(rows, cols, vals, func="last")
    probe = rows[0] + ","
    assert g[probe, :].same_as(a[probe, :])
    # column query via transpose table
    at = a.transpose()
    probe_c = cols[0] + ","
    assert g[:, probe_c].same_as(a[:, probe_c])


# ------------------------------------------------- naive baseline parity
def test_naive_matches_optimized():
    DB = small_server()
    T = DB["opt"]
    N = NaiveTable("naive")
    rows, cols, vals = graph500_triples(scale=5, edges_per_vertex=4, seed=2)
    a = Assoc(rows, cols, vals, func="last")
    T.put(a)
    N.put(a)
    for probe in [rows[0] + ",", rows[5] + ",", "v00000000,"]:
        assert T[probe, :].same_as(N[probe, :]), probe


# ------------------------------------------------------ property tests
keys = st.lists(st.integers(0, 30), min_size=1, max_size=40)


@settings(max_examples=25, deadline=None)
@given(keys, keys, st.integers(0, 2 ** 31 - 1))
def test_chunked_ingest_equals_bulk(rs, cs, seed):
    """Splitting an ingest into arbitrary chunks must not change the table."""
    n = min(len(rs), len(cs))
    rows = np.asarray([f"r{i}" for i in rs[:n]], object)
    cols = np.asarray([f"c{i}" for i in cs[:n]], object)
    vals = np.arange(1, n + 1).astype(np.float64)
    # last-wins oracle
    a = Assoc(rows, cols, vals, func="last")
    DB = small_server()
    T = DB["chunked"]
    rng = np.random.default_rng(seed)
    splits = np.sort(rng.integers(0, n + 1, 3))
    prev = 0
    for s in list(splits) + [n]:
        if s > prev:
            T.put_triple(rows[prev:s], cols[prev:s], vals[prev:s])
        prev = s
    assert T[:, :].same_as(a)
