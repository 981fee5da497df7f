"""Engine-maintained transpose pairs + the ReadPlan connector API.

* Differential column-selector property: the transpose-routed fused scan,
  the on-device ``col_filter`` pushdown, and the full-scan + host-isin
  baseline must agree with a sequential dict oracle for every combiner,
  across random interleavings of ingest/flush/compact (so ranges span
  flush and compaction boundaries).
* One-dispatch structure: a column range read on a pair executes as fused
  scan dispatches on the SIBLING only — the primary's full-scan counter
  and its own scan/query dispatch counters stay flat.
* Connector surface: ``DB[t, tt]`` binds a pair backed by ONE store,
  ``put`` ingests once (engine dual-writes), checkpoint/recover restore
  both sides from one snapshot + pair-tagged WAL, ``delete``/``drop``
  release the store (leak regression).
* ``ReadPlan`` / ``StoreConfig`` round-trips and the deprecated
  ``resolve_selector`` shim.
"""
import dataclasses
import os

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.db.connector import (ReadPlan, TablePair, TransposedView,
                                dbsetup, delete, recover_connector)
from repro.db.kvstore import COMBINERS, ShardedTable, StoreConfig
from repro.obs import default_registry

FUZZ_BUDGET = int(os.environ.get("FUZZ_BUDGET", "0"))

# one tiny fixed geometry for every example: jit caches stay warm
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
    assert set(got) == set(want), (label, ctx, set(got) ^ set(want))
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-4, abs=1e-5), \
            (label, ctx, k, got[k], want[k])


# ------------------------------------------------ column-selector routes
@settings(max_examples=8 + FUZZ_BUDGET, deadline=None)
@given(st.integers(0, 2 ** 31 - 1),
       st.sampled_from(COMBINERS),
       st.lists(st.sampled_from(["ins", "ins", "ins", "flush", "compact",
                                 "colscan", "colquery"]),
                min_size=4, max_size=12))
def test_column_selector_routes_agree(seed, combiner, ops):
    """Random ingest/flush/compact interleavings; every column read must
    return identical results from (a) the transpose-routed fused scan,
    (b) the on-device col_filter pushdown on a row-driven scan, and
    (c) the full-scan + host-isin baseline — all equal to the oracle.
    Ends by checking the sibling IS the transpose of the forward table."""
    rng = np.random.default_rng(seed)
    pair = ShardedTable(f"cprop_{combiner}", transpose=True,
                        combiner=combiner, **CFG)
    oracle = {}

    def check_colscan():
        lo = int(rng.integers(0, CFG["id_capacity"]))
        hi = min(lo + int(rng.integers(0, 64)), CFG["id_capacity"] + 4)
        want = {k: v for k, v in oracle.items() if lo <= k[1] < hi}
        ctx = (seed, combiner, lo, hi)
        routed = _as_dict(*pair.scan_col_range(lo, hi))
        ids = np.arange(lo, min(hi, CFG["id_capacity"]), dtype=np.int32)
        pushed = _as_dict(*pair.scan_range(0, CFG["id_capacity"],
                                           col_filter=ids))
        r, c, v = pair.scan()
        keep = (c >= lo) & (c < hi)
        host = _as_dict(r[keep], c[keep], v[keep])
        _check_close(routed, want, "transpose-routed", ctx)
        _check_close(pushed, want, "col_filter-pushdown", ctx)
        _check_close(host, want, "host-isin", ctx)

    def check_colquery():
        cols = np.asarray(sorted({k[1] for k in oracle}), np.int32)
        if len(cols) == 0:
            return
        pick = rng.choice(cols, size=min(8, len(cols)), replace=False)
        absent = rng.integers(0, CFG["id_capacity"], 2).astype(np.int32)
        q = np.unique(np.concatenate([pick, absent])).astype(np.int32)
        want = {k: v for k, v in oracle.items() if k[1] in set(q.tolist())}
        ctx = (seed, combiner, q.tolist())
        routed = _as_dict(*pair.query_cols(q))
        pushed = _as_dict(*pair.scan_range(0, CFG["id_capacity"],
                                           col_filter=q))
        _check_close(routed, want, "query_cols", ctx)
        _check_close(pushed, want, "col_filter-pushdown", ctx)

    for op in ops:
        if op == "ins":
            n = int(rng.integers(1, 24))
            r = rng.integers(0, CFG["id_capacity"], n).astype(np.int32)
            c = rng.integers(0, CFG["id_capacity"], n).astype(np.int32)
            v = rng.integers(-4, 5, n).astype(np.float32)
            pair.insert(r, c, v)
            _oracle_apply(oracle, r, c, v, combiner)
        elif op == "flush":
            pair.flush()
        elif op == "compact":
            pair.major_compact()
        elif op == "colscan":
            check_colscan()
        else:
            check_colquery()
    check_colscan()
    # the sibling is EXACTLY the transpose of the forward table
    fwd = _as_dict(*pair.scan())
    sib = _as_dict(*pair.t_store.scan())
    _check_close(sib, {(b, a): v for (a, b), v in fwd.items()},
                 "sibling-transpose", (seed, combiner))
    pair.close()


def test_col_range_read_is_one_sibling_dispatch():
    """Structural acceptance: a column range read on a pair serves from
    the transpose sibling's fused scan — sibling scan dispatches move,
    while the primary's full-scan counter, the primary's own dispatch
    counters, and the sibling's point-query path ALL stay flat."""
    reg = default_registry()
    st = ShardedTable("onedisp", transpose=True, combiner="last", **CFG)
    rng = np.random.default_rng(5)
    for _ in range(6):
        r = rng.integers(0, CFG["id_capacity"], 24).astype(np.int32)
        c = rng.integers(0, CFG["id_capacity"], 24).astype(np.int32)
        st.insert(r, c, rng.normal(size=24).astype(np.float32))
    st.flush()
    st.scan_col_range(10, 90)  # warm the compiled path

    def snap():
        full = sum(x.value for x in reg.series("db_full_scans",
                                               table="onedisp"))
        return (full,
                st.engine_stats()["scan_dispatches"],
                st.engine_stats()["fused_dispatches"],
                st.t_store.engine_stats()["scan_dispatches"],
                st.t_store.engine_stats()["fused_dispatches"])

    before = snap()
    r, c, v = st.scan_col_range(10, 90)
    assert len(r) > 0
    after = snap()
    assert after[0] == before[0], "column read fell back to a full scan"
    assert after[1] == before[1], "primary scan path dispatched"
    assert after[2] == before[2], "primary point-query path dispatched"
    sib_scans = after[3] - before[3]
    assert 1 <= sib_scans <= CFG["num_shards"], sib_scans
    assert after[4] == before[4], "sibling point-query path dispatched"
    st.close()


def test_empty_col_filter_short_circuits():
    st = ShardedTable("emptyf", transpose=True, combiner="last", **CFG)
    st.insert(np.asarray([1, 2], np.int32), np.asarray([3, 4], np.int32),
              np.asarray([1.0, 2.0], np.float32))
    r, c, v = st.scan_range(0, CFG["id_capacity"],
                            col_filter=np.zeros(0, np.int32))
    assert len(r) == len(c) == len(v) == 0
    r, c, v = st.query_rows(np.asarray([1, 2], np.int32),
                            col_filter=np.zeros(0, np.int32))
    assert len(r) == 0
    st.close()


def test_insert_routed_rejected_on_pair():
    st = ShardedTable("irpair", transpose=True, combiner="last", **CFG)
    with pytest.raises(ValueError, match="sibling"):
        st.insert_routed(np.asarray([1], np.int32),
                         np.asarray([2], np.int32),
                         np.asarray([1.0], np.float32))
    st.close()


# ------------------------------------------------------ connector surface
def _server(**kw):
    conf = dict(num_shards=2, capacity_per_shard=2048, batch_cap=256,
                id_capacity=1 << 10)
    conf.update(kw)
    return dbsetup("tp", conf)


def _put_demo(pair, n=60, seed=0):
    rng = np.random.default_rng(seed)
    rows = np.asarray([f"e{i:03d}" for i in rng.integers(0, 30, n)], object)
    cols = np.asarray([f"v{i:03d}" for i in rng.integers(0, 30, n)], object)
    vals = rng.integers(1, 9, n).astype(float)
    pair.put_triple(rows, cols, vals)
    return rows, cols, vals


def test_pair_binding_is_one_store_and_routes_columns():
    DB = _server()
    E = DB["edges", "edgesT"]
    assert isinstance(E, TablePair)
    assert isinstance(DB.tables["edgesT"], TransposedView)
    _put_demo(E)
    store = E.table.store
    assert store.t_store is not None
    # ONE ingest, engine dual-writes: sibling mirrors the primary exactly
    assert store.nnz() == store.t_store.nnz() == E.nnz()
    # column range read via the pair == via the view == host oracle
    full = _as_str_set(E[:, :])
    want = {(r, c, v) for r, c, v in full if "v005" <= c <= "v015"}
    got = _as_str_set(E[:, "v005,:,v015,"])
    assert got == want
    got_view = _as_str_set(DB.tables["edgesT"]["v005,:,v015,", :])
    assert got_view == {(c, r, v) for r, c, v in want}
    # re-binding the same pair returns the same underlying table
    E2 = DB["edges", "edgesT"]
    assert E2.table is E.table
    # metrics: pair reported once, sibling nested under "transpose"
    m = DB.metrics()
    assert "transpose" in m["tables"]["edges"]
    assert m["tables"]["edges"]["transpose"]["sibling"] == "edges@T"
    assert "edgesT" not in m["tables"]
    delete(E)


def _as_str_set(assoc):
    r, c, v = assoc.triples()
    return {(str(a), str(b), float(x)) for a, b, x in zip(r, c, v)}


def test_rebinding_single_table_as_pair_raises():
    DB = _server()
    DB["solo"]
    with pytest.raises(ValueError, match="transpose"):
        DB["solo", "soloT"]
    DB.drop("solo")


def test_pair_checkpoint_and_recovery(tmp_path):
    """One checkpoint covers both sides; recovery by the (name, name_t)
    tuple rebuilds the pair — including post-checkpoint batches that live
    only as pair-tagged WAL records — and column routing still works."""
    d = str(tmp_path / "wal_root")
    DB = dbsetup("durpair", dict(num_shards=2, capacity_per_shard=2048,
                                 batch_cap=256, id_capacity=1 << 10,
                                 wal_root=d))
    E = DB["edges", "edgesT"]
    _put_demo(E, seed=1)
    E.checkpoint()
    E.put_triple(np.asarray(["zz"], object), np.asarray(["yy"], object),
                 np.asarray([42.0]))
    want = _as_str_set(E[:, :])
    want_col = _as_str_set(E[:, "v005,:,v015,"]) | {("zz", "yy", 42.0)} \
        if "v005" <= "yy" <= "v015" else _as_str_set(E[:, "v005,:,v015,"])
    del E, DB  # crash
    DB2, E2 = recover_connector(d, ("edges", "edgesT"))
    assert isinstance(E2, TablePair)
    store = E2.table.store
    assert store.t_store is not None
    assert store.nnz() == store.t_store.nnz()
    assert _as_str_set(E2[:, :]) == want
    assert _as_str_set(E2[:, "v005,:,v015,"]) == want_col
    # recovering a pair-checkpointed table by its single name still works
    del E2, DB2
    DB3, T3 = recover_connector(d, "edges")
    assert _as_str_set(T3[:, :]) == want
    # ...but tuple recovery of a non-pair table must refuse
    T4 = DB3["plain"]
    T4.put_triple(np.asarray(["a"], object), np.asarray(["b"], object),
                  np.asarray([1.0]))
    T4.checkpoint()
    del T4, DB3
    with pytest.raises(ValueError, match="pair"):
        recover_connector(d, ("plain", "plainT"))


def test_delete_pair_and_drop_release_the_store():
    DB = _server()
    E = DB["e", "eT"]
    _put_demo(E, n=10)
    store = E.table.store
    sib = store.t_store
    delete(E)
    assert store._closed and sib._closed
    assert DB.ls() == []
    with pytest.raises(RuntimeError):
        E.nnz()
    # drop() releases single-table stores too (old pop-only drop leaked
    # the device memtables and WAL handle)
    T = DB["solo"]
    st = T.store
    DB.drop("solo")
    assert st._closed and T._deleted
    # double-delete stays a no-op
    DB.drop("solo")
    delete(T)


# ------------------------------------------------- ReadPlan / StoreConfig
def test_read_plan_kinds_and_filter_ids():
    DB = _server()
    DB.encode_keys(np.asarray([f"k{i:02d}" for i in range(10)], object))
    assert DB.resolve_selector_plan(":").kind == "all"
    assert DB.resolve_selector_plan(None, axis="col").axis == "col"
    p = DB.resolve_selector_plan("k02,k05,")
    assert p.kind == "ids" and sorted(p.ids.tolist()) == [2, 5]
    r = DB.resolve_selector_plan("k02,:,k05,")
    assert (r.kind, r.lo, r.hi, r.filter) == ("range", 2, 6, None)
    assert r.filter_ids().tolist() == [2, 3, 4, 5]
    pre = DB.resolve_selector_plan("k0*,")
    assert pre.kind == "range" and (pre.lo, pre.hi) == (0, 10)
    route = r.with_route("transpose")
    assert route.route == "transpose" and r.route == "native"
    missing = DB.resolve_selector_plan("nope,")
    assert missing.kind == "ids" and len(missing.ids) == 0


def test_resolve_selector_shim_warns_and_matches_plan():
    DB = _server()
    DB.encode_keys(np.asarray(["a", "b", "c"], object))
    with pytest.warns(DeprecationWarning):
        ids = DB.resolve_selector("a,c,")
    assert sorted(ids.tolist()) == [0, 2]
    with pytest.warns(DeprecationWarning):
        assert DB.resolve_selector(":") is None


def test_store_config_roundtrip_and_overrides():
    cfg = StoreConfig(num_shards=3, l0_slots=5, transpose=True,
                      memtable_cap=128)
    rt = StoreConfig.from_manifest(dataclasses.asdict(cfg))
    assert rt == cfg
    # legacy manifest: mem_cap maps in, unknown per-table keys ignored
    legacy = {"num_shards": 2, "mem_cap": 99, "combiner": "sum",
              "bloom_bits_per_key": [8]}
    rt2 = StoreConfig.from_manifest(legacy)
    assert rt2.num_shards == 2 and rt2.memtable_cap == 99
    # kwargs still override the shared config at every layer
    DB = dbsetup("cfg", dict(config=StoreConfig(num_shards=2),
                             num_shards=4, fanout=8))
    assert DB.num_shards == 4 and DB.config.fanout == 8
    st = ShardedTable("cfgtab", config=DB.config, num_shards=8)
    assert st.S == 8 and st.config.num_shards == 8
    st.close()
    with pytest.raises(TypeError):
        DB.config.replace(not_a_field=1)


def test_transpose_requires_lsm_engine():
    with pytest.raises(ValueError, match="lsm"):
        ShardedTable("bad", engine="single", transpose=True,
                     num_shards=1, capacity_per_shard=512,
                     batch_cap=64, id_capacity=1 << 8)
