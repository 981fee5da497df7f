"""Observability layer tests (ISSUE 6): histogram quantile accuracy vs a
numpy oracle, labeled-series aggregation, cross-process snapshot merging,
span nesting/ring eviction/slow-op capture, Chrome-trace export shape,
the engine counter schema, DBserver.metrics(), and the disabled-mode
overhead budget (instrumentation must cost <2% of a query when off)."""
import json
import math
import time
from time import perf_counter

import numpy as np
import pytest

from repro.db import dbsetup
from repro.db.kvstore import ShardedTable
from repro.obs import (Counter, Gauge, Histogram, Registry, Tracer,
                       default_registry, default_tracer, merge_snapshots,
                       set_enabled)

# histogram buckets grow by 2**(1/8): any sample's representative is
# within ~4.4% of the true value; 12% headroom covers rank-vs-bucket
# interaction at sparse tails
QUANT_RTOL = 0.12


# ------------------------------------------------------------- histograms
def _fill(h, xs):
    for x in xs:
        h.observe(float(x))


@pytest.mark.parametrize("dist", ["powerlaw", "constant", "bimodal"])
def test_histogram_quantiles_vs_numpy_oracle(dist):
    rng = np.random.default_rng(42)
    n = 20_000
    if dist == "powerlaw":          # latency-shaped heavy tail
        xs = 1e-4 * (1.0 + rng.pareto(1.5, n))
    elif dist == "constant":
        xs = np.full(n, 3.7e-3)
    else:                           # fast path + slow path mixture
        xs = np.where(rng.random(n) < 0.9,
                      np.abs(rng.normal(2e-4, 2e-5, n)),
                      np.abs(rng.normal(2e-2, 2e-3, n)))
    reg = Registry()
    h = reg.histogram("t_lat")
    _fill(h, xs)
    assert h.count == n
    assert h.min == pytest.approx(xs.min()) and h.max == pytest.approx(xs.max())
    assert h.mean == pytest.approx(xs.mean(), rel=1e-6)
    for q in (0.50, 0.90, 0.99, 0.999):
        got = h.quantile(q)
        # nearest-rank oracle (matches the histogram's rank definition)
        want = float(np.quantile(xs, q, method="inverted_cdf"))
        if dist == "constant":
            assert got == pytest.approx(want, rel=1e-12), q
        else:
            assert got == pytest.approx(want, rel=QUANT_RTOL), (q, got, want)
    p = h.percentiles()
    assert set(p) == {"p50", "p90", "p99", "p999"}
    assert p["p50"] <= p["p90"] <= p["p99"] <= p["p999"]


def test_histogram_merge_equals_pooled():
    """Merging two histograms must equal one histogram fed all samples —
    exactly, bucket for bucket (same fixed layout; only float ``sum`` is
    order-dependent)."""
    rng = np.random.default_rng(7)
    a, b = rng.exponential(1e-3, 5000), rng.exponential(5e-3, 3000)
    reg = Registry()
    ha, hb, pooled = (reg.histogram("m", part=i) for i in range(3))
    _fill(ha, a)
    _fill(hb, b)
    _fill(pooled, np.concatenate([a, b]))
    merged = reg.histogram("m", part=9)
    merged.merge(ha)
    merged.merge(hb)
    assert merged._buckets == pooled._buckets
    assert merged.count == pooled.count == 8000
    assert merged.min == pooled.min and merged.max == pooled.max
    assert merged.sum == pytest.approx(pooled.sum, rel=1e-9)
    for q in (0.5, 0.99):
        assert merged.quantile(q) == pooled.quantile(q)
    # snapshot -> load_snapshot round-trip preserves buckets
    h2 = reg.histogram("m", part=10)
    h2.load_snapshot(pooled.snapshot())
    assert h2._buckets == pooled._buckets and h2.count == pooled.count


def test_empty_histogram_is_nan_and_snapshot_minimal():
    h = Registry().histogram("e")
    assert math.isnan(h.quantile(0.5)) and math.isnan(h.mean)
    assert h.snapshot() == {"count": 0, "sum": 0.0}


# --------------------------------------------------------------- registry
def test_registry_series_identity_labels_and_kind_guard():
    reg = Registry()
    c1 = reg.counter("hits", table="t", shard=0)
    c2 = reg.counter("hits", shard=0, table="t")   # label order irrelevant
    assert c1 is c2
    c1.inc()
    c1.inc(4)
    assert c2.value == 5
    with pytest.raises(TypeError):
        reg.histogram("hits", table="t", shard=0)  # kind mismatch
    g = reg.gauge("depth", table="t")
    g.set(3.5)
    assert g.value == 3.5


def test_label_aggregation_and_filtering():
    reg = Registry()
    for s in range(4):
        reg.counter("ops", table="a", shard=s).inc(s + 1)
    reg.counter("ops", table="b", shard=0).inc(100)
    assert reg.aggregate("ops", table="a") == 1 + 2 + 3 + 4
    assert reg.aggregate("ops") == 110
    assert reg.aggregate("ops", table="a", shard=2) == 3
    assert reg.aggregate("nosuch") is None
    assert len(reg.series("ops", table="a")) == 4
    # histogram aggregation merges across the filtered series
    for s, v in ((0, 1e-3), (1, 4e-3)):
        h = reg.histogram("lat", table="a", shard=s)
        for _ in range(10):
            h.observe(v)
    agg = reg.aggregate("lat", table="a")
    assert agg["count"] == 20
    assert agg["min"] == pytest.approx(1e-3) and agg["max"] == pytest.approx(4e-3)


def test_merge_snapshots_across_processes():
    """Per-process registry snapshots merge at the host: counters sum,
    histograms bucket-merge (the spmd per-process path)."""
    snaps = []
    for proc in range(3):
        reg = Registry()
        reg.counter("n_steps", op="ingest").inc(10 * (proc + 1))
        h = reg.histogram("step_s", op="ingest")
        for _ in range(50):
            h.observe(1e-3 * (proc + 1))
        snaps.append(reg.snapshot())
    merged = merge_snapshots(snaps)
    assert merged["n_steps{op=ingest}"] == 60
    hs = merged["step_s{op=ingest}"]
    assert hs["count"] == 150
    assert hs["min"] == pytest.approx(1e-3) and hs["max"] == pytest.approx(3e-3)
    from repro.db.spmd import merge_process_metrics
    assert merge_process_metrics(snaps) == merged


def test_registry_disabled_is_noop():
    reg = Registry(enabled=False)
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h")
    c.inc(5)
    g.set(2.0)
    h.observe(1e-3)
    assert c.value == 0 and g.value == 0.0 and h.count == 0
    reg.enabled = True
    c.inc(5)
    assert c.value == 5


# ---------------------------------------------------------------- tracing
def test_span_nesting_and_ring_eviction():
    tr = Tracer(capacity=4, slow_threshold_s=10.0)
    with tr.span("outer", table="t"):
        with tr.span("inner"):
            pass
    spans = tr.spans()
    inner, outer = spans[-2], spans[-1]   # inner exits (records) first
    assert inner["name"] == "inner" and inner["depth"] == 1 \
        and inner["parent"] == "outer"
    assert outer["name"] == "outer" and outer["depth"] == 0 \
        and outer["parent"] is None
    assert outer["labels"] == {"table": "t"}
    assert outer["dur"] >= inner["dur"] >= 0.0
    for i in range(6):                    # ring evicts oldest beyond cap
        with tr.span(f"s{i}"):
            pass
    assert [r["name"] for r in tr.spans()] == ["s2", "s3", "s4", "s5"]
    assert tr.slow_ops() == []            # nothing crossed 10s


def test_slow_op_log_and_exports(tmp_path):
    tr = Tracer(slow_threshold_s=0.005)
    with tr.span("fast"):
        pass
    with tr.span("slow", table="t", shard=1):
        time.sleep(0.012)
    slow = tr.slow_ops()
    assert [r["name"] for r in slow] == ["slow"]
    assert slow[0]["dur"] >= 0.005
    jpath, cpath = tmp_path / "trace.json", tmp_path / "chrome.json"
    tr.export_json(str(jpath))
    tr.export_chrome(str(cpath))
    j = json.loads(jpath.read_text())
    assert [s["name"] for s in j["spans"]] == ["fast", "slow"]
    assert j["slow_threshold_s"] == 0.005
    chrome = json.loads(cpath.read_text())
    evs = chrome["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X" and ev["cat"] == "repro.db"
        assert ev["dur"] >= 0 and "depth" in ev["args"]
    slow_ev = [e for e in evs if e["name"] == "slow"][0]
    assert slow_ev["dur"] >= 5_000        # microseconds
    assert slow_ev["args"]["table"] == "t"
    tr.clear()
    assert tr.spans() == [] and tr.slow_ops() == []


def test_disabled_tracer_hands_back_shared_null_span():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("a"), tr.span("b", x=1)
    assert s1 is s2                       # one shared no-op object
    with s1:
        pass
    assert tr.spans() == []
    assert tr.current_trace_id() is None
    assert tr.flight_recordings() == []


def test_span_self_time_is_duration_less_direct_children():
    reg = Registry()
    tr = Tracer(slow_threshold_s=10.0, registry=reg)
    with tr.span("root"):
        time.sleep(0.02)
        with tr.span("child"):
            time.sleep(0.03)
            with tr.span("grandchild"):
                time.sleep(0.01)
        with tr.span("child"):
            time.sleep(0.01)
    names = ("root", "child", "grandchild")
    dur = {n: reg.histogram("span_s", span=n) for n in names}
    own = {n: reg.counter("span_self_s", span=n).value for n in names}
    assert [dur[n].count for n in names] == [1, 2, 1]
    # self time = duration - the time the DIRECT children covered
    assert own["root"] == pytest.approx(dur["root"].sum - dur["child"].sum,
                                        abs=1e-9)
    assert own["child"] == pytest.approx(
        dur["child"].sum - dur["grandchild"].sum, abs=1e-9)
    assert own["grandchild"] == pytest.approx(dur["grandchild"].sum,
                                              abs=1e-9)
    # ... and each is about the sleep written at that level
    for n, slept in (("root", 0.02), ("child", 0.04), ("grandchild", 0.01)):
        assert slept <= own[n] < slept + 0.05, (n, own[n])
    # the ring records carry the same durations, stamped on the wall clock
    recs = {r["name"]: r for r in tr.spans()}
    assert recs["root"]["dur"] == pytest.approx(dur["root"].sum, abs=1e-12)
    assert abs(recs["root"]["ts"] - time.time()) < 5.0


class _RecordingAnnotation:
    """Stand-in for jax.profiler.TraceAnnotation that logs enters/exits."""
    log = []

    def __init__(self, name, **kwargs):
        assert not kwargs               # the name only, no labels
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_enabled_spans_enter_a_profiler_annotation(monkeypatch):
    from repro.obs import tracing
    monkeypatch.setattr(tracing, "_ANNOTATION", _RecordingAnnotation)
    monkeypatch.setattr(_RecordingAnnotation, "log", [])
    tr = Tracer(registry=Registry())
    with tr.span("outer", table="t"):
        with tr.span("inner", n=3):
            pass
    assert _RecordingAnnotation.log == [
        ("enter", "outer"), ("enter", "inner"),
        ("exit", "inner"), ("exit", "outer")]


def test_real_annotation_is_jax_trace_annotation():
    import jax
    with Tracer(registry=Registry()).span("probe") as sp:
        assert isinstance(sp.ann, jax.profiler.TraceAnnotation)


def test_disabled_tracer_makes_no_span_series_and_no_annotation(monkeypatch):
    from repro.obs import tracing
    monkeypatch.setattr(tracing, "_ANNOTATION", _RecordingAnnotation)
    monkeypatch.setattr(_RecordingAnnotation, "log", [])
    reg = Registry()
    tr = Tracer(enabled=False, registry=reg)
    with tr.span("a"):
        with tr.span("b", x=1):
            pass
    assert reg.series("span_s") == [] and reg.series("span_self_s") == []
    assert _RecordingAnnotation.log == []
    assert tr.spans() == []


def test_span_feeds_the_site_histogram_with_its_own_reading():
    reg = Registry()
    site = reg.histogram("db_op_latency_s", table="t", op="ingest")
    tr = Tracer(registry=reg)
    with tr.span("ingest", site, table="t"):
        time.sleep(0.002)
    assert site.count == 1
    assert site.sum == reg.histogram("span_s", span="ingest").sum
    with pytest.raises(RuntimeError):      # a failed op is not a latency
        with tr.span("ingest", site):
            raise RuntimeError("boom")
    assert site.count == 1
    assert reg.histogram("span_s", span="ingest").count == 2
    # a disabled tracer still times the site (its own switch is the
    # registry's), and makes no span series
    off_reg = Registry()
    off_site = off_reg.histogram("wal_latency_s", log="t", op="append")
    off = Tracer(enabled=False, registry=off_reg)
    with off.span("wal.append", off_site):
        time.sleep(0.002)
    assert off_site.count == 1 and off_site.sum >= 0.002
    assert off_reg.series("span_s") == []


# ------------------------------------ trace context + flight recorder
def test_trace_id_propagation_root_allocates_children_inherit():
    tr = Tracer(slow_threshold_s=10.0)
    with tr.span("op_a", table="t") as root:
        a_trace = root.trace
        assert tr.current_trace_id() == a_trace
        with tr.span("kv") as child:
            assert child.trace == a_trace       # inherited, not fresh
            with tr.span("wal") as grand:
                assert grand.trace == a_trace
    with tr.span("op_b") as root_b:
        b_trace = root_b.trace
    assert a_trace != b_trace                   # one id per root op
    assert tr.current_trace_id() is None        # nothing open
    by_trace = {}
    for rec in tr.spans():
        by_trace.setdefault(rec["trace"], set()).add(rec["name"])
    assert by_trace[a_trace] == {"op_a", "kv", "wal"}
    assert by_trace[b_trace] == {"op_b"}


def test_histogram_exemplars_capture_merge_and_roundtrip():
    from repro.obs import span as gspan

    reg = Registry()
    h = reg.histogram("lat")
    h.observe(1e-3)                       # no open span -> no exemplar
    assert h.exemplars() == {}
    with gspan("op"):
        from repro.obs import current_trace
        tid = current_trace()
        assert tid is not None
        h.observe(2e-3)
        h.observe(64e-3)                  # different bucket, same trace
    ex = h.exemplars()
    assert len(ex) == 2
    assert all(t == tid for _v, t in ex.values())
    assert sorted(v for v, _t in ex.values()) == [2e-3, 64e-3]
    # snapshot carries them; load_snapshot round-trips into a sibling
    snap = h.snapshot()
    assert {e["trace"] for e in snap["exemplars"].values()} == {tid}
    h2 = reg.histogram("lat2")
    h2.load_snapshot(snap)
    assert h2.exemplars() == ex
    # merge propagates exemplars (latest-wins per bucket)
    h3 = reg.histogram("lat3")
    h3.merge(h)
    assert h3.exemplars() == ex
    # disabled registry: observe is a no-op, no exemplar capture even
    # under an open span (the kill switch gates the whole hot path)
    off = Registry(enabled=False)
    hoff = off.histogram("lat")
    with gspan("op2"):
        hoff.observe(5e-3)
    assert hoff.count == 0 and hoff.exemplars() == {}


def test_flight_recorder_captures_slow_trees_and_evicts():
    tr = Tracer(slow_threshold_s=0.005, flight_capacity=2)
    with tr.span("fast_root"):            # under threshold: not recorded
        with tr.span("child"):
            pass
    assert tr.flight_recordings() == []
    with tr.span("slow_root", table="t") as root:
        slow_trace = root.trace
        with tr.span("child_a"):
            pass
        with tr.span("child_b"):
            time.sleep(0.008)
    recs = tr.flight_recordings()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["trace"] == slow_trace
    assert rec["root"]["name"] == "slow_root"
    # full tree in completion order, every span sharing the root's trace
    assert [s["name"] for s in rec["spans"]] == \
        ["child_a", "child_b", "slow_root"]
    assert all(s["trace"] == slow_trace for s in rec["spans"])
    # the root's wall includes its children: a slow child alone pushes
    # the root over the threshold, so the tree is still captured
    with tr.span("root2"):
        with tr.span("slow_child"):
            time.sleep(0.008)
    assert [r["root"]["name"] for r in tr.flight_recordings()] == \
        ["slow_root", "root2"]
    # bounded ring: capacity 2 keeps only the newest two recordings
    for i in range(3):
        with tr.span(f"slow_{i}"):
            time.sleep(0.006)
    names = [r["root"]["name"] for r in tr.flight_recordings()]
    assert len(names) == 2 and names == ["slow_1", "slow_2"]
    tr.clear()
    assert tr.flight_recordings() == []


# ------------------------------------------- engine/server instrumentation
_CFG = dict(num_shards=2, capacity_per_shard=2048, batch_cap=256,
            id_capacity=1 << 10, memtable_cap=64, l0_slots=4)


def _tiny(name):
    st = ShardedTable(name, **_CFG)
    rng = np.random.default_rng(5)
    r = rng.integers(0, 1 << 10, 200).astype(np.int32)
    for i in range(0, 200, 50):           # memtable cap is 64
        st.insert(r[i:i + 50], np.zeros(50, np.int32),
                  np.ones(50, np.float32))
    st.flush()
    return st, r


@pytest.mark.parametrize("table", ["plain", "pair_sibling"])
def test_engine_stats_schema(table):
    """``engine_stats()`` is exactly the engine's counter schema plus the
    two geometry keys — on a plain table and on a transpose pair's
    sibling — and the registry holds no per-table ``lsm_*`` counter
    outside that schema and the engine's write/tablet counters."""
    from repro.db.lsm.engine import STAT_KEYS
    assert STAT_KEYS == ("flushes", "major_compactions", "runs_probed",
                         "runs_skipped", "fused_dispatches",
                         "fused_widen_retries", "fused_tiles",
                         "scan_dispatches", "scan_widen_retries")
    name = "schema_" + table
    st = ShardedTable(name, transpose=(table == "pair_sibling"), **_CFG)
    st.insert(np.arange(50, dtype=np.int32), np.zeros(50, np.int32),
              np.ones(50, np.float32))
    target = st.t_store if table == "pair_sibling" else st
    target.query_rows(np.arange(8, dtype=np.int32))
    target.scan_range(0, 32)
    stats = target.engine_stats()
    assert set(stats) == set(STAT_KEYS) | {"l0_used", "level_entries"}
    assert len(stats["l0_used"]) == _CFG["num_shards"]
    assert stats["fused_dispatches"] + stats["scan_dispatches"] >= 1
    table_ctrs = {i.name for i in default_registry().series(
                      table=target.name)
                  if i.kind == "counter" and set(i.labels) == {"table"}
                  and i.name.startswith("lsm_")}
    assert table_ctrs == {"lsm_" + k for k in STAT_KEYS} | {
        "lsm_flush_entries", "lsm_compact_entries",
        "lsm_compact_skipped_shards", "lsm_tablet_splits",
        "lsm_tablet_moves", "lsm_tablet_merges"}
    st.close()


def test_metrics_and_debug_bundle_report_the_lsm_engine(tmp_path):
    """The operator-facing formats keep their ``"engine": "lsm"`` key."""
    import zipfile
    DB = dbsetup("engdb", dict(num_shards=2, capacity_per_shard=4096,
                               batch_cap=2048, id_capacity=1 << 16))
    T = DB["etab"]
    T.put_triple(np.asarray(["a", "b"], object), np.asarray(["x", "y"], object),
                 np.asarray([1.0, 2.0]))
    assert DB.metrics()["tables"]["etab"]["engine"] == "lsm"
    path = DB.debug_bundle(str(tmp_path / "b.zip"))
    with zipfile.ZipFile(path) as zf:
        geo = json.loads(zf.read("resident_geometry.json"))
        cfg = json.loads(zf.read("store_config.json"))
    assert geo["etab"]["engine"] == "lsm"
    assert (cfg["engine"], cfg["fused_reads"]) == ("lsm", True)


def test_ingest_and_query_series_land_in_registry():
    st, r = _tiny("obs_tab")
    reg = default_registry()
    per_shard = sum(c.value for c in reg.series("db_ingest_entries",
                                                table="obs_tab"))
    assert per_shard == 200               # every ingested entry attributed
    st.query_rows(np.unique(r[:16]))
    st.scan_range(0, 64)
    hq = reg.series("db_op_latency_s", table="obs_tab", op="query")
    hs = reg.series("db_op_latency_s", table="obs_tab", op="scan")
    assert len(hq) == 1 and hq[0].count >= 1 and hq[0].min > 0
    assert len(hs) == 1 and hs[0].count >= 1
    assert sum(c.value for c in
               reg.series("db_point_queries", table="obs_tab")) >= 1


def test_dbserver_metrics_and_dump(tmp_path):
    DB = dbsetup("obsdb", dict(num_shards=2, capacity_per_shard=4096,
                               batch_cap=2048, id_capacity=1 << 16))
    T = DB["mtab"]
    T.put_triple(np.asarray(["a", "b", "c"], object),
                 np.asarray(["x", "x", "y"], object),
                 np.asarray([1.0, 2.0, 3.0]))
    assert T["a,", :].nnz() == 1
    m = DB.metrics()
    assert m["instance"] == "obsdb"
    tab = m["tables"]["mtab"]
    assert set(tab["latency_s"]) == {"ingest", "query", "scan", "flush",
                                     "major_compaction"}
    assert tab["latency_s"]["ingest"]["count"] >= 1
    assert tab["counters"]["fused_dispatches"] >= 0
    assert set(tab["shards"]) == {"0", "1"}
    shard_ing = sum(s["ingest_entries"] for s in tab["shards"].values())
    assert shard_ing >= 3                 # transpose table is separate
    agg = m["aggregate"]
    assert agg["latency_s"]["ingest"]["count"] >= \
        tab["latency_s"]["ingest"]["count"]
    assert agg["counters"]["flushes"] >= 0
    path = tmp_path / "metrics.json"
    snap = DB.dump_metrics(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk["instance"] == "obsdb"
    assert on_disk["tables"].keys() == snap["tables"].keys()


# ------------------------------------------------------- disabled overhead
def test_disabled_mode_overhead_budget():
    """Acceptance bar: with the registry disabled, the instrumentation
    left in the hot path must cost <2% of a point query. Measured as
    (actual instrument touches for one query) x (measured per-op disabled
    cost), against the measured query wall time.

    The v2 surface rides inside the same gated sites: trace-id allocation
    lives in ``_Span.__enter__`` (a disabled tracer hands back the shared
    null span, so no id is ever allocated) and exemplar capture lives in
    ``Histogram.observe`` AFTER the ``enabled`` early-return — so the
    disabled per-op costs measured below are the true all-in costs of the
    PR-9 instrumentation, not a subset."""
    st, r = _tiny("ovh_tab")
    st.insert(r[:32], np.zeros(32, np.int32), np.ones(32, np.float32))
    q = np.unique(r[:8])
    st.query_rows(q)                      # warm the jit cache
    reps = 15
    times = []
    for _ in range(reps):
        t0 = perf_counter()
        st.query_rows(q)
        times.append(perf_counter() - t0)
    query_wall = sorted(times)[reps // 2]

    # count the instrument touches ONE query actually performs
    reg, tr = default_registry(), default_tracer()
    c0 = {id(i): i.value for i in reg.series() if i.kind == "counter"}
    h0 = {id(i): i.count for i in reg.series() if i.kind == "histogram"}
    tr.clear()
    st.query_rows(q)
    n_incs = sum(1 for i in reg.series()
                 if i.kind == "counter" and i.value != c0.get(id(i), 0))
    n_obs = sum(1 for i in reg.series()
                if i.kind == "histogram" and i.count != h0.get(id(i), 0))
    n_spans = len(tr.spans())
    assert n_spans >= 2 and n_obs >= 1    # instrumentation is actually live

    # per-op cost with everything disabled — these paths now also carry
    # the trace-context + exemplar machinery behind the same switches
    priv = Registry(enabled=False)
    ptr = Tracer(enabled=False)
    c, h = priv.counter("x"), priv.histogram("y")
    with ptr.span("probe"):
        h.observe(1e-3)                   # even under an "open" span...
    assert h.exemplars() == {} and ptr.flight_recordings() == []
    N = 20_000

    def cost(fn):
        best = math.inf
        for _ in range(3):
            t0 = perf_counter()
            for _ in range(N):
                fn()
            best = min(best, (perf_counter() - t0) / N)
        return best

    inc_cost = cost(c.inc)
    obs_cost = cost(lambda: h.observe(1e-3))
    span_cost = cost(lambda: ptr.span("s"))
    budget = (n_incs * inc_cost + n_obs * obs_cost
              + (n_spans + 2) * span_cost)
    assert budget < 0.02 * query_wall, (
        f"disabled-mode budget {budget * 1e6:.2f}us exceeds 2% of "
        f"query wall {query_wall * 1e6:.1f}us "
        f"(incs={n_incs} obs={n_obs} spans={n_spans})")


def test_set_enabled_kill_switch_round_trip():
    reg = default_registry()
    c = reg.counter("kill_switch_probe")
    c.reset()
    try:
        set_enabled(False)
        c.inc(7)
        assert c.value == 0
    finally:
        set_enabled(True)
    c.inc(7)
    assert c.value == 7
