"""Exporter + debug-bundle surface (ISSUE 9): Prometheus text exposition
against a golden rendering (exemplars included), snapshot -> Registry
round-trip, the JSONL emitter, the health report, debug-bundle archives
(raw writer and ``DBserver.debug_bundle``), and the registry-asserted
zero-retrace guarantee across the 64..4096 query batch sweep."""
import json
import time
import zipfile

import numpy as np
import pytest

from repro.db import dbsetup
from repro.db.kvstore import ShardedTable
from repro.obs import (JsonlEmitter, Registry, Tracer, default_registry,
                       health_report, prometheus_text, write_debug_bundle)
from repro.obs.export import registry_from_snapshot
from repro.obs.metrics import _GROWTH, _LO


# ------------------------------------------------------- prometheus text
def _golden_registry():
    """Deterministic registry: exemplars injected via load_snapshot so
    the rendered text is reproducible regardless of test order (live
    spans would consume process-global trace ids)."""
    reg = Registry()
    reg.counter("db_ingest_entries", table="t", shard=0).inc(5)
    reg.gauge("lsm_read_amplification", table="t").set(1.5)
    h = reg.histogram("db_op_latency_s", table="t", op="query")
    h.load_snapshot({"count": 3, "sum": 0.007, "min": 0.001, "max": 0.004,
                     "buckets": {"100": 2, "200": 1},
                     "exemplars": {"100": {"value": 0.001,
                                           "trace": "t000abc"}}})
    return reg


def test_prometheus_text_golden():
    le100 = repr(_LO * _GROWTH ** 100)
    le200 = repr(_LO * _GROWTH ** 200)
    want = [
        "# TYPE db_ingest_entries counter",
        'db_ingest_entries_total{shard="0",table="t"} 5',
        "# TYPE db_op_latency_s histogram",
        f'db_op_latency_s_bucket{{le="{le100}",op="query",table="t"}} 2'
        ' # {trace_id="t000abc"} 0.001',
        f'db_op_latency_s_bucket{{le="{le200}",op="query",table="t"}} 3',
        'db_op_latency_s_bucket{le="+Inf",op="query",table="t"} 3',
        'db_op_latency_s_sum{op="query",table="t"} 0.007',
        'db_op_latency_s_count{op="query",table="t"} 3',
        "# TYPE lsm_read_amplification gauge",
        'lsm_read_amplification{table="t"} 1.5',
    ]
    assert prometheus_text(_golden_registry()).splitlines() == want


def test_prometheus_text_live_exemplar_links_to_open_span():
    from repro.obs import current_trace, span
    reg = Registry()
    h = reg.histogram("lat", op="q")
    with span("golden_op"):
        tid = current_trace()
        h.observe(2e-3)
    text = prometheus_text(reg)
    assert f'# {{trace_id="{tid}"}} 0.002' in text


def test_registry_from_snapshot_round_trip():
    reg = _golden_registry()
    reg.gauge("occupancy", shard=1).set(0.25)
    snap = reg.snapshot()
    rebuilt = registry_from_snapshot(snap)
    assert rebuilt.snapshot() == snap
    # kinds survive: counters stay counters, float gauges stay gauges
    kinds = {i.name: i.kind for i in rebuilt.series()}
    assert kinds["db_ingest_entries"] == "counter"
    assert kinds["occupancy"] == "gauge"
    assert kinds["db_op_latency_s"] == "histogram"
    # exemplars survive the rebuild (Prometheus view still carries them)
    assert 'trace_id="t000abc"' in prometheus_text(rebuilt)


# ---------------------------------------------------------- jsonl emitter
def test_jsonl_emitter_on_demand_and_context_manager(tmp_path):
    reg = Registry()
    c = reg.counter("ticks")
    path = tmp_path / "metrics.jsonl"
    em = JsonlEmitter(str(path), reg=reg, interval_s=3600.0)
    c.inc()
    em.emit_once()
    c.inc()
    em.emit_once()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l["metrics"]["ticks"] for l in lines] == [1, 2]
    assert lines[0]["ts"] <= lines[1]["ts"]
    # context manager: background thread started, final emit on exit even
    # if the interval never elapsed
    with JsonlEmitter(str(path), reg=reg, interval_s=3600.0):
        c.inc(10)
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines[-1]["metrics"]["ticks"] == 12


# ---------------------------------------------------------- health report
def test_health_report_sections_and_formats():
    reg = Registry()
    reg.counter("wal_appends", log="t").inc(4)
    reg.gauge("lsm_read_amplification", table="t").set(2.5)
    h = reg.histogram("db_op_latency_s", table="t", op="query")
    h.observe(1e-3)
    md = health_report(reg.snapshot(), fmt="md")
    assert "### Health gauges" in md and "### Counters" in md \
        and "### Latency histograms" in md
    assert "lsm_read_amplification{table=t}" in md
    assert "| wal_appends | 4 |" in md
    assert "db_op_latency_s{op=query,table=t}" in md
    term = health_report(reg.snapshot(), fmt="term")
    assert "== Health gauges ==" in term and "|" not in term
    # empty snapshot still renders every section head
    empty = health_report({}, fmt="md")
    assert "(none)" in empty


# ----------------------------------------------------------- debug bundle
def test_write_debug_bundle_round_trip(tmp_path):
    reg = Registry()
    reg.counter("ops").inc(3)
    tr = Tracer(slow_threshold_s=0.002)
    with tr.span("slow_op", table="t"):
        time.sleep(0.005)
    path = str(tmp_path / "bundle.zip")
    assert write_debug_bundle(path, reg=reg, tracer=tr,
                              extra={"geometry": {"shards": 2}}) == path
    with zipfile.ZipFile(path) as zf:
        names = set(zf.namelist())
        assert names == {"metrics.json", "prometheus.txt",
                         "slow_traces.json", "geometry.json"}
        metrics = json.loads(zf.read("metrics.json"))
        assert metrics["ops"] == 3
        assert "# TYPE ops counter" in zf.read("prometheus.txt").decode()
        slow = json.loads(zf.read("slow_traces.json"))
        assert slow["slow_threshold_s"] == 0.002
        assert [r["root"]["name"]
                for r in slow["flight_recordings"]] == ["slow_op"]
        assert json.loads(zf.read("geometry.json")) == {"shards": 2}


def test_dbserver_debug_bundle_archive(tmp_path):
    DB = dbsetup("bundledb", dict(num_shards=2, capacity_per_shard=4096,
                                  batch_cap=2048, id_capacity=1 << 16))
    T = DB["btab"]
    T.put_triple(np.asarray(["a", "b", "c"], object),
                 np.asarray(["x", "x", "y"], object),
                 np.asarray([1.0, 2.0, 3.0]))
    assert T["a,", :].nnz() == 1
    path = str(tmp_path / "db_bundle.zip")
    assert DB.debug_bundle(path) == path
    with zipfile.ZipFile(path) as zf:
        names = set(zf.namelist())
        assert {"metrics.json", "prometheus.txt", "slow_traces.json",
                "store_config.json", "resident_geometry.json",
                "metrics_view.json"} <= names
        cfg = json.loads(zf.read("store_config.json"))
        assert cfg["num_shards"] == 2 and cfg["capacity_per_shard"] == 4096
        geo = json.loads(zf.read("resident_geometry.json"))
        assert "btab" in geo
        g = geo["btab"]
        assert g["num_shards"] == 2 and len(g["memtable_n"]) == 2
        assert g["engine"] == "lsm"
        assert len(g["resident_runs"]) == 2
        view = json.loads(zf.read("metrics_view.json"))
        assert view["instance"] == "bundledb"
        assert "health" in view["tables"]["btab"]


def test_export_cli_renders_snapshot_and_rejects_view(tmp_path, capsys):
    """The CLI takes a RAW registry snapshot (Registry.dump /
    debug-bundle metrics.json); the aggregated DBserver.dump_metrics
    view must be rejected with a clear message, not a TypeError."""
    from repro.obs.export import main
    snap_path = tmp_path / "reg.json"
    snap_path.write_text(json.dumps(_golden_registry().snapshot()))
    prom_path = tmp_path / "prom.txt"
    assert main(["--metrics", str(snap_path), "--format", "term",
                 "--prometheus", str(prom_path)]) == 0
    assert "== Health gauges ==" in capsys.readouterr().out
    assert 'trace_id="t000abc"' in prom_path.read_text()
    view = tmp_path / "view.json"
    view.write_text(json.dumps({"instance": "db", "tables": {},
                                "aggregate": {}}))
    with pytest.raises(SystemExit):
        main(["--metrics", str(view)])
    assert "dump_metrics() view" in capsys.readouterr().err


# ------------------------------------------------- retrace acceptance bar
def test_no_unexpected_retraces_across_query_batch_sweep():
    """ISSUE 9 acceptance criterion, registry-asserted: after
    ``warm_reads`` compiles the fused tile, NO query batch size in
    64..4096 may trigger a fresh XLA trace — the ``lsm_retraces`` counter
    and the compiled-shapes gauge must both hold still across the sweep
    (PR 5's 'no batch size ever retraces' invariant, now a metric)."""
    st = ShardedTable("retrace_sweep", num_shards=2,
                      capacity_per_shard=1 << 14, batch_cap=1024,
                      id_capacity=1 << 16, memtable_cap=1024, engine="lsm")
    rng = np.random.default_rng(11)
    rows = rng.integers(0, 1 << 16, 6144).astype(np.int32)
    for i in range(0, len(rows), 1024):
        st.insert(rows[i:i + 1024], np.zeros(1024, np.int32),
                  np.ones(1024, np.float32))
    st.flush()
    st.insert(rows[:256], np.zeros(256, np.int32),
              np.ones(256, np.float32))    # memtable tail stays resident
    st.warm_reads()
    reg = default_registry()

    def retraces():
        return sum(c.value for c in reg.series("lsm_retraces",
                                               table="retrace_sweep"))

    def shapes():
        return sum(g.value for g in reg.series("lsm_compiled_shapes",
                                               op="query"))

    warm_retraces, warm_shapes = retraces(), shapes()
    assert warm_retraces >= 1              # warm_reads really compiled
    q_pool = rng.choice(rows, 4096).astype(np.int32)
    for size in (64, 256, 1024, 2048, 4096):
        hit_rows, _c, _v = st.query_rows(q_pool[:size])
        assert len(hit_rows) > 0
        assert retraces() == warm_retraces, \
            f"batch {size} triggered a fresh trace"
        assert shapes() == warm_shapes, \
            f"batch {size} grew the compile cache"


def test_no_retraces_after_tablet_split_and_move():
    """The 'no retraces' invariant must survive TOPOLOGY changes: after a
    tablet split + move + rebalance, ``warm_reads`` (which probes ids
    sampled from each shard's OWNED ranges, not a uniform linspace) re-
    warms both serving shapes, and no query batch in 64..4096 may trace
    again — splits change routing values, never compiled shapes."""
    st = ShardedTable("retrace_tablets", num_shards=2,
                      capacity_per_shard=1 << 14, batch_cap=1024,
                      id_capacity=1 << 16, memtable_cap=1024, engine="lsm",
                      dynamic_tablets=True)
    rng = np.random.default_rng(29)
    # Zipf-skewed rows: the hot range drives a real split decision
    rows = ((rng.zipf(1.2, 6144) * 7) % (1 << 16)).astype(np.int32)
    for i in range(0, len(rows), 1024):
        st.insert(rows[i:i + 1024], np.zeros(1024, np.int32),
                  np.ones(1024, np.float32))
    assert st.split_tablet() is not None
    tm = st.tablet_map
    moved = int(tm.tablet_ids[-1])
    st.move_tablet(moved, 1 - int(tm.owners[tm.index_of(moved)]))
    st.maybe_rebalance()
    st.flush()
    st.warm_reads()
    reg = default_registry()

    def retraces():
        return sum(c.value for c in reg.series("lsm_retraces",
                                               table="retrace_tablets"))

    def shapes():
        return sum(g.value for g in reg.series("lsm_compiled_shapes",
                                               op="query"))

    warm_retraces, warm_shapes = retraces(), shapes()
    assert warm_retraces >= 1
    q_pool = rng.choice(rows, 4096).astype(np.int32)
    for size in (64, 256, 1024, 2048, 4096):
        hit_rows, _c, _v = st.query_rows(q_pool[:size])
        assert len(hit_rows) > 0
        assert retraces() == warm_retraces, \
            f"batch {size} retraced after split/move"
        assert shapes() == warm_shapes, \
            f"batch {size} grew the compile cache after split/move"
    # a FURTHER split + re-warm must also hold the line (values-only
    # routing updates: the compiled shapes are already resident)
    if st.split_tablet() is not None:
        st.flush()
        st.warm_reads()
        post_retraces, post_shapes = retraces(), shapes()
        st.query_rows(q_pool[:1024])
        assert retraces() == post_retraces
        assert shapes() == post_shapes
