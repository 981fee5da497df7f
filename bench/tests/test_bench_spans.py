"""The per-layer metrics that read the program's span totals, on synthetic
registries, and the program spans on a trace recorded here on the CPU."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, trace_reduce  # noqa: E402

BENCH = common.load_benchmark()


def _ctx(before, after, latencies=(0.01,) * 100):
    return SimpleNamespace(window_s=10.0, config={"schema": "g500"},
                           before=before, after=after,
                           record={"latencies_s": list(latencies),
                                   "compact_s": []})


def _hist(s, n):
    return {"count": n, "sum": s}


# a series that moves while no span metric reads it
OTHER = "db_op_latency_s{op=ingest,table=g500_Tedge}"
SNAP0 = {OTHER: _hist(1.0, 10),
         "span_self_s{span=dict.encode}": 1.0,
         "span_self_s{span=dict.journal}": 0.0,
         "span_self_s{span=degree.lookup}": 0.5,
         "span_self_s{span=degree.update}": 0.5,
         "span_s{span=major_compact}": _hist(1.0, 2),
         "span_s{span=schema.put}": _hist(1.0, 10),
         "span_self_s{span=connector.plan}": 0.0,
         "span_self_s{span=dict.decode}": 0.0,
         "span_self_s{span=assoc.build}": 0.0,
         "span_self_s{span=degree.read}": 0.0,
         "span_s{span=connector.query}": _hist(0.0, 0),
         "span_s{span=schema.degrees}": _hist(0.0, 0)}
SNAP1 = {OTHER: _hist(7.0, 410),
         "span_self_s{span=dict.encode}": 2.5,
         "span_self_s{span=dict.journal}": 0.5,
         "span_self_s{span=degree.lookup}": 1.0,
         "span_self_s{span=degree.update}": 1.5,
         "span_s{span=major_compact}": _hist(3.0, 5),
         "span_s{span=schema.put}": _hist(10.6, 410),
         "span_self_s{span=connector.plan}": 0.05,
         "span_self_s{span=dict.decode}": 0.3,
         "span_self_s{span=assoc.build}": 0.2,
         "span_self_s{span=degree.read}": 0.1,
         "span_s{span=connector.query}": _hist(0.7, 70),
         "span_s{span=schema.degrees}": _hist(0.28, 30)}

# span totals over a 10 s window and 1 s of client latency
EXPECTED = {
    "ingest.dict_share": 20.0, "ingest.degree_share": 15.0,
    "ingest.compact_share": 20.0, "ingest.unattributed_share": 4.0,
    "query.plan_share": 5.0, "query.decode_share": 30.0,
    "query.assemble_share": 20.0, "query.degree_read_share": 10.0,
    "query.unattributed_share": 2.0}


def _read(name, ctx):
    return common.load_module(common.metric_file(name)).read(ctx)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_metric_reads_a_synthetic_record(name):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert entry["source"] == "program_span" and entry["unit"] == "%"
    cells = {w["name"] for w in BENCH["workloads"]}
    assert len(entry["workloads"]) == 1 and entry["workloads"][0] in cells
    assert _read(name, _ctx(SNAP0, SNAP1)) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_metric_with_nothing_to_read_returns_nothing(name):
    assert _read(name, _ctx({}, {}, latencies=())) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_span_metric_is_silent_on_a_program_without_spans(name):
    """A program whose spans keep no totals (the parent of the change that
    added them) moves every other series: the reader finds nothing."""
    def strip(snap):
        return {k: v for k, v in snap.items() if not k.startswith("span_")}
    assert _read(name, _ctx(strip(SNAP0), strip(SNAP1))) is None


SPAN_NAMES = {"schema.put", "connector.put", "dict.encode", "dict.journal",
              "ingest", "wal.append", "flush", "major_compact",
              "degree.lookup", "degree.update", "connector.query",
              "connector.plan", "connector.read", "query.fused", "dispatch",
              "host_sync", "widen_retry", "dict.decode", "assoc.build"}


@pytest.fixture(scope="module")
def connector_trace(tmp_path_factory):
    """A tiny EdgeSchema ingest and one row query, recorded on the CPU
    inside ``bench.window`` with the harness's per-operation annotations;
    every program is compiled before the trace starts."""
    import jax
    import numpy as np
    from bench.run import _options
    from repro.db import EdgeSchema, dbsetup

    names = np.asarray([f"v{i:08d}" for i in range(256)], object)
    rng = np.random.default_rng(3)
    u, v = rng.integers(0, 256, (2, 1024))
    d = tmp_path_factory.mktemp("connector")
    server = dbsetup("trace", capacity_per_shard=1 << 12, num_shards=2,
                     id_capacity=1 << 10, batch_cap=256, memtable_cap=512,
                     wal_root=str(d / "wal"))
    E = EdgeSchema(server, "g")

    def put(a, b):
        E.put_triple(names[u[a:b]], names[v[a:b]],
                     np.arange(a, b, dtype=np.float32) + 1)

    put(0, 512)
    sel = "".join(f"{s}," for s in names[u[:4]])
    E[sel, :]
    jax.block_until_ready((E.deg.out_deg, E.deg.in_deg))
    with jax.profiler.trace(str(d / "trace"), profiler_options=_options()):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.put"):
                put(512, 1024)
            with jax.profiler.TraceAnnotation("bench.query.svr"):
                E[sel, :]
    E.delete()
    from jax.profiler import ProfileData
    files = sorted((d / "trace").glob("plugins/profile/*/*.xplane.pb"))
    return ProfileData.from_file(str(files[-1]))


def test_spans_land_on_the_host_line_of_the_window(connector_trace):
    host = {n for _, _, n in trace_reduce._host_line(connector_trace)}
    assert "bench.window" in host
    assert {"schema.put", "dict.encode", "degree.update", "connector.query",
            "dict.decode", "assoc.build"} <= host, sorted(host)


def test_idle_gaps_are_named_by_program_spans(connector_trace):
    red = trace_reduce.reduce(connector_trace, 1)
    labels = [n for n, _ in red["breakdown"]["idle_gaps"]]
    named = [n for n in labels
             if n.split(" / ")[-1] in SPAN_NAMES and " / " in n]
    assert named, labels
