"""BENCHMARK.json and the files it names: found by name, well formed, and
each per-layer reader computes its number from a synthetic record."""
import re
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, trace_reduce  # noqa: E402
from bench.tests.rehearse import with_pending  # noqa: E402

BENCH = common.load_benchmark()
# BENCHMARK.json, and it with the cells not yet measured on the chip added
BOTH = pytest.mark.parametrize("bench", [BENCH, with_pending()],
                               ids=["measured", "with_pending"])
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_file_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[group]}) == len(BENCH[group])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@BOTH
def test_every_config_mix_and_metric_is_found_by_name(bench):
    used = set()
    for w in bench["workloads"]:
        cfg = common.load_json(common.config_file(bench, w["config"]))
        assert cfg["chips"] == w["chips"]
        assert common.traffic_file(w["traffic"]).is_file()
        used.add(w["config"])
        assert len(w["why"]) <= 200
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert common.load_json(ROOT / c["file"])["name"] == c["name"]
        assert c["reduced"] == common.load_json(ROOT / c["file"])["reduced"]
    for m in bench["per_layer"]:
        assert common.metric_file(m["name"]).is_file(), m["name"]
        assert hasattr(common.load_module(common.metric_file(m["name"])),
                       "read")


@BOTH
def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for w in bench["workloads"]:
        mine = {m["name"] for m in common.cell_metrics(bench, w["name"],
                                                       "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layers = common.cell_metrics(bench, w["name"], "per_layer")
        assert layers
        assert all(m["moves"] in mine for m in layers)


def _ctx(before, after, trace=None, record=None):
    return SimpleNamespace(
        window_s=10.0, config={"schema": "g500"}, before=before, after=after,
        record=record or {"latencies_s": [0.01] * 100,
                          "compact_s": [0.5, 0.5]},
        trace=trace or {"busy_s": 2.5, "window_s": 10.0,
                        "program_s": {"compact": 0.5},
                        "op_s": {"jit_shard_fn:all-to-all": 0.25,
                                 "jit_shard_fn:sort": 3.0}},
        peaks=trace_reduce.peaks("TPU v5 lite"))


def _hist(s):
    return {"count": 1, "sum": s}


SNAP0 = {"db_op_latency_s{op=ingest,table=g500_Tedge}": _hist(1.0),
         "db_op_latency_s{op=flush,table=g500_Tedge}": _hist(0.0),
         "db_op_latency_s{op=flush,table=g500_Tedge@T}": _hist(0.0),
         "db_op_latency_s{op=query,table=g500_Tedge}": _hist(0.0),
         "db_op_latency_s{op=query,table=g500_Tedge@T}": _hist(0.0),
         "wal_latency_s{log=g500_Tedge,op=append}": _hist(0.0),
         "db_ingest_entries{shard=0,table=g500_Tedge}": 0,
         "db_ingest_entries{shard=0,table=g500_Tedge@T}": 0,
         "lsm_flush_entries{table=g500_Tedge}": 0,
         "lsm_compact_entries{table=g500_Tedge}": 0,
         "lsm_fused_dispatches{table=g500_Tedge}": 0,
         "lsm_fused_dispatches{table=g500_Tedge@T}": 0,
         "lsm_scan_dispatches{table=g500_Tedge}": 0,
         "lsm_runs_probed{table=g500_Tedge}": 0}
SNAP1 = {"db_op_latency_s{op=ingest,table=g500_Tedge}": _hist(7.0),
         "db_op_latency_s{op=flush,table=g500_Tedge}": _hist(2.0),
         "db_op_latency_s{op=flush,table=g500_Tedge@T}": _hist(1.0),
         "db_op_latency_s{op=query,table=g500_Tedge}": _hist(0.1),
         "db_op_latency_s{op=query,table=g500_Tedge@T}": _hist(0.2),
         "wal_latency_s{log=g500_Tedge,op=append}": _hist(0.5),
         "db_ingest_entries{shard=0,table=g500_Tedge}": 1000,
         "db_ingest_entries{shard=0,table=g500_Tedge@T}": 1000,
         "lsm_flush_entries{table=g500_Tedge}": 1500,
         "lsm_compact_entries{table=g500_Tedge}": 8_190_000_000,
         "lsm_fused_dispatches{table=g500_Tedge}": 120,
         "lsm_fused_dispatches{table=g500_Tedge@T}": 80,
         "lsm_scan_dispatches{table=g500_Tedge}": 50,
         "lsm_runs_probed{table=g500_Tedge}": 600}

EXPECTED = {
    "ingest.host_share": 40.0, "ingest.wal_share": 5.0,
    "ingest.lsm_share": 30.0, "ingest.write_amp": 8_190_001_500 / 2000,
    # 2 x 12 B x 8.19e9 entries at 819 GB/s = 0.24 s over 0.5 s of device
    "ingest.compact_roofline": 48.0,
    "device_idle.ingest": 75.0, "device_idle.query": 75.0,
    "device_idle.spmd": 75.0,
    "query.host_share": 70.0, "query.dispatches_per_query": 2.5,
    "query.read_amp": 3.0, "spmd.all_to_all_share": 2.5,
    "spmd.compact_share": 10.0}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_a_synthetic_record(name):
    assert name in {m["name"] for m in with_pending()["per_layer"]}
    mod = common.load_module(common.metric_file(name))
    assert mod.read(_ctx(SNAP0, SNAP1)) == pytest.approx(EXPECTED[name])


def test_metrics_with_nothing_to_read_return_nothing():
    empty = _ctx({}, {}, trace={"busy_s": 1.0, "window_s": 2.0,
                                "program_s": {}, "op_s": {}},
                 record={"latencies_s": [], "compact_s": []})
    for name in ("ingest.write_amp", "ingest.compact_roofline",
                 "query.dispatches_per_query", "query.read_amp",
                 "spmd.all_to_all_share", "query.host_share"):
        assert common.load_module(common.metric_file(name)).read(empty) \
            is None, name


def test_the_device_check_refuses_the_cpu():
    from bench.run import check_devices
    cpu = [SimpleNamespace(platform="cpu", device_kind="cpu")]
    with pytest.raises(SystemExit):
        check_devices(cpu, 1)
    with pytest.raises(SystemExit):
        check_devices([], 1)
    tpu = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
    with pytest.raises(SystemExit):
        check_devices(tpu, 4)
    assert check_devices(tpu * 4, 4) == tpu * 4


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError):
        trace_reduce.peaks("TPU v9 imaginary")
    assert trace_reduce.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9

