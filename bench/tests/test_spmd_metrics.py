"""The four-chip cell's per-layer metrics: each reader computes its number
from a synthetic record, returns None when there is nothing to read, and
has its entry in BENCHMARK.json."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, trace_reduce  # noqa: E402

BENCH = common.load_benchmark()
CELL = "g500-s20-spmd4.ingest"
# 2 x 12 B x 4.095e9 entries a chip at 819 GB/s = 0.12 s over 0.5 s
ENTRIES_PER_CHIP = 4_095_000_000


def _ctx(before, after, op_s):
    return SimpleNamespace(
        window_s=20.0, config={"chips": 4}, before=before, after=after,
        record={"compact_s": []},
        trace={"busy_s": 8.0, "window_s": 10.0, "program_s": {},
               "op_s": op_s},
        peaks=trace_reduce.peaks("TPU v5 lite"))


def _hist(count, s):
    return {"count": count, "sum": s}


BEFORE = {"spmd_compact_entries{op=spmd_lsm_compact}": 1000,
          "span_s{span=spmd.lsm_ingest}": _hist(3, 0.5),
          "span_s{span=spmd.lsm_compact}": _hist(1, 0.1),
          "span_s{span=major_compact}": _hist(5, 9.0)}
AFTER = {"spmd_compact_entries{op=spmd_lsm_compact}":
         1000 + 4 * ENTRIES_PER_CHIP,
         "span_s{span=spmd.lsm_ingest}": _hist(60, 2.5),
         "span_s{span=spmd.lsm_compact}": _hist(16, 0.5),
         "span_s{span=major_compact}": _hist(9, 30.0)}
OP_S = {"jit_spmd_lsm_ingest:sort": 1.5,
        "jit_spmd_lsm_ingest:all-to-all": 0.5,
        "jit_spmd_lsm_compact:sort": 0.375,
        "jit_spmd_lsm_compact:fusion": 0.125,
        "jit_lsm_compact:sort": 4.0}

EXPECTED = {
    # 2.0 s of the ingest program's device time over the 10 s trace window
    "spmd.ingest_device_share": 20.0,
    "spmd.compact_roofline": 24.0,
    # (2.0 + 0.4) s inside the spmd.lsm_* spans over the 20 s window
    "spmd.dispatch_share": 12.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_spmd_metric_reads_a_synthetic_record(name):
    mod = common.load_module(common.metric_file(name))
    assert mod.read(_ctx(BEFORE, AFTER, OP_S)) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_spmd_metric_with_nothing_to_read_returns_nothing(name):
    mod = common.load_module(common.metric_file(name))
    assert mod.read(_ctx({}, {}, {})) is None
    # a program without the SPMD steps: the one-chip compaction alone
    assert mod.read(_ctx(BEFORE, BEFORE, {"jit_lsm_compact:sort": 4.0})) \
        is None


def test_the_roofline_needs_both_bytes_and_device_time():
    mod = common.load_module(common.metric_file("spmd.compact_roofline"))
    assert mod.read(_ctx(BEFORE, AFTER, {"jit_lsm_compact:sort": 4.0})) \
        is None
    assert mod.read(_ctx(BEFORE, BEFORE, OP_S)) is None


@pytest.mark.parametrize("name, source, better, layer", [
    ("device_idle.spmd", "device_trace", "lower", "device"),
    ("spmd.compact_share", "host_clock", "lower", "SPMD compaction"),
    ("spmd.ingest_device_share", "device_trace", "lower", "SPMD ingest step"),
    ("spmd.compact_roofline", "device_trace", "higher", "SPMD compaction"),
    ("spmd.dispatch_share", "program_span", "lower", "host dispatch"),
])
def test_the_cell_reports_its_layer_metrics(name, source, better, layer):
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert (entry["source"], entry["better"], entry["layer"]) == \
        (source, better, layer)
    assert entry["unit"] == "%" and entry["moves"] == "ingest_edges_per_s"
    assert entry["workloads"] == [CELL]
    assert name in {m["name"] for m in
                    common.cell_metrics(BENCH, CELL, "per_layer")}


def test_the_cell_is_measured_on_four_chips():
    cell = common.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("g500-s20-spmd4", "lockstep_ingest", 4)
    assert {m["name"] for m in common.cell_metrics(BENCH, CELL,
                                                   "end_to_end")} == \
        {"ingest_edges_per_s", "setup_s"}
    cfg = common.load_json(common.config_file(BENCH, "g500-s20-spmd4"))
    assert cfg["reduced"] == [] and cfg["chips"] == 4
    # the guarantee the cell keeps: no log, acknowledged when every chip
    # has completed the step
    assert "no write-ahead log" in cfg["durability"]
