"""``ingest.compact_skip_share`` on synthetic registries: the share of
the shards whose compaction merge was skipped, over both sides of the
pair, and nothing where the program has no such counter."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common  # noqa: E402

BENCH = common.load_benchmark()
NAME = "ingest.compact_skip_share"
CELL = "g500-s20-d4m2.ingest"


def _read(before, after):
    ctx = SimpleNamespace(window_s=10.0, before=before, after=after,
                          config={"schema": "g500",
                                  "store": {"num_shards": 4}})
    return common.load_module(common.metric_file(NAME)).read(ctx)


def _snap(compactions, skipped, t_compactions, t_skipped):
    return {"lsm_major_compactions{table=g500_Tedge}": compactions,
            "lsm_major_compactions{table=g500_Tedge@T}": t_compactions,
            "lsm_compact_skipped_shards{table=g500_Tedge}": skipped,
            "lsm_compact_skipped_shards{table=g500_Tedge@T}": t_skipped,
            # another table's compactions are not the pair's
            "lsm_major_compactions{table=g500_TedgeDeg}": 7 * compactions,
            "lsm_compact_skipped_shards{table=g500_TedgeDeg}": 0}


@pytest.mark.parametrize("after, share", [
    # three of four shards skipped in every compaction of both sides
    (_snap(12, 3 + 3 * 10, 9, 3 + 3 * 8), 75.0),
    # every shard merged
    (_snap(12, 3, 9, 3), 0.0),
    # one side skips three shards of four, the other none: 30 of 72
    (_snap(12, 3 + 30, 9, 3), 100.0 * 30 / 72),
])
def test_the_share_reads_a_synthetic_registry(after, share):
    assert _read(_snap(2, 3, 1, 3), after) == pytest.approx(share)


def test_without_the_counter_or_a_compaction_it_reads_nothing():
    def no_counter(snap):
        return {k: v for k, v in snap.items()
                if not k.startswith("lsm_compact_skipped_shards")}
    # the program before the counter existed
    assert _read(no_counter(_snap(2, 0, 1, 0)),
                 no_counter(_snap(12, 0, 9, 0))) is None
    assert _read({}, {}) is None
    # no compaction in the window
    assert _read(_snap(2, 3, 1, 3), _snap(2, 3, 1, 3)) is None


def test_the_cell_reports_the_share():
    entry = {m["name"]: m for m in BENCH["per_layer"]}[NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "LSM engine",
                     "moves": "ingest_edges_per_s", "workloads": [CELL]}
    assert NAME in {m["name"] for m in
                    common.cell_metrics(BENCH, CELL, "per_layer")}
