"""The check fails what it must: each fault planted under the timed path,
and the control (the reference computed in bfloat16 in the program's
place), turn ``correct`` false, at a tiny scale on the CPU."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common  # noqa: E402
from bench.tests.rehearse import (small_config, small_traffic,  # noqa: E402
                                  with_pending)
from bench.tests.test_bench_rehearsal import in_subprocess  # noqa: E402

BENCH = with_pending()
INGEST, QUERY = "g500-s20-d4m2.ingest", "g500-s20-d4m2.query"
SPMD = "g500-s20-spmd4.ingest"


def failed_check(res: dict) -> None:
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload, fault", [
    (INGEST, "state_unchanged"), (INGEST, "half_batch"),
    (QUERY, "answer_altered")])
def test_fault_on_one_chip_is_caught(workload, fault, tmp_path):
    from bench.tests.rehearse import rehearse
    failed_check(rehearse(workload, scale=11, seconds=1.0, fault=fault,
                          state=tmp_path))


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_fault_on_four_chips_is_caught(fault, tmp_path):
    failed_check(in_subprocess(SPMD, tmp_path, "--fault", fault))


@pytest.mark.parametrize("workload, ops", [(INGEST, 3), (QUERY, 400),
                                           (SPMD, 10)])
def test_bfloat16_control_is_not_correct(workload, ops):
    from bench.control import control_checks
    cell = common.find_cell(BENCH, workload)
    cfg = small_config(BENCH, cell, 14)
    checks = control_checks(BENCH, cell, 3, ops, cfg=cfg,
                            traffic=small_traffic(cell, cfg))
    assert any(v > lim for _, v, lim in checks), checks


class Float32:
    """The reference's own float32 arithmetic, for the control's twin."""

    def values(self, x):
        import numpy as np
        return np.asarray(x, np.float32)

    def count(self, ids, n):
        import numpy as np
        return np.bincount(ids, minlength=n).astype(np.float32)

    def sums(self, inv, vals, n):
        import numpy as np
        return np.bincount(inv, weights=np.asarray(vals, np.float64),
                           minlength=n).astype(np.float32)


@pytest.mark.parametrize("workload, ops", [(INGEST, 3), (QUERY, 400),
                                           (SPMD, 10)])
def test_the_control_path_passes_in_float32(workload, ops):
    """The same substitution without the lower precision is correct, so the
    control fails for its precision alone."""
    from bench.run import log, make_cell
    cell = common.find_cell(BENCH, workload)
    cfg = small_config(BENCH, cell, 14)
    sut = make_cell(cfg, small_traffic(cell, cfg), 3, None, None,
                    ROOT / ".bench_run", program=False)
    sut.control(ops, Float32())
    checks = sut.check()
    log(f"[control twin] {checks}")
    assert all(v <= lim for _, v, lim in checks), checks
