"""A CPU rehearsal of every cell at a tiny scale: each run ends with a
well-formed result that the reference passes as correct."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common  # noqa: E402
from bench.tests.rehearse import with_pending  # noqa: E402

BENCH = with_pending()
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
FOUR_CHIPS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]


def in_subprocess(workload, tmp_path, *extra) -> dict:
    """Run a four-chip cell on four virtual CPU devices in a child."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-m", "bench.tests.rehearse", "--workload", workload,
         "--scale", "12", "--seconds", "1", "--state", str(tmp_path), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def well_formed(res: dict, workload: str, trace: bool) -> None:
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    dev = res["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    section = "per_layer" if trace else "end_to_end"
    want = {m["name"]: m["unit"]
            for m in common.cell_metrics(BENCH, workload, section)}
    assert set(res["metrics"]) <= set(want)
    if not trace:
        assert set(res["metrics"]) == set(want)
    assert res["metrics"]
    for name, m in res["metrics"].items():
        assert m["unit"] == want[name] and isinstance(m["value"], float)
    if trace:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        bd = res["breakdown"]
        assert 0 < len(bd["device_ops"]) <= 10
        assert 0 < len(bd["idle_gaps"]) <= 10
    assert all(set(c) == {"value", "limit"} for c in res["checks"].values())
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_one_chip_cell_rehearses(workload, trace, tmp_path):
    from bench.tests.rehearse import rehearse
    res = rehearse(workload, scale=11, seconds=1.0, trace=trace,
                   state=tmp_path)
    well_formed(res, workload, trace)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("workload", FOUR_CHIPS)
def test_four_chip_cell_rehearses(workload, trace, tmp_path):
    res = in_subprocess(workload, tmp_path, "--trace", str(int(trace)))
    assert res["device"]["count"] == 4
    well_formed(res, workload, trace)
