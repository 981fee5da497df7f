"""Integration: one real dry-run cell compiles on the production mesh and
produces coherent roofline terms (subprocess: needs 512 fake devices).

The full 40-cell x 2-mesh sweep is exercised by
``python -m repro.launch.dryrun --all --mesh both`` (EXPERIMENTS §Dry-run);
this test pins the machinery in CI at one cheap cell per mesh.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
from repro.launch.dryrun import run_cell   # sets XLA_FLAGS on import
import json

for multi in (False, True):
    rec = run_cell("smollm-135m", "decode_32k", multi, verbose=False)
    assert rec["chips"] == (512 if multi else 256)
    assert rec["flops_per_device"] > 0
    assert rec["collective_s"] >= 0
    assert rec["bottleneck"] in ("compute", "memory", "collective")
    assert rec["hbm_bytes_per_device"] < 16e9, "decode must fit one v5e"
print("DRYRUN-OK")
"""


@pytest.mark.slow
def test_dryrun_cell_both_meshes():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"  # never reach for a chip the parent holds
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DRYRUN-OK" in out.stdout


@pytest.mark.slow
def test_ingest_dryrun_single_pod():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"  # never reach for a chip the parent holds
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.ingest", "--dryrun",
         "--mesh", "single"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ingest dry-run" in out.stdout
    assert "all-to-all" in out.stdout  # the BatchWriter routing collective
