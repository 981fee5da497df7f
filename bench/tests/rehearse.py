"""Test-only entry point: one benchmark cell at a tiny scale on the CPU.

    JAX_PLATFORMS=cpu python3 -m bench.tests.rehearse \
        --workload g500-s20-d4m2.ingest --scale 10 --seconds 1

It skips the harness's look for a TPU and runs the rest of a run on CPU
devices (four virtual ones for a four-chip cell, when the caller set
``--xla_force_host_platform_device_count=4``), with the cell's configuration
shrunk to ``--scale``. ``--fault`` breaks the path under test in one of the
ways the check has to catch; the result's ``correct`` must then be false.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common  # noqa: E402
from bench.tests import faults  # noqa: E402


def with_pending() -> dict:
    """``BENCHMARK.json`` with the entries of the cells built and rehearsed
    here but not yet measured on the chip (``pending.json``) added: its
    configurations, cells and per-layer metrics, and its cells in the
    ``workloads`` of the end-to-end metrics they report."""
    bench = common.load_benchmark()
    pending = common.load_json(Path(__file__).with_name("pending.json"))
    for key in ("configs", "workloads", "per_layer"):
        bench[key] = bench[key] + pending[key]
    for m in bench["end_to_end"]:
        if m["name"] in pending["end_to_end"]:
            m["workloads"] = m["workloads"] + pending["end_to_end"][m["name"]]
    return bench


def small_config(bench: dict, cell: dict, scale: int) -> dict:
    cfg = common.load_json(common.config_file(bench, cell["config"]))
    cfg["scale"] = scale
    return cfg


def small_traffic(cell: dict, cfg: dict) -> dict:
    """The cell's mix; an SPMD step shrinks so the stream has 16 steps."""
    traffic = common.load_json(common.traffic_file(cell["traffic"]))
    if "step_edges" in traffic:
        traffic["step_edges"] = (cfg["edge_factor"] << cfg["scale"]) // 16
    return traffic


def rehearse(workload: str, scale: int, seed: int = 1, seconds: float = 1.0,
             trace: bool = False, fault: str = None, state=None,
             traffic: dict = None) -> dict:
    import jax
    from bench import run
    bench = with_pending()
    cell = common.find_cell(bench, workload)
    devices = jax.devices("cpu")[:cell["chips"]]
    assert len(devices) == cell["chips"], "not enough CPU devices"
    cfg = small_config(bench, cell, scale)
    traffic = traffic or small_traffic(cell, cfg)
    from bench import trace_reduce
    v5e = trace_reduce.peaks("TPU v5 lite")
    with faults.planted(fault), faults.patched(
            trace_reduce, "peaks", lambda kind: v5e):
        # the CPU has no peaks of its own: the rehearsal borrows the v5e's
        return run.run_cell(bench, cell, seed, seconds, trace, devices,
                            cfg=cfg, state=state, traffic=traffic)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--state", default=None)
    args = ap.parse_args(argv)
    out = rehearse(args.workload, args.scale, args.seed, args.seconds,
                   bool(args.trace), args.fault,
                   Path(args.state) if args.state else None)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
