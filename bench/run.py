"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload g500-s20-d4m2.ingest --seed 7 \
        --seconds 30 --trace 0

The cell's configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<mix>.json``) are found through ``BENCHMARK.json``. The run
pins JAX to the TPU, keeps JAX's compile cache inside the checkout, makes
its data from ``--seed``, builds and warms the cell (set-up), measures for
``--seconds``, reads back what the window produced and compares it with the
plain reference in ``bench/reference.py``. With ``--trace 1`` the window
runs under the profiler and the run reports the cell's per-layer metrics
(``bench/metrics/<name>.py``) and a breakdown instead of the end-to-end
ones. The last line of standard output is the result as JSON; the numbers
compared with the reference, each beside its limit, are the last lines of
standard error and the last key of the result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common  # noqa: E402

STATE = ROOT / ".bench_run"        # per-run state, emptied by every run


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_devices(devices, chips: int):
    """The cell's chips, or exit: a measurement never falls back to the
    CPU and never runs on fewer chips than the cell asks for."""
    if not devices or devices[0].platform != "tpu":
        kind = devices[0].platform if devices else "none"
        raise SystemExit(f"bench: no TPU found (platform {kind})")
    if len(devices) < chips:
        raise SystemExit(f"bench: {chips} chips asked for, "
                         f"{len(devices)} found")
    return list(devices[:chips])


def percentile(xs, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(xs, float), q))


def end_to_end(name: str, rec: dict, setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "ingest_edges_per_s":
        return rec["edges"] / rec["span_s"]
    if name == "query_edges_per_s":
        return rec["entries"] / rec["span_s"]
    if name == "query_p50_ms":
        return 1e3 * percentile(rec["latencies_s"], 50)
    if name == "query_p95_ms":
        return 1e3 * percentile(rec["latencies_s"], 95)
    raise KeyError(f"no end-to-end metric {name!r}")


def make_cell(cfg: dict, traffic: dict, seed: int, devices, span, state,
              program: bool = True):
    """The system under test, chosen by the configuration's ``system``.
    With ``program=False`` only the data and the reference are made."""
    if cfg["system"] == "connector":
        from bench.systems.connector import Connector
        return Connector(cfg, traffic, seed, state / "connector", log, span,
                         program)
    if cfg["system"] == "spmd":
        from bench.systems.spmd import Spmd
        return Spmd(cfg, traffic, seed, devices, log, span, program)
    raise SystemExit(f"bench: unknown system {cfg['system']!r}")


def run_cell(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
             devices, cfg: dict = None, t_start: float = T_START,
             state: Path = None, traffic: dict = None) -> dict:
    """Set up, measure, check; returns the result object. ``cfg`` and
    ``traffic`` replace the cell's files and ``state`` the run's directory
    (the CPU rehearsal in the tests shrinks them)."""
    import jax
    from repro.obs import default_registry

    cfg = cfg or common.load_json(common.config_file(bench, cell["config"]))
    traffic = traffic or common.load_json(
        common.traffic_file(cell["traffic"]))
    state = state or STATE
    misses = {"n": 0, "hits": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_misses":
            misses["n"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            misses["hits"] += 1

    jax.monitoring.register_event_listener(on_event)
    span = ((lambda name: jax.profiler.TraceAnnotation(name)) if trace
            else (lambda name: contextlib.nullcontext()))
    shutil.rmtree(state, ignore_errors=True)
    state.mkdir(parents=True)
    sut = make_cell(cfg, traffic, seed, devices, span, state)
    gc.collect()

    reg = default_registry()
    before = reg.snapshot()
    m0, h0 = misses["n"], misses["hits"]
    trace_dir = state / "trace"
    setup_s = time.perf_counter() - t_start
    with (jax.profiler.trace(str(trace_dir), profiler_options=_options())
          if trace else contextlib.nullcontext()):
        with span("bench.window"):
            rec = sut.window(seconds)
    after = reg.snapshot()
    retraces = (common.total(after, "lsm_retraces")
                - common.total(before, "lsm_retraces"))
    log(f"[window] {rec['attempted']} operations, {rec['failed']} failed, "
        f"{rec['span_s']:.6f} s to the last one's end; compile-cache misses "
        f"in the window "
        f"{misses['n'] - m0}, hits {misses['hits'] - h0}; lsm_retraces "
        f"{retraces:g}")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}

    metrics, breakdown = {}, None
    names = common.cell_metrics(bench, cell["name"],
                                "per_layer" if trace else "end_to_end")
    if trace:
        from bench import trace_reduce
        t0 = time.perf_counter()
        red = trace_reduce.reduce_dir(trace_dir, len(devices),
                                      sut.programs())
        log(f"[trace] reduced in {time.perf_counter() - t0:.3f} s")
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = red["breakdown"]
        ctx = SimpleNamespace(window_s=rec["span_s"], record=rec,
                              config=cfg,
                              before=before, after=after, trace=red,
                              peaks=trace_reduce.peaks(dev.device_kind))
        for m in names:
            val = common.load_module(common.metric_file(m["name"])).read(ctx)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        for m in names:
            metrics[m["name"]] = {"value": end_to_end(m["name"], rec, setup_s),
                                  "unit": m["unit"]}

    sut.collect()
    gc.collect()
    t0 = time.perf_counter()
    checks = sut.check()
    log(f"[check] reference compared in {time.perf_counter() - t0:.3f} s")
    result = {"correct": all(v <= lim for _, v, lim in checks),
              "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    for n, v, lim in checks:
        log(f"check {n} {v} limit {lim}")
    return result


def _options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = common.load_benchmark()
    cell = common.find_cell(bench, args.workload)
    import jax
    jax.config.update("jax_platforms", "tpu")   # no silent CPU fallback
    from repro.compile_cache import enable_compile_cache
    devices = check_devices(jax.devices(), cell["chips"])
    enable_compile_cache()
    result = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                      devices)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
