"""The control of the correctness check: the plain reference, computed in
bfloat16 (the precision below the float32 the configurations state), put in
the program's place. The check has to come out false on it.

    python3 bench/control.py --workload g500-s20-d4m2.ingest --ops 60 \
        --seeds 11 12 13

``--ops`` is how many operations of the window the control stands in for
(ingest batches, queries or SPMD steps): as many as a run acknowledges.
Each seed prints the numbers the check compares, beside their limits, as
one JSON line. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class Bfloat16:
    """Reference arithmetic in bfloat16 on the default device."""

    def __init__(self):
        import jax.numpy as jnp
        self.jnp = jnp

    def values(self, x):
        import numpy as np
        jnp = self.jnp
        return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                          .astype(jnp.float32))

    def count(self, ids, n: int):
        import numpy as np
        jnp = self.jnp
        acc = jnp.zeros((n,), jnp.bfloat16).at[jnp.asarray(ids)].add(
            jnp.bfloat16(1))
        return np.asarray(acc.astype(jnp.float32))

    def sums(self, inv, vals, n: int):
        import jax
        import numpy as np
        jnp = self.jnp
        acc = jax.ops.segment_sum(jnp.asarray(vals, jnp.bfloat16),
                                  jnp.asarray(inv), num_segments=n)
        return np.asarray(acc.astype(jnp.float32))


def control_checks(bench: dict, cell: dict, seed: int, ops: int,
                   cfg: dict = None, traffic: dict = None) -> list:
    """The check's numbers with the bfloat16 reference in the program's
    place, for one seed."""
    from bench import common
    from bench.run import log, make_cell
    cfg = cfg or common.load_json(common.config_file(bench, cell["config"]))
    traffic = traffic or common.load_json(
        common.traffic_file(cell["traffic"]))
    sut = make_cell(cfg, traffic, seed, None, None, ROOT / ".bench_run",
                    program=False)
    sut.control(ops, Bfloat16())
    log(f"[control] seed {seed}: reference in bfloat16 for {ops} operations")
    return sut.check()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--ops", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    jax.config.update("jax_platforms", "tpu")
    from bench import common
    from bench.run import check_devices
    bench = common.load_benchmark()
    cell = common.find_cell(bench, args.workload)
    dev = check_devices(jax.devices(), 1)[0]
    for seed in args.seeds:
        checks = control_checks(bench, cell, seed, args.ops)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "ops": args.ops, "device": dev.device_kind,
                          "correct": all(v <= lim for _, v, lim in checks),
                          "checks": {n: {"value": v, "limit": lim}
                                     for n, v, lim in checks}}), flush=True)


if __name__ == "__main__":
    main()
