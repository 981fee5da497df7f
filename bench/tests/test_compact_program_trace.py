"""The harness finds the compaction program in a trace: a small LSM
store that compacts inside a ``bench.window`` is recorded here on the
CPU, and the programs that ``bench/systems/connector.py:programs()``
lowers from the stacked state account for device time."""
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce  # noqa: E402


def _compaction_programs(runs) -> dict:
    """What ``Connector.programs()`` gives for an ingest cell whose store
    holds ``runs``."""
    from bench.systems.connector import Connector
    cell = SimpleNamespace(traffic={"op": "ingest"},
                           table=SimpleNamespace(
                               store=SimpleNamespace(_runs=runs)))
    return Connector.programs(cell)


def test_the_compaction_program_is_found_in_a_trace(tmp_path):
    import jax
    from bench.run import _options
    from repro.db.kvstore import ShardedTable

    t = ShardedTable("trace_compact", engine="lsm", combiner="last",
                     num_shards=4, capacity_per_shard=2048, batch_cap=64,
                     id_capacity=1 << 10, memtable_cap=64, l0_slots=2)
    runs = t._runs
    runs.warmup(t._mem_r, t._mem_c, t._mem_v)
    rng = np.random.default_rng(3)

    def put():
        # rows below id_capacity / 4: every entry lands on shard 0
        t.insert(rng.integers(0, 256, 64).astype(np.int32),
                 rng.integers(0, 8, 64).astype(np.int32),
                 np.ones(64, np.float32))
        t.flush()

    put()                  # compile the append and flush outside the trace
    before = t.engine_stats()["major_compactions"]
    with jax.profiler.trace(str(tmp_path), profiler_options=_options()):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(4):
                put()
            jax.block_until_ready(runs.levels[0]["rows"])
    assert t.engine_stats()["major_compactions"] > before
    red = trace_reduce.reduce_dir(tmp_path, 1, _compaction_programs(runs))
    assert 0 < red["program_s"]["compact"] <= red["busy_s"] + 1e-9
    assert any(n.startswith("jit_lsm_compact:")
               for n in red["op_s"]), sorted(red["op_s"])[:20]
