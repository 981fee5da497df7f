"""The mesh compaction's share of its HBM roofline in the traced window.

Least bytes: the entries the compactions' merges read on each chip
(spmd_compact_entries over the chips: every L0 slot and the whole level
run, padding included), read once and written once at 12 bytes an entry
(int32 row, int32 column, float32 value), as ingest.compact_roofline
counts. Time: the device time of the operations of
``jit_spmd_lsm_compact``, a mean over chips."""
from bench.common import delta

ENTRY_BYTES = 12
PROGRAM = "jit_spmd_lsm_compact:"


def read(ctx):
    entries = delta(ctx, "spmd_compact_entries") / ctx.config["chips"]
    busy = sum(v for k, v in ctx.trace["op_s"].items()
               if k.startswith(PROGRAM))
    if entries <= 0 or busy <= 0:
        return None
    least_s = 2 * ENTRY_BYTES * entries / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / busy
