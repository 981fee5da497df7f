"""The compaction merge's share of its HBM roofline in the traced window.

Least bytes: the entries the compactions wrote (lsm_compact_entries, both
sides of the pair), written once and read once at 12 bytes an entry (int32
row, int32 column, float32 value). The inputs are at least as many entries
as the output, so this is a floor on the traffic and the share cannot pass
100% unless the device time misses part of the work. Time: the device time
of the compaction programs in the trace."""
from bench.common import delta

ENTRY_BYTES = 12


def read(ctx):
    t = f"{ctx.config['schema']}_Tedge"
    entries = delta(ctx, "lsm_compact_entries", table=(t, t + "@T"))
    busy = ctx.trace["program_s"].get("compact", 0.0)
    if entries <= 0 or busy <= 0:
        return None
    least_s = 2 * ENTRY_BYTES * entries / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / busy
