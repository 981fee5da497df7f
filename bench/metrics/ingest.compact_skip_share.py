"""Share of the shards whose major-compaction merge was skipped in the
window, over both sides of the pair: lsm_compact_skipped_shards over
lsm_major_compactions times the store's shards. A shard skips when it
holds nothing to merge besides its target level. None where the program
has no such counter."""
from bench.common import delta

COUNTER = "lsm_compact_skipped_shards"


def read(ctx):
    if not any(k.split("{", 1)[0] == COUNTER for k in ctx.after):
        return None
    t = f"{ctx.config['schema']}_Tedge"
    tables = (t, t + "@T")
    compactions = delta(ctx, "lsm_major_compactions", table=tables)
    if compactions <= 0:
        return None
    skipped = delta(ctx, COUNTER, table=tables)
    return 100.0 * skipped / (compactions * ctx.config["store"]["num_shards"])
