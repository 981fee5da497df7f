"""Share of the ingest window spent appending to the write-ahead log:
sum of wal_latency_s{op=append} of the pair's log (it holds any fsync)."""
from bench.common import delta


def read(ctx):
    log = f"{ctx.config['schema']}_Tedge"
    return 100.0 * delta(ctx, "wal_latency_s", "sum", log=log,
                         op="append") / ctx.window_s
