"""Share of the ingest window spent in memtable flushes of both sides of
the pair: sum of db_op_latency_s{op=flush}. A flush holds the major
compactions it triggers, so those are not added again."""
from bench.common import delta


def read(ctx):
    t = f"{ctx.config['schema']}_Tedge"
    return 100.0 * delta(ctx, "db_op_latency_s", "sum", table=(t, t + "@T"),
                         op="flush") / ctx.window_s
