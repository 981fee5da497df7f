"""Share of the ingest window spent outside the edge table's store calls:
the connector's batching, string dictionary, degree upkeep and the
benchmark's loop. 1 - (sum of db_op_latency_s{op=ingest} of Tedge) / window;
the store call holds the WAL append, both memtable appends and any flush."""
from bench.common import delta


def read(ctx):
    t = f"{ctx.config['schema']}_Tedge"
    inside = delta(ctx, "db_op_latency_s", "sum", table=t, op="ingest")
    return 100.0 * (1.0 - inside / ctx.window_s)
