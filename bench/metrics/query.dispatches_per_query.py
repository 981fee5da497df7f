"""Fused read dispatches (point and scan, widen retries included) per query
of the window, over both sides of the pair."""
from bench.common import delta


def read(ctx):
    t = f"{ctx.config['schema']}_Tedge"
    n = len(ctx.record["latencies_s"])
    if n == 0:
        return None
    tables = (t, t + "@T")
    return (delta(ctx, "lsm_fused_dispatches", table=tables)
            + delta(ctx, "lsm_scan_dispatches", table=tables)) / n
