"""Sorted runs probed per fused point dispatch (lsm_runs_probed over
lsm_fused_dispatches), over both sides of the pair."""
from bench.common import delta


def read(ctx):
    t = f"{ctx.config['schema']}_Tedge"
    tables = (t, t + "@T")
    d = delta(ctx, "lsm_fused_dispatches", table=tables)
    if d <= 0:
        return None
    return delta(ctx, "lsm_runs_probed", table=tables) / d
