"""Share of the ingest window spent in the degree table's upkeep: the self
time of the degree.lookup spans (the key-dictionary lookups of a batch's
rows and columns) and the degree.update spans (the host-to-device copies
and both degree_update dispatches)."""
from bench.common import delta


def read(ctx):
    s = delta(ctx, "span_self_s", span=("degree.lookup", "degree.update"))
    return 100.0 * s / ctx.window_s if s > 0 else None
