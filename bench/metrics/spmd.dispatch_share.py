"""Share of the window the host spends inside the SPMD store's step calls:
the summed duration of the ``spmd.lsm_*`` program spans, which cover each
step's dispatch (the harness's read of the result waits for the device
outside them)."""
from bench.common import delta

SPANS = ("spmd.lsm_ingest", "spmd.lsm_compact", "spmd.lsm_pair_ingest",
         "spmd.lsm_query", "spmd.lsm_scan")


def read(ctx):
    if delta(ctx, "span_s", "count", span=SPANS) <= 0:
        return None
    return 100.0 * delta(ctx, "span_s", "sum", span=SPANS) / ctx.window_s
