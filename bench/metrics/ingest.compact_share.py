"""Share of the ingest window spent in major compactions of both sides of
the pair: the summed duration of the major_compact spans, the compaction
part of ingest.lsm_share."""
from bench.common import delta


def read(ctx):
    if delta(ctx, "span_s", "count", span="major_compact") <= 0:
        return None
    s = delta(ctx, "span_s", "sum", span="major_compact")
    return 100.0 * s / ctx.window_s
