"""Share of the ingest window spent in the string dictionaries: the self
time of the dict.encode spans (interning each batch's row, column and
value strings) and the dict.journal spans (journaling the new ones)."""
from bench.common import delta


def read(ctx):
    s = delta(ctx, "span_self_s", span=("dict.encode", "dict.journal"))
    return 100.0 * s / ctx.window_s if s > 0 else None
