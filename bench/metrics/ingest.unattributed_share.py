"""Share of the ingest window that no program span covers: 1 - the summed
duration of the schema.put spans (each EdgeSchema.put_triple, the root of
a batch's spans) over the window. What is left is the benchmark's loop and
the closing wait for the device."""
from bench.common import delta


def read(ctx):
    if delta(ctx, "span_s", "count", span="schema.put") <= 0:
        return None
    s = delta(ctx, "span_s", "sum", span="schema.put")
    return 100.0 * (1.0 - s / ctx.window_s)
