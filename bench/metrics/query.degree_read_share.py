"""Share of the client's query latency spent reading degrees: the self
time of the degree.read spans (the out- and in-degree vectors pulled to
the host for each degree lookup)."""
from bench.common import delta


def read(ctx):
    client = sum(ctx.record["latencies_s"])
    s = delta(ctx, "span_self_s", span="degree.read")
    return 100.0 * s / client if client > 0 and s > 0 else None
