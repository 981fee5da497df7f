"""Share of the client's query latency spent in selector planning: the
self time of the connector.plan spans (resolve_selector_plan of each query
and each degree lookup)."""
from bench.common import delta


def read(ctx):
    client = sum(ctx.record["latencies_s"])
    s = delta(ctx, "span_self_s", span="connector.plan")
    return 100.0 * s / client if client > 0 and s > 0 else None
