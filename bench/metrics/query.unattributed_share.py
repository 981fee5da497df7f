"""Share of the client's query latency that no program span covers: 1 -
the summed duration of the query roots (connector.query for row and column
queries, schema.degrees for degree lookups) over the summed latency."""
from bench.common import delta


def read(ctx):
    client = sum(ctx.record["latencies_s"])
    roots = ("connector.query", "schema.degrees")
    if client <= 0 or delta(ctx, "span_s", "count", span=roots) <= 0:
        return None
    s = delta(ctx, "span_s", "sum", span=roots)
    return 100.0 * (1.0 - s / client)
