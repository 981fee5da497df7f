"""Share of the client's query latency spent outside the pair's store read
calls (db_op_latency_s{op=query|scan} of Tedge and its transpose): selector
planning, key decoding, Assoc assembly and degree lookups."""
from bench.common import delta


def read(ctx):
    t = f"{ctx.config['schema']}_Tedge"
    client = sum(ctx.record["latencies_s"])
    if client <= 0:
        return None
    inside = delta(ctx, "db_op_latency_s", "sum", table=(t, t + "@T"),
                   op=("query", "scan"))
    return 100.0 * (1.0 - inside / client)
