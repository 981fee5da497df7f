"""Share of the client's query latency spent decoding answers: the self
time of the dict.decode spans (the answer's ids turned back into strings
through the key and value dictionaries)."""
from bench.common import delta


def read(ctx):
    client = sum(ctx.record["latencies_s"])
    s = delta(ctx, "span_self_s", span="dict.decode")
    return 100.0 * s / client if client > 0 and s > 0 else None
