"""Share of the client's query latency spent building answers: the self
time of the assoc.build spans (the Assoc construction, which runs
np.unique over the answer's row and column strings)."""
from bench.common import delta


def read(ctx):
    client = sum(ctx.record["latencies_s"])
    s = delta(ctx, "span_self_s", span="assoc.build")
    return 100.0 * s / client if client > 0 and s > 0 else None
