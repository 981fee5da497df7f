"""Share of the traced window the chips spend in the all-to-all exchange of
the SPMD ingest step, averaged over the chips."""


def read(ctx):
    op_s = ctx.trace["op_s"]
    t = sum(v for k, v in op_s.items() if "all-to-all" in k.split(":")[-1])
    if t <= 0:
        return None
    return 100.0 * t / ctx.trace["window_s"]
