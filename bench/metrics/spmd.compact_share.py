"""Share of the window spent in SPMD compaction steps, each timed on the
host from its call to the blocking read of the new level sizes."""


def read(ctx):
    return 100.0 * sum(ctx.record["compact_s"]) / ctx.window_s
