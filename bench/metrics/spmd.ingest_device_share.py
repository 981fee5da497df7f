"""Share of the traced window the chips spend running the SPMD ingest step
(exchange, sort, combine and L0 append): the device time of the operations
of ``jit_spmd_lsm_ingest``, a mean over chips, over the window."""

PROGRAM = "jit_spmd_lsm_ingest:"


def read(ctx):
    t = sum(v for k, v in ctx.trace["op_s"].items() if k.startswith(PROGRAM))
    if t <= 0:
        return None
    return 100.0 * t / ctx.trace["window_s"]
