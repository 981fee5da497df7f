"""Entries written into sorted runs (by flushes and compactions) per entry
ingested, over both sides of the pair, in the window."""
from bench.common import delta


def read(ctx):
    t = f"{ctx.config['schema']}_Tedge"
    tables = (t, t + "@T")
    ingested = delta(ctx, "db_ingest_entries", table=tables)
    if ingested <= 0:
        return None
    written = (delta(ctx, "lsm_flush_entries", table=tables)
               + delta(ctx, "lsm_compact_entries", table=tables))
    return written / ingested
