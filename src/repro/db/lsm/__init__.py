# LSM storage engine: leveled sorted runs + fused single-dispatch reads
# (bloom/fence gated) + WAL. The engine under every ShardedTable;
# see src/repro/db/README.md.
from .bloom import (bloom_build, bloom_maybe_contains,
                    bloom_maybe_contains_batch, fence_build, num_words,
                    suggest_hashes, theoretical_fp_rate)
from .engine import LSMRuns, combine_triples, plan_levels
from .manifest import recover, wal_path, write_snapshot
from .wal import WriteAheadLog

__all__ = [
    "LSMRuns", "WriteAheadLog", "bloom_build", "bloom_maybe_contains",
    "bloom_maybe_contains_batch", "combine_triples", "fence_build",
    "num_words", "plan_levels", "recover", "suggest_hashes",
    "theoretical_fp_rate", "wal_path", "write_snapshot",
]
