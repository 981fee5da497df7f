"""Graph500 unpermuted Kronecker edge generator, kept with the benchmark.

A copy of the generator the program ships (``repro.data.graph500``), so the
yardstick cannot move with the program; it draws blocks of edges in
parallel. Scale ``s`` and edge factor ``e`` give ``e * 2**s`` edges over
``2**s`` vertices with the Graph500 initiator A, B, C = 0.57, 0.19, 0.19 and
no relabelling pass (D4M.jl, arXiv:1808.05138 section IV). Vertex keys are fixed-width strings ``v%08d`` taken from one
name table, so string order is numeric order.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

A, B, C = 0.57, 0.19, 0.19
NAME_WIDTH = 9          # "v" + 8 digits
CHUNK = 1 << 21         # edges per independently seeded block


def _kronecker_block(scale: int, m: int, seed) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ij = np.zeros((2, m), dtype=np.int64)
    ab = A + B
    c_norm = C / (1.0 - ab)
    a_norm = A / ab
    for ib in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > (c_norm * ii_bit + a_norm * ~ii_bit)
        ij[0] += (1 << ib) * ii_bit
        ij[1] += (1 << ib) * jj_bit
    return ij.astype(np.int32)


def kronecker_edges(scale: int, edge_factor: int, seed):
    """(start, end) int32 vertex ids of one Graph500 Kronecker graph: the
    program's generator, run on blocks of ``CHUNK`` edges that each draw
    from their own stream of the seed, in parallel threads."""
    m = edge_factor * (1 << scale)
    seeds = np.random.SeedSequence(seed).spawn(-(-m // CHUNK))
    sizes = [min(CHUNK, m - i * CHUNK) for i in range(len(seeds))]
    with ThreadPoolExecutor(min(8, len(seeds))) as pool:
        parts = list(pool.map(lambda a: _kronecker_block(scale, *a),
                              zip(sizes, seeds)))
    ij = np.concatenate(parts, axis=1)
    return ij[0], ij[1]


def name_table(scale: int) -> np.ndarray:
    """Object array of the ``2**scale`` vertex keys, indexed by vertex id."""
    return np.asarray([f"v{i:08d}" for i in range(1 << scale)], dtype=object)


def dyadic_weights(n: int, seed: int) -> np.ndarray:
    """float32 edge values ``1 + k/256``, k uniform in [0, 256): sums of a
    few thousand of them are exact in float32 in any order, and odd k are
    not representable in bfloat16."""
    k = np.random.default_rng(seed).integers(0, 256, n)
    return (1.0 + k / 256.0).astype(np.float32)
