"""Plain numpy reference of what the benchmark's cells must read back.

Independent of the program: nothing here imports ``repro``. It states the
semantics the deployments promise and nothing more:

* the connector's key dictionary interns each ingest batch's new row keys,
  then its new column keys, each in sorted order (``dictionary_order``);
* the edge table keeps one entry per distinct (row, col) whose value is the
  last one ingested (last-wins), and its transpose holds the same entries
  with the roles swapped (``EdgeReference``);
* the degree table counts every ingested edge, duplicates included;
* the SPMD store sums the values of duplicate keys (``sum_combine``) and
  each chip owns a contiguous quarter of the vertex space (``owner``).
"""
from __future__ import annotations

import numpy as np

_LOW = np.int64(0xFFFFFFFF)


def pack(r: np.ndarray, c: np.ndarray) -> np.ndarray:
    return r.astype(np.int64) << 32 | c.astype(np.int64)


def unpack(k: np.ndarray):
    return (k >> 32).astype(np.int64), (k & _LOW).astype(np.int64)


def dictionary_order(u: np.ndarray, v: np.ndarray, batch: int,
                     n_vertices: int) -> np.ndarray:
    """Vertex ids in the order the key dictionary assigns them ids."""
    seen = np.zeros(n_vertices, bool)
    out = []
    for a in range(0, len(u), batch):
        for part in (u[a:a + batch], v[a:a + batch]):
            uq = np.unique(part)
            new = uq[~seen[uq]]
            seen[new] = True
            out.append(new)
    return np.concatenate(out) if out else np.zeros(0, np.int64)


def owner(ids: np.ndarray, shards: int, id_capacity: int) -> np.ndarray:
    """Owning shard under a uniform range split of ``[0, id_capacity)``."""
    return np.minimum(ids.astype(np.int64) * shards // id_capacity,
                      shards - 1)


class EdgeReference:
    """Last-wins edge table, its transpose and the degree counts of the
    edges ``u[i] -> v[i]`` with values ``vals[i]``, in stream order."""

    def __init__(self, u: np.ndarray, v: np.ndarray, vals: np.ndarray,
                 n_vertices: int):
        keys = pack(u, v)
        order = np.argsort(keys, kind="stable")
        ks = keys[order]
        last = np.ones(len(ks), bool)
        last[:-1] = ks[1:] != ks[:-1]
        self.keys = ks[last]                       # sorted by (src, dst)
        self.src, self.dst = unpack(self.keys)
        self.val = np.asarray(vals, np.float32)[order][last]
        t_keys = pack(self.dst, self.src)
        t = np.argsort(t_keys)                     # sorted by (dst, src)
        self.t_keys = t_keys[t]
        self.t_dst = self.dst[t]
        self.t_val = self.val[t]
        self.out_deg = np.bincount(u, minlength=n_vertices).astype(np.int64)
        self.in_deg = np.bincount(v, minlength=n_vertices).astype(np.int64)

    def row(self, x: int):
        lo, hi = np.searchsorted(self.src, [x, x + 1])
        return self.keys[lo:hi], self.val[lo:hi]

    def col(self, y: int):
        """Entries of column ``y`` as (row, col) keys, sorted by row."""
        lo, hi = np.searchsorted(self.t_dst, [y, y + 1])
        r, c = unpack(self.t_keys[lo:hi])
        return pack(c, r), self.t_val[lo:hi]


def sum_combine(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray):
    """Distinct (row, col) keys, sorted, with the sum of their values."""
    keys = pack(rows, cols)
    uk, inv = np.unique(keys, return_inverse=True)
    sums = np.bincount(inv, weights=np.asarray(vals, np.float64),
                       minlength=len(uk))
    return uk, sums.astype(np.float32)


def mismatches(got_keys: np.ndarray, got_vals: np.ndarray,
               want_keys: np.ndarray, want_vals: np.ndarray) -> int:
    """Entries that differ between two key -> value tables: keys on one side
    only, keys held twice, and common keys whose values are not equal."""
    got_keys = np.asarray(got_keys, np.int64)
    uk, first = np.unique(got_keys, return_index=True)
    dup = len(got_keys) - len(uk)
    gv = np.asarray(got_vals)[first]
    _, gi, wi = np.intersect1d(uk, want_keys, assume_unique=True,
                               return_indices=True)
    only = (len(uk) - len(gi)) + (len(want_keys) - len(wi))
    differ = int(np.count_nonzero(gv[gi] != np.asarray(want_vals)[wi]))
    return int(dup + only + differ)
