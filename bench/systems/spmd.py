"""Cells that drive the SPMD store (``repro.db.spmd``): k ingestors and k
tablet servers on a k-chip mesh, one shard per chip.

Traffic ``op`` ``ingest``: the ingestors step in lockstep; each step takes
``step_edges`` integer-id edges from every ingestor's own Graph500 graph,
exchanges them by owner chip and appends one sorted L0 run per chip. Before
a step that would meet a full L0 stack the chips compact. Every step ends
in a host read of the stack heights, which acknowledges it.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from bench import graph500, reference

I32_MAX = np.iinfo(np.int32).max


class Spmd:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices, log,
                 span, program: bool = True):
        self.log, self.span = log, span
        S = self.S = cfg["ingestors"]
        scale, ef = cfg["scale"], cfg["edge_factor"]
        self.slots, self.bcap = cfg["l0_slots"], traffic["step_edges"]
        self.id_cap = 1 << scale
        t0 = time.perf_counter()
        with ThreadPoolExecutor(S) as pool:
            graphs = list(pool.map(
                lambda i: graph500.kronecker_edges(scale, ef, [seed, i]),
                range(S)))
        vals = [graph500.dyadic_weights(len(g[0]), [seed, S + i])
                for i, g in enumerate(graphs)]
        per = len(graphs[0][0])
        self.steps = -(-per // self.bcap)
        shape = (self.steps, S, self.bcap)
        self.br = np.full(shape, I32_MAX, np.int32)
        self.bc = np.full(shape, I32_MAX, np.int32)
        self.bv = np.zeros(shape, np.float32)
        for i, (u, v) in enumerate(graphs):
            pad = self.steps * self.bcap - per
            self.br[:, i] = np.pad(u, (0, pad), constant_values=I32_MAX
                                   ).reshape(self.steps, self.bcap)
            self.bc[:, i] = np.pad(v, (0, pad), constant_values=I32_MAX
                                   ).reshape(self.steps, self.bcap)
            self.bv[:, i] = np.pad(vals[i], (0, pad)).reshape(self.steps,
                                                              self.bcap)
        # level capacity: the fullest chip's share of the whole stream,
        # duplicates included, with the configuration's headroom
        own = np.bincount(reference.owner(
            np.concatenate([g[0] for g in graphs]), S, self.id_cap),
            minlength=S)
        self.cap = max(1 << 12, int(own.max() * cfg["skew_headroom"]))
        log(f"[setup] {S} ingestors x scale {scale}: {S * per} edges in "
            f"{self.steps} steps of {S} x {self.bcap}; edges per chip "
            f"{own.tolist()}; level capacity {self.cap} "
            f"({time.perf_counter() - t0:.3f} s)")
        self.t = 0
        self.compacted_at = []     # steps before which the chips compacted
        if program:
            self._build(cfg, devices)

    def _build(self, cfg: dict, devices) -> None:
        import jax
        from jax.sharding import AxisType, NamedSharding
        from jax.sharding import PartitionSpec as P
        from repro.db.kvstore import Tablet
        from repro.db.spmd import (L0Stack, l0_stacked_empty,
                                   make_spmd_lsm_compact_step,
                                   make_spmd_lsm_ingest_step, stacked_empty)
        self.jax = jax
        S = self.S
        assert len(devices) == S, "one chip per ingestor"
        mesh = jax.make_mesh((S,), ("data",), devices=devices,
                             axis_types=(AxisType.Auto,))
        sh1 = NamedSharding(mesh, P("data"))
        self.sh2 = sh2 = NamedSharding(mesh, P("data", None))
        sh3 = NamedSharding(mesh, P("data", None, None))
        empty_l0 = jax.jit(
            lambda: l0_stacked_empty(S, self.slots, S * self.bcap),
            out_shardings=L0Stack(rows=sh3, cols=sh3, vals=sh3, k=sh1))
        empty_level = jax.jit(lambda: stacked_empty(S, self.cap),
                              out_shardings=Tablet(rows=sh2, cols=sh2,
                                                   vals=sh2, n=sh1))
        self.ingest = make_spmd_lsm_ingest_step(mesh, "data", S, self.id_cap,
                                                combiner=cfg["combiner"])
        self.compact = make_spmd_lsm_compact_step(mesh, "data",
                                                  combiner=cfg["combiner"])
        # warm both programs on throwaway state of the cell's shapes
        t0 = time.perf_counter()
        l0, level = empty_l0(), empty_level()
        l0 = self.ingest(l0, *self._batch(0))
        l0, level = self.compact(l0, level)
        jax.block_until_ready((l0, level))
        np.asarray(l0.k), np.asarray(level.n)
        del l0, level
        self.l0, self.level = empty_l0(), empty_level()
        jax.block_until_ready((self.l0, self.level))
        self.log(f"[setup] ingest and compaction steps warmed "
                 f"{time.perf_counter() - t0:.3f} s")

    def _batch(self, t: int):
        put = self.jax.device_put
        return (put(self.br[t], self.sh2), put(self.bc[t], self.sh2),
                put(self.bv[t], self.sh2))

    def window(self, seconds: float) -> dict:
        k_max, compact_s = int(np.asarray(self.l0.k).max()), []
        start = self.t
        failed = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline and self.t < self.steps:
            try:
                if k_max == self.slots:
                    tc = time.perf_counter()
                    with self.span("bench.compact"):
                        self.l0, self.level = self.compact(self.l0,
                                                           self.level)
                        n_max = int(np.asarray(self.level.n).max())
                    compact_s.append(time.perf_counter() - tc)
                    if n_max > self.cap:
                        raise OverflowError(f"level overflow {n_max}")
                    self.compacted_at.append(self.t)
                with self.span("bench.step"):
                    self.l0 = self.ingest(self.l0, *self._batch(self.t))
                    k_max = int(np.asarray(self.l0.k).max())
            except Exception as e:  # noqa: BLE001 -- counted, then stop
                failed += 1
                self.log(f"[window] SPMD step {self.t} failed: {e!r}")
                break
            self.t += 1
        # as in the connector cells: every step acknowledged, over the time
        # until the step in flight at the deadline has completed
        self.jax.block_until_ready((self.l0, self.level))
        span = time.perf_counter() - t0
        if self.t >= self.steps:
            self.log(f"[window] the stream ran out after {span:.3f} s")
        return {"attempted": self.t - start + failed, "failed": failed,
                "edges": int(np.count_nonzero(self.br[start:self.t]
                                              != I32_MAX)),
                "span_s": span,
                "compact_s": compact_s}

    def programs(self) -> dict:
        return {}

    # ------------------------------------------------------------- check
    def collect(self) -> None:
        """Every chip's level run and L0 stack, read back to the host; the
        device state is then released."""
        def per_chip(x):
            out = [None] * self.S
            for sh in x.addressable_shards:
                out[sh.index[0].start or 0] = np.asarray(sh.data)[0]
            return out
        n = per_chip(self.level.n)
        self.got_level = [tuple(a[:int(n[s])] for a in (r, c, v)) for s, (r, c, v)
                          in enumerate(zip(per_chip(self.level.rows),
                                           per_chip(self.level.cols),
                                           per_chip(self.level.vals)))]
        self.got_l0 = list(zip(per_chip(self.l0.rows), per_chip(self.l0.cols),
                               per_chip(self.l0.vals), per_chip(self.l0.k)))
        self.l0 = self.level = None

    def check(self) -> list:
        S, T = self.S, self.t
        last = self.compacted_at[-1] if self.compacted_at else 0
        r = self.br[:last].reshape(-1)
        keep = r != I32_MAX
        r, c = r[keep], self.bc[:last].reshape(-1)[keep]
        v = self.bv[:last].reshape(-1)[keep]
        own = reference.owner(r, S, self.id_cap)
        level_bad = l0_bad = 0
        for s in range(S):
            wk, wv = reference.sum_combine(r[own == s], c[own == s],
                                           v[own == s])
            gr, gc, gv = self.got_level[s]
            gk = reference.pack(gr, gc)
            level_bad += reference.mismatches(gk, gv, wk, wv)
            level_bad += int(np.count_nonzero(np.diff(gk) <= 0))
        for s in range(S):
            rows, cols, vals, k = self.got_l0[s]
            l0_bad += abs(int(k) - (T - last))
            for j in range(self.slots):
                t = last + j
                live = rows[j] != I32_MAX
                gk = reference.pack(rows[j][live], cols[j][live])
                if t < T:
                    br, bc, bv = (x[t].reshape(-1) for x in
                                  (self.br, self.bc, self.bv))
                    m = (br != I32_MAX) & (reference.owner(br, S, self.id_cap)
                                           == s)
                    wk, wv = reference.sum_combine(br[m], bc[m], bv[m])
                else:
                    wk, wv = np.zeros(0, np.int64), np.zeros(0, np.float32)
                l0_bad += reference.mismatches(gk, vals[j][live], wk, wv)
                l0_bad += int(np.count_nonzero(np.diff(gk) <= 0))
        return [("level_mismatch", int(level_bad), 0),
                ("l0_mismatch", int(l0_bad), 0)]

    # ----------------------------------------------------------- control
    def control(self, ops: int, lower) -> None:
        """Put the reference, summed through ``lower`` (a lower-precision
        accumulation), in the program's place for ``ops`` steps, with the
        compactions where the window's loop would make them."""
        S, T = self.S, min(ops, self.steps)
        self.t = T
        self.compacted_at = list(range(self.slots, T, self.slots))
        last = self.compacted_at[-1] if self.compacted_at else 0

        def combined(br, bc, bv):
            keys = reference.pack(br, bc)
            uk, inv = np.unique(keys, return_inverse=True)
            r, c = reference.unpack(uk)
            return r, c, lower.sums(inv, bv, len(uk))

        r, c, v = (x[:last].reshape(-1) for x in (self.br, self.bc, self.bv))
        keep = r != I32_MAX
        r, c, v = r[keep], c[keep], v[keep]
        own = reference.owner(r, S, self.id_cap)
        self.got_level = [combined(r[own == s], c[own == s], v[own == s])
                          for s in range(S)]
        run = S * self.bcap
        self.got_l0 = []
        for s in range(S):
            rows = np.full((self.slots, run), I32_MAX, np.int64)
            cols = np.full((self.slots, run), I32_MAX, np.int64)
            vals = np.zeros((self.slots, run), np.float32)
            for j, t in enumerate(range(last, T)):
                br, bc, bv = (x[t].reshape(-1) for x in
                              (self.br, self.bc, self.bv))
                m = (br != I32_MAX) & (reference.owner(br, S, self.id_cap)
                                       == s)
                rr, cc, vv = combined(br[m], bc[m], bv[m])
                rows[j, :len(rr)], cols[j, :len(rr)], vals[j, :len(rr)] = \
                    rr, cc, vv
            self.got_l0.append((rows, cols, vals, T - last))
