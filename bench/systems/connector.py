"""Cells that drive the D4M connector: ``EdgeSchema`` (``Tedge``, its
transpose ``TedgeT`` and ``TedgeDeg``) behind ``dbsetup``, fed Graph500
edges as strings one character-budget batch at a time.

Traffic ``op``:

* ``ingest`` -- one closed-loop writer streams the graph into an empty
  schema (after ``prefill_edges`` in set-up), one batch per ``put_triple``.
* ``query`` -- set-up ingests ``prefill_edges`` (all of them by default),
  then one closed-loop client sends the mix's classes of row, column and
  degree queries in equal seeded shares.
"""
from __future__ import annotations

import json
import shutil
import time

import numpy as np

from bench import graph500, reference
from bench.common import positional_mismatch, read_wal


class Connector:
    def __init__(self, cfg: dict, traffic: dict, seed: int, workdir, log,
                 span, program: bool = True):
        self.log, self.span = log, span
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.workdir = workdir
        scale = cfg["scale"]
        t0 = time.perf_counter()
        self.u, self.v = graph500.kronecker_edges(scale, cfg["edge_factor"],
                                                  seed)
        self.m = len(self.u)
        self.names = graph500.name_table(scale)
        self.rows, self.cols = self.names[self.u], self.names[self.v]
        # each edge's value is its 1-based place in the stream: exact in
        # float32 up to 2**24, and it makes last-wins observable
        self.vals = np.arange(1, self.m + 1, dtype=np.float32)
        self.batch = cfg["batch_chars"] // (2 * graph500.NAME_WIDTH + 8)
        prefill = traffic.get("prefill_edges", 0)
        self.prefill = self.m if prefill == "all" else int(prefill)
        self.pos = 0
        if traffic["op"] == "query":
            self._choose_queries()
        log(f"[setup] scale={scale} edges={self.m} batch={self.batch} edges;"
            f" data and reference {time.perf_counter() - t0:.3f} s")
        if program:
            self._build()

    # ------------------------------------------------------------ set-up
    def _build(self) -> None:
        import jax
        from repro.db import EdgeSchema, dbsetup
        self.jax = jax
        store = dict(self.cfg["store"])
        n = len(self.names)
        order = reference.dictionary_order(self.u, self.v, self.batch, n)
        kid = np.empty(n, np.int64)
        kid[order] = np.arange(len(order))
        S, idc = store["num_shards"], store["id_capacity"]
        counts = np.maximum(
            np.bincount(reference.owner(kid[self.u], S, idc), minlength=S),
            np.bincount(reference.owner(kid[self.v], S, idc), minlength=S))
        cap = max(1 << 12, int(counts.max() * self.cfg["skew_headroom"]))
        self.log(f"[setup] shard_edges={counts.tolist()} "
                 f"capacity_per_shard={cap}")
        shutil.rmtree(self.workdir, ignore_errors=True)
        self.workdir.mkdir(parents=True)
        self._warm_write_path(dbsetup, EdgeSchema, store, cap)
        self.server = dbsetup(self.cfg["instance"], capacity_per_shard=cap,
                              wal_root=str(self.workdir / "wal"), **store)
        self.E = EdgeSchema(self.server, self.cfg["schema"])
        self.table = self.E.pair.table
        if self.traffic["op"] == "ingest":   # a prefill warms what it meets
            t0 = time.perf_counter()
            self.table.store.warmup()
            self._warm_compaction_updates()
            self.log(f"[setup] store warmup {time.perf_counter() - t0:.3f} s")
        if self.prefill:
            t0 = time.perf_counter()
            while self.pos < self.prefill:
                self._put(min(self.batch, self.prefill - self.pos))
            self._sync()
            self.log(f"[setup] prefill {self.pos} edges "
                     f"{time.perf_counter() - t0:.3f} s")
        if self.traffic["op"] == "query":
            t0 = time.perf_counter()
            self.table.store.warm_reads()
            for q in self.pool:
                self._ask(q)
            self.log(f"[setup] {len(self.pool)} pool queries warmed in "
                     f"{time.perf_counter() - t0:.3f} s")

    def _warm_write_path(self, dbsetup, EdgeSchema, store, cap):
        """Compile the per-batch programs (memtable append, degree update,
        flush, a first compaction) on a small throwaway schema whose
        memtable and first level have the cell's shapes; the store's own
        ``warmup`` compiles the compactions of every level."""
        t0 = time.perf_counter()
        mem = max(store["batch_cap"] * 4, min(cap, 1 << 18))
        server = dbsetup("warm", capacity_per_shard=mem, **store)
        E = EdgeSchema(server, "warm")
        # a full batch per flush until a compaction, then the stream's
        # last, partial batch
        sizes = [self.batch] * (store["l0_slots"] + 1) + [self.m % self.batch]
        a = 0
        for n in sizes:
            E.put_triple(self.rows[a:a + n], self.cols[a:a + n],
                         self.vals[a:a + n])
            E.pair.table.store.flush()
            a += n
        self.jax.block_until_ready((E.deg.out_deg, E.deg.in_deg))
        E.delete()
        self.log(f"[setup] write-path warm-up {time.perf_counter() - t0:.3f}"
                 " s")

    def _warm_compaction_updates(self) -> None:
        """Run on the still empty store the eager array updates a major
        compaction makes at each depth (the merged level selected per
        shard; L0 and the shallower levels cleared): the store's
        ``warmup`` compiles the merges but not these, and the window
        reaches every depth."""
        import jax.numpy as jnp
        runs = self.table.store._runs
        runs.clear_shard(0)
        m = jnp.asarray(np.ones(runs.S, bool))
        out = [jnp.where(m.reshape((-1,) + (1,) * (x.ndim - 1)), x, x)
               for lv in runs.levels
               for x in (lv["rows"], lv["cols"], lv["vals"], lv["bloom"],
                         lv["fence"])]
        self.jax.block_until_ready((out, runs.l0_rows))

    def _choose_queries(self) -> None:
        """A pool of queries per class, chosen from the reference's degree
        counts after the prefill (never from the program)."""
        P = self.prefill
        self.ref = ref = reference.EdgeReference(
            self.u[:P], self.v[:P], self.vals[:P], len(self.names))
        rng = np.random.default_rng([self.seed, 1])
        per = self.traffic["pool_per_class"]
        self.pool = []
        for cls in self.traffic["classes"]:
            kind, k = cls["kind"], cls["vertices"]
            if kind == "degree":
                deg = ref.out_deg + ref.in_deg
                picks = [[nearest(deg, t, 1, rng)[0] for t in cls["degrees"]]
                         for _ in range(per)]
            else:
                deg = ref.out_deg if kind == "row" else ref.in_deg
                near = nearest(deg, cls["degree"], per * k, rng)
                picks = [sorted(near[i * k:(i + 1) * k]) for i in range(per)]
            for ids in picks:
                sel = "".join(f"{self.names[i]}," for i in ids)
                self.pool.append((cls["name"], kind, np.asarray(ids), sel))
        n_cls = len(self.traffic["classes"])
        order = np.concatenate([rng.permutation(n_cls) for _ in range(4096)])
        member = rng.integers(0, per, len(order))
        self.sequence = (order * per + member).tolist()

    # ------------------------------------------------------------ window
    def _put(self, n: int) -> None:
        a, b = self.pos, self.pos + n
        self.E.put_triple(self.rows[a:b], self.cols[a:b], self.vals[a:b])
        self.pos = b

    def _sync(self) -> None:
        for x in self.jax.live_arrays():
            x.block_until_ready()

    def _ask(self, q):
        _, kind, _, sel = q
        if kind == "row":
            return self.E[sel, :]
        if kind == "col":
            return self.E[:, sel]
        return self.E.deg.degrees(sel)

    def window(self, seconds: float) -> dict:
        if self.traffic["op"] == "query":
            return self._query_window(seconds)
        # the window closes when the batch in flight at the deadline has
        # returned and every array is ready: the rate is all the edges
        # acknowledged over all that time
        start_pos = self.pos
        attempted = failed = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline and self.pos < self.m:
            attempted += 1
            with self.span("bench.put"):
                try:
                    self._put(min(self.batch, self.m - self.pos))
                except Exception as e:  # noqa: BLE001 -- counted, then stop
                    failed += 1
                    self.log(f"[window] put_triple failed: {e!r}")
                    break
        with self.span("bench.sync"):
            self._sync()
        span = time.perf_counter() - t0
        if self.pos >= self.m:
            self.log(f"[window] the stream ran out after {span:.3f} s")
        return {"attempted": attempted, "failed": failed,
                "edges": self.pos - start_pos, "span_s": span}

    def _query_window(self, seconds: float) -> dict:
        lat, answers = [], []
        entries = failed = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        for qi in self.sequence:
            if time.perf_counter() >= deadline:
                break
            q = self.pool[qi]
            t = time.perf_counter()
            with self.span("bench.query." + q[0]):
                try:
                    a = self._ask(q)
                except Exception as e:  # noqa: BLE001 -- counted, then stop
                    failed += 1
                    self.log(f"[window] query {q[0]} failed: {e!r}")
                    break
            lat.append(time.perf_counter() - t)
            entries += a.nnz()
            answers.append((qi, a))
        elapsed = time.perf_counter() - t0
        self.answers = answers
        return {"attempted": len(lat) + failed, "failed": failed,
                "latencies_s": lat, "entries": int(entries),
                "span_s": elapsed}

    def programs(self) -> dict:
        """The store's compaction programs, one per level depth, as the
        trace reduction finds them (the edge table and its transpose run
        the same compiled programs); an ingest mix reads them."""
        if self.traffic["op"] != "ingest":
            return {}
        from repro.db.lsm.engine import _compact_fn
        from bench.trace_reduce import program
        runs = self.table.store._runs
        found = {"ids": set(), "sigs": set()}
        for d, lv in enumerate(runs.levels):
            lvls = tuple((runs.levels[i]["rows"], runs.levels[i]["cols"],
                          runs.levels[i]["vals"]) for i in range(d, -1, -1))
            fn = _compact_fn(runs.combiner, runs.use_pallas, lv["cap"],
                             lv["words"], lv["block"], lv["hashes"])
            p = program(fn.lower(runs.l0_rows, runs.l0_cols, runs.l0_vals,
                                 lvls).compile())
            found["ids"] |= p["ids"]
            found["sigs"] |= p["sigs"]
        return {"compact": found}

    # ------------------------------------------------------------- check
    def collect(self) -> None:
        """Read back what the window produced, then free the program's
        state, so the reference runs on a released device."""
        if self.traffic["op"] == "ingest":
            store = self.table.store
            wal = self.workdir / "wal"
            self.got = {
                "keys": self.server.keydict.decode(
                    np.arange(len(self.server.keydict))),
                "journal": [json.loads(line) for line in
                            (wal / "keydict.log").read_text().splitlines()
                            if line],
                "tedge": store.scan(),
                "tedget": store.t_store.scan(),
                "out_deg": np.asarray(self.E.deg.out_deg),
                "in_deg": np.asarray(self.E.deg.in_deg),
                "wal": read_wal(wal / f"{self.cfg['schema']}_Tedge"
                                / "wal.log")}
        else:
            self.got = [(qi, a.triples()) for qi, a in self.answers]
            self.answers = None
        self.E.delete()
        self.server = self.E = self.table = None

    def check(self) -> list:
        if self.traffic["op"] == "query":
            return [("query_mismatch", self._check_answers(), 0)]
        return self._check_ingest()

    def _acked(self):
        N = self.pos
        return self.u[:N], self.v[:N], self.vals[:N]

    def _check_ingest(self) -> list:
        n = len(self.names)
        u, v, vals = self._acked()
        order = reference.dictionary_order(u, v, self.batch, n)
        ref = reference.EdgeReference(u, v, vals, n)
        got = self.got
        vid = vertex_ids(got["keys"])
        keydict = (positional_mismatch(vid, order)
                   + positional_mismatch(vertex_ids(got["journal"]), order))

        def table(trip, want_keys, want_vals):
            r, c, x = trip
            keys = reference.pack(vid[r], vid[c])
            return reference.mismatches(keys, x, want_keys, want_vals)

        tedge = table(got["tedge"], ref.keys, ref.val)
        tedget = table(got["tedget"], ref.t_keys, ref.t_val)
        deg = 0
        for name, want in (("out_deg", ref.out_deg), ("in_deg", ref.in_deg)):
            g = np.zeros(n, np.float64)
            g[vid] = got[name][:len(vid)]
            deg += int(np.count_nonzero(g != want))
            deg += int(np.count_nonzero(got[name][len(vid):]))
        kid = np.empty(n, np.int64)
        kid[order] = np.arange(len(order))
        wr, wc, wv, frames, pairs = got["wal"]
        wal = (positional_mismatch(wr, kid[u]) + positional_mismatch(wc, kid[v])
               + positional_mismatch(wv, vals) + (frames - pairs))
        return [("keydict_mismatch", keydict, 0), ("tedge_mismatch", tedge, 0),
                ("tedget_mismatch", tedget, 0), ("tedgedeg_mismatch", deg, 0),
                ("wal_mismatch", wal, 0)]

    def _check_answers(self) -> int:
        """Entries that differ from the reference over every answer of the
        window; repeats of one query are held to its first answer."""
        ref = self.ref
        first, bad = {}, 0
        for qi, (r, c, x) in self.got:
            if qi in first:
                fr, fc, fx = first[qi]
                same = (len(r) == len(fr) and np.array_equal(r, fr)
                        and np.array_equal(c, fc) and np.array_equal(x, fx))
                bad += 0 if same else max(len(r), len(fr), 1)
                continue
            first[qi] = (r, c, x)
            _, kind, ids, _ = self.pool[qi]
            if kind == "degree":
                got = {(int(s[1:]), k): float(val) for s, k, val in
                       zip(r, c, np.asarray(x, np.float64))}
                want = {}
                for i in ids.tolist():
                    for k, arr in (("OutDeg", ref.out_deg),
                                   ("InDeg", ref.in_deg)):
                        if arr[i]:
                            want[(i, k)] = float(arr[i])
                bad += sum(got.get(k) != w for k, w in want.items())
                bad += sum(k not in want for k in got)
                continue
            keys = reference.pack(vertex_ids(r), vertex_ids(c))
            parts = [ref.row(i) if kind == "row" else ref.col(i)
                     for i in ids.tolist()]
            bad += reference.mismatches(
                keys, np.asarray(x, np.float32),
                np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))
        return int(bad)

    # ----------------------------------------------------------- control
    def control(self, ops: int, lower) -> None:
        """Put the reference, computed through ``lower`` (a lower-precision
        round trip of float32 values), in the program's place for ``ops``
        operations: ``ops`` ingest batches, or the first ``ops`` queries of
        the window's sequence."""
        n = len(self.names)
        if self.traffic["op"] == "query":
            ref = self.ref
            out_deg, in_deg = (lower.count(self.u[:self.prefill], n),
                               lower.count(self.v[:self.prefill], n))
            self.got = []
            for qi in self.sequence[:ops]:
                _, kind, ids, _ = self.pool[qi]
                if kind == "degree":
                    trip = ([], [], [])
                    for i in ids.tolist():
                        for k, arr in (("OutDeg", out_deg), ("InDeg", in_deg)):
                            if arr[i]:
                                trip[0].append(self.names[i])
                                trip[1].append(k)
                                trip[2].append(arr[i])
                    self.got.append((qi, tuple(np.asarray(t) for t in trip)))
                    continue
                parts = [ref.row(i) if kind == "row" else ref.col(i)
                         for i in ids.tolist()]
                r, c = reference.unpack(np.concatenate([p[0] for p in parts]))
                x = lower.values(np.concatenate([p[1] for p in parts]))
                self.got.append((qi, (self.names[r], self.names[c], x)))
            return
        self.pos = min(self.m, ops * self.batch)
        u, v, vals = self._acked()
        order = reference.dictionary_order(u, v, self.batch, n)
        kid = np.empty(n, np.int64)
        kid[order] = np.arange(len(order))
        ref = reference.EdgeReference(u, v, vals, n)
        r, c = reference.unpack(ref.keys)
        tr, tc = reference.unpack(ref.t_keys)
        low = lower.values(vals)
        self.got = {
            "keys": self.names[order], "journal": list(self.names[order]),
            "tedge": (kid[r], kid[c], lower.values(ref.val)),
            "tedget": (kid[tr], kid[tc], lower.values(ref.t_val)),
            "out_deg": lower.count(kid[u], n), "in_deg": lower.count(kid[v], n),
            "wal": (kid[u], kid[v], low, 1, 1)}


def vertex_ids(keys) -> np.ndarray:
    """Vertex ids of ``v%08d`` keys."""
    return np.fromiter((int(s[1:]) for s in keys), np.int64, len(keys))


def nearest(deg: np.ndarray, target: float, k: int, rng) -> list:
    """``k`` distinct vertices whose degree lies nearest ``target`` on a log
    scale, ties broken in a seeded order."""
    have = np.flatnonzero(deg > 0)
    dist = np.abs(np.log(deg[have] / float(target)))
    order = np.lexsort((rng.random(len(have)), dist))
    return have[order[:k]].tolist()
