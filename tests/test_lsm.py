"""LSM storage engine tests: combiner semantics across flush/compaction
boundaries, bloom/fence read path (no flush on reads), WAL crash recovery,
k-way Pallas merge, connector delete semantics, SPMD L0 ingest."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.db import DBserver, dbsetup, delete
from repro.db.kvstore import ShardedTable
from repro.db.lsm import WriteAheadLog, recover
from repro.db.lsm.bloom import bloom_build, bloom_maybe_contains
from repro.db.lsm.engine import plan_levels
from repro.kernels.common import I32_MAX
from repro.kernels.merge_rank import kway_merge
from repro.kernels.merge_rank.ref import merge_sorted_ref

COMBINE = {
    "last": lambda old, new: new,
    "sum": lambda old, new: old + new,
    "min": min,
    "max": max,
}


def oracle_apply(oracle, rows, cols, vals, combiner):
    for r, c, v in zip(rows, cols, vals):
        k = (int(r), int(c))
        oracle[k] = COMBINE[combiner](oracle[k], float(v)) if k in oracle \
            else float(v)


def tiny_lsm(combiner="last", **kw):
    cfg = dict(num_shards=2, capacity_per_shard=4096, batch_cap=512,
               id_capacity=1 << 10, combiner=combiner, memtable_cap=64,
               engine="lsm")
    cfg.update(kw)
    return ShardedTable("lsm_t", **cfg)


# ---------------------------------------------- combiners across boundaries
@pytest.mark.parametrize("combiner", ["last", "sum", "min", "max"])
def test_combiner_across_flush_and_compaction(combiner):
    """Duplicate keys land in the memtable, several L0 runs, AND deeper
    levels; the combined result must match a sequential oracle exactly."""
    st = tiny_lsm(combiner)
    rng = np.random.default_rng(7)
    oracle = {}
    for _ in range(40):  # 64-entry memtable -> many flushes + compactions
        n = 48
        r = rng.integers(0, 200, n).astype(np.int32)
        c = rng.integers(0, 4, n).astype(np.int32)
        v = rng.normal(size=n).astype(np.float32)
        st.insert(r, c, v)
        oracle_apply(oracle, r, c, v, combiner)
    stats = st.engine_stats()
    assert stats["flushes"] > 4 and stats["major_compactions"] >= 1
    sr, sc, sv = st.scan()
    got = {(int(a), int(b)): float(x) for a, b, x in zip(sr, sc, sv)}
    assert set(got) == set(oracle)
    for k in oracle:
        assert got[k] == pytest.approx(oracle[k], rel=1e-5), (combiner, k)
    # scan output is sorted lex by (row, col) within each shard range
    assert np.all(np.lexsort((sc, sr)) == np.arange(len(sr)))


def test_point_queries_never_flush():
    st = tiny_lsm("sum")
    rng = np.random.default_rng(3)
    oracle = {}
    for _ in range(10):
        r = rng.integers(0, 1 << 10, 40).astype(np.int32)
        c = rng.integers(0, 4, 40).astype(np.int32)
        v = rng.normal(size=40).astype(np.float32)
        st.insert(r, c, v)
        oracle_apply(oracle, r, c, v, "sum")
    assert st._mem_n.max() > 0, "test needs a non-empty memtable"
    mem_before = st._mem_n.copy()
    l0_before = st.engine_stats()["l0_used"]
    q = np.unique([k[0] for k in oracle])[:64].astype(np.int32)
    qr, qc, qv = st.query_rows(q)
    assert (st._mem_n == mem_before).all() and \
        st.engine_stats()["l0_used"] == l0_before, "read triggered a flush"
    want = {k: v for k, v in oracle.items() if k[0] in set(q.tolist())}
    got = {(int(a), int(b)): float(x) for a, b, x in zip(qr, qc, qv)}
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-5)


def test_query_widens_past_max_return_lsm():
    st = tiny_lsm("last", memtable_cap=2048, num_shards=1)
    n = 600
    st.insert(np.full(n, 7, np.int32), np.arange(n, dtype=np.int32),
              np.ones(n, np.float32))
    st.flush()  # run-resident (fence path), not just memtable
    r, c, v = st.query_rows(np.asarray([7], np.int32), max_return=256)
    assert len(c) == n and set(c.tolist()) == set(range(n))


def test_bloom_skips_absent_rows():
    st = tiny_lsm("last")
    rng = np.random.default_rng(5)
    # two key populations far apart; flush everything into runs
    st.insert(rng.integers(0, 100, 60).astype(np.int32),
              rng.integers(0, 4, 60).astype(np.int32),
              rng.normal(size=60).astype(np.float32))
    st.flush()
    st.insert(rng.integers(400, 500, 60).astype(np.int32),
              rng.integers(0, 4, 60).astype(np.int32),
              rng.normal(size=60).astype(np.float32))
    st.flush()
    before = dict(st.engine_stats())
    r, c, v = st.query_rows(np.asarray([250, 251, 252], np.int32))
    after = st.engine_stats()
    assert len(r) == 0
    assert after["runs_skipped"] > before["runs_skipped"], \
        "bloom/range filters should skip runs for absent keys"


def test_bloom_unit_no_false_negatives():
    rng = np.random.default_rng(0)
    keys = rng.choice(1 << 20, 500, replace=False).astype(np.int32)
    cap = 1024
    rows = np.full(cap, I32_MAX, np.int32)
    rows[:500] = np.sort(keys)
    words = np.asarray(bloom_build(rows, 256))
    present = np.asarray(bloom_maybe_contains(words, keys))
    assert present.all(), "bloom false negative"
    absent = np.setdiff1d(rng.choice(1 << 20, 2000), keys)[:1000]
    fp = np.asarray(bloom_maybe_contains(words, absent.astype(np.int32)))
    assert fp.mean() < 0.25, f"false-positive rate {fp.mean():.2f} too high"


def test_plan_levels_geometry():
    caps = plan_levels(1 << 19, 1 << 14, l0_slots=4, fanout=4)
    assert caps[-1] >= 1 << 19  # deepest holds advertised capacity
    assert all(b > a for a, b in zip(caps, caps[1:]))
    assert caps[-1] >= 4 * (1 << 14) + sum(caps[:-1])  # merge always fits


def test_lsm_overflow_backpressure():
    st = ShardedTable("tiny", num_shards=1, capacity_per_shard=64,
                      batch_cap=64, id_capacity=1 << 10, engine="lsm")
    with pytest.raises(OverflowError):
        for i in range(4):
            st.insert(np.arange(64, dtype=np.int32) + 64 * i,
                      np.zeros(64, np.int32), np.ones(64, np.float32))
            st.flush()


# --------------------------------------------------------- k-way merge op
@pytest.mark.parametrize("use_pallas", [True, False])
def test_kway_merge_matches_ref(use_pallas):
    rng = np.random.default_rng(9)
    runs = []
    for n, cap in [(100, 128), (50, 256), (200, 256), (10, 64), (77, 128)]:
        r = np.full(cap, I32_MAX, np.int32)
        c = np.full(cap, I32_MAX, np.int32)
        v = np.zeros(cap, np.float32)
        rr = np.sort(rng.integers(0, 500, n)).astype(np.int32)
        cc = rng.integers(0, 8, n).astype(np.int32)
        order = np.lexsort((cc, rr))
        r[:n], c[:n] = rr[order], cc[order]
        v[:n] = rng.normal(size=n)
        runs.append((r, c, v))
    # Pallas path (interpret on CPU) or the one-sort XLA path vs the
    # pairwise-reduced jnp reference
    mr, mc, mv = kway_merge([tuple(map(np.asarray, run)) for run in runs],
                            use_pallas=use_pallas, interpret=True)
    er, ec, ev = runs[0]
    for run in runs[1:]:
        er, ec, ev = merge_sorted_ref(er, ec, ev, *run)
    np.testing.assert_array_equal(np.asarray(mr), np.asarray(er))
    np.testing.assert_array_equal(np.asarray(mc), np.asarray(ec))
    total = sum((np.asarray(r) != I32_MAX).sum() for r, _, _ in runs)
    valid = np.asarray(mr) != I32_MAX
    assert valid.sum() == total
    # age order within equal-key groups: values of older runs come first
    np.testing.assert_allclose(np.asarray(mv)[valid], np.asarray(ev)[valid],
                               rtol=1e-6)


# ------------------------------------------------------------- durability
def test_wal_roundtrip_and_torn_tail(tmp_path):
    path = str(tmp_path / "wal.log")
    wal = WriteAheadLog(path)
    batches = []
    rng = np.random.default_rng(1)
    for _ in range(5):
        b = (rng.integers(0, 100, 20).astype(np.int32),
             rng.integers(0, 100, 20).astype(np.int32),
             rng.normal(size=20).astype(np.float32))
        wal.append(*b)
        batches.append(b)
    wal.close()
    got = list(WriteAheadLog.replay(path))
    assert len(got) == 5
    for (gr, gc, gv), (br, bc, bv) in zip(got, batches):
        np.testing.assert_array_equal(gr, br)
        np.testing.assert_array_equal(gc, bc)
        np.testing.assert_array_equal(gv, bv)
    # torn tail: chop the last record mid-payload -> replay drops ONLY it
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 37)
    got = list(WriteAheadLog.replay(path))
    assert len(got) == 4


def test_crash_recovery_snapshot_plus_wal(tmp_path):
    d = str(tmp_path / "db")
    st = ShardedTable("w", num_shards=2, capacity_per_shard=2048,
                      batch_cap=256, id_capacity=1 << 10, combiner="sum",
                      memtable_cap=64, engine="lsm", wal_dir=d)
    rng = np.random.default_rng(2)
    mk = lambda: (rng.integers(0, 1 << 10, 40).astype(np.int32),
                  rng.integers(0, 4, 40).astype(np.int32),
                  rng.normal(size=40).astype(np.float32))
    for _ in range(6):
        st.insert(*mk())
    st.checkpoint()
    for _ in range(4):  # post-snapshot writes live only in the WAL
        st.insert(*mk())
    want = st.scan()
    del st  # crash: all device state lost
    rec = recover(d)
    got = rec.scan()
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(got[2], want[2], rtol=1e-5)
    # recovered table stays writable + durable
    rec.insert(np.asarray([3], np.int32), np.asarray([1], np.int32),
               np.asarray([1.0], np.float32))
    assert rec.nnz() >= len(got[0])


def test_recovery_without_checkpoint_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        recover(str(tmp_path / "nope"))


def test_recovery_truncates_torn_tail_so_new_writes_survive(tmp_path):
    """Double-crash: recovery after a torn tail must truncate it, or every
    batch journaled after recovery is appended past the corrupt bytes and
    lost to the NEXT recovery."""
    d = str(tmp_path / "db")
    st = ShardedTable("w", num_shards=1, capacity_per_shard=2048,
                      batch_cap=256, id_capacity=1 << 10, combiner="last",
                      memtable_cap=64, engine="lsm", wal_dir=d)
    st.insert(np.asarray([1, 2], np.int32), np.asarray([0, 0], np.int32),
              np.asarray([1.0, 2.0], np.float32))
    st.checkpoint()
    st.insert(np.asarray([3], np.int32), np.asarray([0], np.int32),
              np.asarray([3.0], np.float32))
    del st
    wal = os.path.join(d, "wal.log")
    with open(wal, "r+b") as f:  # crash tore the last record mid-payload
        f.truncate(os.path.getsize(wal) - 5)
    rec = recover(d)  # row 3's torn record is (correctly) gone
    rec.insert(np.asarray([4], np.int32), np.asarray([0], np.int32),
               np.asarray([4.0], np.float32))
    del rec  # second crash, before any checkpoint
    rec2 = recover(d)
    rows = set(rec2.scan()[0].tolist())
    assert rows == {1, 2, 4}, rows  # row 4 must survive the second crash


def test_duplicate_query_ids_return_duplicate_results():
    """query_rows([x, x]) yields x's entries twice."""
    st = ShardedTable("dup", num_shards=1, capacity_per_shard=256,
                      batch_cap=64, id_capacity=1 << 10)
    st.insert(np.asarray([7, 7], np.int32), np.asarray([1, 2], np.int32),
              np.asarray([1.0, 2.0], np.float32))
    r, c, v = st.query_rows(np.asarray([7, 7], np.int32))
    assert len(r) == 4, len(r)


# ------------------------------------------------------- connector delete
def test_delete_poisons_handle_and_frees_store():
    DB = dbsetup("deldb", dict(num_shards=2, capacity_per_shard=2048,
                               batch_cap=512, id_capacity=1 << 12))
    T = DB["t_del"]
    T.put_triple(np.asarray(["a", "b"], object), np.asarray(["x", "y"], object),
                 np.asarray([1.0, 2.0]))
    assert T.nnz() == 2
    delete(T)
    assert "t_del" not in DB.ls()
    with pytest.raises(RuntimeError):
        T.put_triple(np.asarray(["c"], object), np.asarray(["z"], object),
                     np.asarray([3.0]))
    with pytest.raises(RuntimeError):
        T["a,", :]
    with pytest.raises(RuntimeError):
        T.nnz()
    # re-binding the name creates a fresh, usable table
    T2 = DB["t_del"]
    assert T2.nnz() == 0


# ------------------------------------------------------------ SPMD L0 path
SPMD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding

from repro.db.spmd import (l0_stacked_empty, make_spmd_lsm_ingest_step,
                           make_spmd_lsm_compact_step,
                           make_spmd_lsm_query_step,
                           make_spmd_lsm_scan_step, stacked_empty)
from repro.kernels.common import I32_MAX

S, BCAP, IDCAP, SLOTS, CAP = 8, 128, 1 << 12, 3, 1 << 13
mesh = jax.make_mesh((S,), ("data",))
ingest = make_spmd_lsm_ingest_step(mesh, "data", S, IDCAP, combiner="sum")
compact = make_spmd_lsm_compact_step(mesh, "data", combiner="sum")
query = make_spmd_lsm_query_step(mesh, "data", combiner="sum",
                                 max_return=64)

l0 = l0_stacked_empty(S, SLOTS, S * BCAP)
level = stacked_empty(S, CAP)
sh3 = NamedSharding(mesh, P("data", None, None))
sh2 = NamedSharding(mesh, P("data", None))
sh1 = NamedSharding(mesh, P("data"))
l0 = jax.device_put(l0, type(l0)(rows=sh3, cols=sh3, vals=sh3, k=sh1))
level = jax.device_put(level, type(level)(rows=sh2, cols=sh2, vals=sh2, n=sh1))

rng = np.random.default_rng(0)
oracle = {}
for step in range(2 * SLOTS):
    br = np.full((S, BCAP), I32_MAX, np.int32)
    bc = np.full((S, BCAP), I32_MAX, np.int32)
    bv = np.zeros((S, BCAP), np.float32)
    for s in range(S):
        n = int(rng.integers(32, BCAP))
        r = rng.integers(0, IDCAP, n).astype(np.int32)
        c = rng.integers(0, 16, n).astype(np.int32)
        v = rng.normal(size=n).astype(np.float32)
        br[s, :n], bc[s, :n], bv[s, :n] = r, c, v
        for a, b, x in zip(r, c, v):
            oracle[(int(a), int(b))] = oracle.get((int(a), int(b)), 0.0) + float(x)
    l0 = ingest(l0, jax.device_put(jnp.asarray(br), sh2),
                jax.device_put(jnp.asarray(bc), sh2),
                jax.device_put(jnp.asarray(bv), sh2))
    if int(np.asarray(l0.k)[0]) == SLOTS:   # L0 full -> major compaction
        l0, level = compact(l0, level)
        assert int(np.asarray(level.n).max()) <= CAP

# fused read BEFORE the final compact: the L0 stack is non-empty, so the
# one-dispatch query must combine level + L0 runs on-device
QB = 16
all_keys = np.asarray(sorted({k[0] for k in oracle}), np.int64)
rng_q = np.random.default_rng(1)
qhost = np.full((S, QB), -1, np.int32)
want_q = {}
for s in range(S):
    lo, hi = s * IDCAP // S, (s + 1) * IDCAP // S
    mine = all_keys[(all_keys >= lo) & (all_keys < hi)]
    pick = (rng_q.choice(mine, size=min(QB - 2, len(mine)), replace=False)
            if len(mine) else np.empty(0, np.int64))
    qhost[s, :len(pick)] = np.sort(pick)
    for r in pick:
        for (rr, cc), v in oracle.items():
            if rr == r:
                want_q[(int(rr), int(cc))] = v
shq = NamedSharding(mesh, P("data", None))
qc, qv, qk = query(l0, level, jax.device_put(jnp.asarray(qhost), shq))
qc, qv, qk = np.asarray(qc), np.asarray(qv), np.asarray(qk)
got_q = {}
for s in range(S):
    for i in range(QB):
        if qhost[s, i] < 0:
            continue
        for j in np.nonzero(qk[s, i])[0]:
            got_q[(int(qhost[s, i]), int(qc[s, i, j]))] = float(qv[s, i, j])
assert set(got_q) == set(want_q), (len(got_q), len(want_q))
badq = [k for k in want_q if abs(got_q[k] - want_q[k]) > 1e-2]
assert not badq, badq[:5]
print("LSM-SPMD-QUERY-OK", len(got_q))

# query tiling: q_tile=8 splits the QB=16 batch into 2 tiles served by the
# same compiled step; outputs must match the untiled dispatch exactly
query_tiled = make_spmd_lsm_query_step(mesh, "data", combiner="sum",
                                       max_return=64, q_tile=8)
tc, tv, tk = query_tiled(l0, level, jax.device_put(jnp.asarray(qhost), shq))
np.testing.assert_array_equal(np.asarray(tk), qk)
np.testing.assert_array_equal(np.where(qk, np.asarray(tc), 0),
                              np.where(qk, qc, 0))
np.testing.assert_allclose(np.where(qk, np.asarray(tv), 0.0),
                           np.where(qk, qv, 0.0), rtol=1e-5, atol=1e-6)
print("LSM-SPMD-QUERY-TILED-OK")

# fused range scan (also BEFORE the final compact, so it must merge the
# level run + L0 stack on-device): a global [lo, hi) split into per-shard
# bounds; shards outside the range pass an empty interval
scan = make_spmd_lsm_scan_step(mesh, "data", combiner="sum", width=1024)
lo_g, hi_g = IDCAP // 4, IDCAP // 2
bounds = np.zeros((S, 2), np.int32)
for s in range(S):
    slo, shi = s * IDCAP // S, (s + 1) * IDCAP // S
    if max(lo_g, slo) < min(hi_g, shi):
        bounds[s] = (max(lo_g, slo), min(hi_g, shi))
sr, sc, sv, sk, scnt = scan(l0, level, jax.device_put(jnp.asarray(bounds), shq))
sr, sc, sv, sk = map(np.asarray, (sr, sc, sv, sk))
assert int(np.asarray(scnt).max()) <= 1024, "scan window overflow"
got_s = {}
for s in range(S):
    for j in np.nonzero(sk[s])[0]:
        got_s[(int(sr[s, j]), int(sc[s, j]))] = float(sv[s, j])
want_s = {k: v for k, v in oracle.items() if lo_g <= k[0] < hi_g}
assert set(got_s) == set(want_s), (len(got_s), len(want_s))
bads = [k for k in want_s if abs(got_s[k] - want_s[k]) > 1e-2]
assert not bads, bads[:5]
print("LSM-SPMD-SCAN-OK", len(got_s))

l0, level = compact(l0, level)
rows = np.asarray(level.rows); cols = np.asarray(level.cols)
vals = np.asarray(level.vals); ns = np.asarray(level.n)
got = {}
for s in range(S):
    for a, b, x in zip(rows[s, :ns[s]], cols[s, :ns[s]], vals[s, :ns[s]]):
        got[(int(a), int(b))] = float(x)
assert set(got) == set(oracle), (len(got), len(oracle))
bad = [k for k in oracle if abs(got[k] - oracle[k]) > 1e-2]
assert not bad, bad[:5]
print("LSM-SPMD-OK", len(got))
"""


@pytest.mark.slow
def test_spmd_lsm_ingest_and_compact():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"  # never reach for a chip the parent holds
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SPMD_SCRIPT], env=env,
                         cwd=".", capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "LSM-SPMD-QUERY-OK" in out.stdout
    assert "LSM-SPMD-QUERY-TILED-OK" in out.stdout
    assert "LSM-SPMD-SCAN-OK" in out.stdout
    assert "LSM-SPMD-OK" in out.stdout
