"""Major compaction merges only the shards that hold runs to merge.

``_compact_fn`` visits the shards in turn and merges a shard only when
an L0 slot or a level shallower than the target holds entries; any other
shard returns its target level as it stands. These tests hold the
program to the formula it replaced (every shard merged under ``vmap``),
bit for bit at every depth, and check the store's answers and the
``lsm_compact_skipped_shards`` counter around it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.db.kvstore import ShardedTable, shard_of
from repro.db.lsm import engine as lsm_engine
from repro.db.lsm.bloom import bloom_build, fence_build
from repro.kernels.common import I32_MAX, INTERPRET
from repro.kernels.merge_rank import kway_merge
from repro.obs import default_registry

S, ID_CAP, MEM = 4, 1 << 10, 32
CFG = dict(num_shards=S, capacity_per_shard=2048, batch_cap=MEM,
           id_capacity=ID_CAP, memtable_cap=MEM, l0_slots=3)


def _vmapped_compact(combiner, use_pallas, out_cap, n_words, block,
                     n_hashes):
    """The compaction as it was: every shard merged, batched by ``vmap``."""
    from repro.db.kvstore import _dedup_combine

    def lsm_compact(l0_r, l0_c, l0_v, lvls):
        runs = [lv for lv in lvls]
        runs += [(l0_r[k], l0_c[k], l0_v[k]) for k in range(l0_r.shape[0])]
        mr, mc, mv = kway_merge(runs, use_pallas=use_pallas,
                                interpret=INTERPRET)
        keep, out_v = _dedup_combine(mr, mc, mv, combiner)
        pos = jnp.cumsum(keep) - 1
        idx = jnp.where(keep, pos, out_cap)
        rr = jnp.full((out_cap,), I32_MAX, jnp.int32).at[idx].set(
            mr, mode="drop")
        cc = jnp.full((out_cap,), I32_MAX, jnp.int32).at[idx].set(
            mc, mode="drop")
        vv = jnp.zeros((out_cap,), jnp.float32).at[idx].set(out_v,
                                                            mode="drop")
        n = keep.sum().astype(jnp.int32)
        return (rr, cc, vv, n, bloom_build(rr, n_words, n_hashes),
                fence_build(rr, block), rr[0], rr[jnp.maximum(n - 1, 0)])

    return jax.jit(jax.vmap(lsm_compact))


def _rows_on(shards, n, rng):
    """``n`` row ids owned by the given shards (range partition)."""
    width = ID_CAP // S
    s = rng.choice(np.asarray(shards), n)
    return (s * width + rng.integers(0, width // 4, n)).astype(np.int32)


def _table(name, combiner, batches, use_pallas=False):
    """A store fed ``batches`` of (shards, n) one flush each (``COMPACT``:
    a full major compaction), and the dict oracle of what it holds."""
    t = ShardedTable(name, engine="lsm", combiner=combiner,
                     use_pallas=use_pallas, **CFG)
    rng = np.random.default_rng(7)
    oracle = {}
    for batch in batches:
        if batch is COMPACT:
            t.major_compact()
            continue
        shards, n = batch
        r = _rows_on(shards, n, rng)
        c = rng.integers(0, 4, n).astype(np.int32)
        v = rng.integers(1, 9, n).astype(np.float32)
        t.insert(r, c, v)
        t.flush()
        for a, b, x in zip(r, c, v):
            k = (int(a), int(b))
            oracle[k] = (oracle.get(k, 0.0) + float(x) if combiner == "sum"
                         else float(x))
    return t, oracle


COMPACT = None
# only shard 0 holds data: entries in L2, two L0 runs pending
SHARD0 = [((0,), MEM)] * 14
# every shard holds data in every run
EVERY = [((0, 1, 2, 3), MEM)] * 14
# shard 0 as above, and shard 2 with one L0 run: a partly filled L0 that
# a compaction of shard 0 alone leaves where it is
PARTIAL = [((0,), MEM)] * 6 + [((2,), 8)] + [((0,), MEM)] * 2
# every shard settled into a level, then shard 0 alone written on: the
# other shards skip with a target that holds data
SETTLED = [((0, 1, 2, 3), MEM)] * 5 + [COMPACT] + [((0,), MEM)] * 5
STATES = {"shard0": SHARD0, "every": EVERY, "partial": PARTIAL,
          "settled": SETTLED}


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    return np.array_equal(a.view(np.uint8), b.view(np.uint8))


@pytest.mark.parametrize("combiner", ["last", "sum"])
@pytest.mark.parametrize("state", sorted(STATES))
def test_compaction_matches_the_vmapped_merge_at_every_depth(state,
                                                             combiner):
    t, _ = _table(f"skip_{state}_{combiner}", combiner, STATES[state])
    runs = t._runs
    assert runs.l0_used.max() > 0 and any(lv["n"].any()
                                          for lv in runs.levels)
    for d, lv in enumerate(runs.levels):
        key = (runs.combiner, runs.use_pallas, lv["cap"], lv["words"],
               lv["block"], lv["hashes"])
        lvls = tuple((runs.levels[i]["rows"], runs.levels[i]["cols"],
                      runs.levels[i]["vals"]) for i in range(d, -1, -1))
        args = (runs.l0_rows, runs.l0_cols, runs.l0_vals, lvls)
        got = lsm_engine._compact_fn(*key)(*args)
        want = _vmapped_compact(*key)(*args)
        names = ("rows", "cols", "vals", "n", "bloom", "fence", "min",
                 "max")
        for name, g, w in zip(names, got, want):
            assert _same_bits(g, w), (state, combiner, d, name)


def test_pallas_merge_matches_the_vmapped_merge():
    t, _ = _table("skip_pallas", "sum", PARTIAL[:4] + [((2,), 8)],
                  use_pallas=True)
    runs = t._runs
    lv = runs.levels[0]
    key = (runs.combiner, True, lv["cap"], lv["words"], lv["block"],
           lv["hashes"])
    args = (runs.l0_rows, runs.l0_cols, runs.l0_vals,
            ((lv["rows"], lv["cols"], lv["vals"]),))
    for g, w in zip(lsm_engine._compact_fn(*key)(*args),
                    _vmapped_compact(*key)(*args)):
        assert _same_bits(g, w)


@pytest.mark.parametrize("combiner", ["last", "sum"])
def test_masked_compaction_keeps_the_store_exact(combiner):
    t, oracle = _table(f"skip_mask_{combiner}", combiner, PARTIAL)
    runs = t._runs
    assert [int(x) for x in runs.l0_used] == [2, 0, 1, 0]
    runs.major_compact(mask=np.asarray([True, False, False, False]))
    # shard 0 merged into a level; shard 2's L0 run stays where it was
    assert [int(x) for x in runs.l0_used] == [0, 0, 1, 0]
    assert int(runs.l0_n[2, 0]) > 0
    r, c, v = t.scan()
    got = {(int(a), int(b)): float(x) for a, b, x in zip(r, c, v)}
    assert got == oracle
    assert set(shard_of(np.asarray([k[0] for k in got]), S, ID_CAP)) == {0, 2}


def _skipped(table: str) -> int:
    series = default_registry().series("lsm_compact_skipped_shards",
                                       table=table)
    assert len(series) == 1
    return int(series[0].value)


@pytest.mark.parametrize("shards, per_compaction", [((0,), 3),
                                                    ((0, 1, 2, 3), 0)])
def test_skipped_shards_are_counted(shards, per_compaction):
    name = f"skip_count_{len(shards)}"
    t, _ = _table(name, "last", [(shards, MEM)] * 10)
    compactions = t.engine_stats()["major_compactions"]
    assert compactions >= 3
    assert _skipped(name) == per_compaction * compactions
