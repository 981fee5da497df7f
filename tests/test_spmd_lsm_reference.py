"""The SPMD LSM write path on four CPU devices against a plain numpy
reference, and the instrumentation of its steps.

A child process (the device count must be set before jax starts; this
process keeps one device) drives ``make_spmd_lsm_ingest_step`` and
``make_spmd_lsm_compact_step`` as the distributed BatchWriter's host loop
does: four ingestors step in lockstep, and the chips compact before a step
that would meet a full L0 stack. It saves every chip's level run and L0
stack after each step. The reference below imports nothing of ``repro``:
each chip owns a contiguous quarter of the id space, duplicates of a key
combine exactly (sum of dyadic weights, or the last value in stream
order), and every run is sorted by (row, col) with ``I32_MAX`` padding.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
I32_MAX = np.iinfo(np.int32).max
S, SCALE, BCAP, SLOTS, STEPS, LEVEL_CAP = 4, 12, 256, 4, 14, 16384
ID_CAP = 1 << SCALE

CHILD = r"""
import json, os, re, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.db.kvstore import Tablet
from repro.db.spmd import (L0Stack, l0_stacked_empty,
                           make_spmd_ingest_step,
                           make_spmd_lsm_compact_step,
                           make_spmd_lsm_ingest_step,
                           make_spmd_lsm_pair_ingest_step,
                           make_spmd_lsm_query_step, make_spmd_lsm_scan_step,
                           make_spmd_tablet_ingest_step, stacked_empty)
from repro.obs import default_registry, default_tracer, set_enabled

out_dir, combiner = sys.argv[1], sys.argv[2]
S, BCAP, SLOTS, STEPS, LEVEL_CAP, ID_CAP = (int(x) for x in sys.argv[3:9])
data = np.load(os.path.join(out_dir, "stream.npz"))
br_all, bc_all, bv_all = data["rows"], data["cols"], data["vals"]

mesh = jax.make_mesh((S,), ("data",), axis_types=(AxisType.Auto,))
sh1 = NamedSharding(mesh, P("data"))
sh2 = NamedSharding(mesh, P("data", None))
sh3 = NamedSharding(mesh, P("data", None, None))
empty_l0 = jax.jit(lambda: l0_stacked_empty(S, SLOTS, S * BCAP),
                   out_shardings=L0Stack(rows=sh3, cols=sh3, vals=sh3, k=sh1))
empty_level = jax.jit(lambda: stacked_empty(S, LEVEL_CAP),
                      out_shardings=Tablet(rows=sh2, cols=sh2, vals=sh2,
                                           n=sh1))
ingest = make_spmd_lsm_ingest_step(mesh, "data", S, ID_CAP,
                                   combiner=combiner)
compact = make_spmd_lsm_compact_step(mesh, "data", combiner=combiner)


def batch(t):
    return tuple(jax.device_put(x[t], sh2) for x in (br_all, bc_all, bv_all))


def host(l0, level):
    return {"l0_rows": np.asarray(l0.rows), "l0_cols": np.asarray(l0.cols),
            "l0_vals": np.asarray(l0.vals), "l0_k": np.asarray(l0.k),
            "lv_rows": np.asarray(level.rows), "lv_cols": np.asarray(level.cols),
            "lv_vals": np.asarray(level.vals), "lv_n": np.asarray(level.n)}


reg = default_registry()
snap0 = reg.snapshot()
ring0 = len(default_tracer().spans())
calls = {"ingest": 0, "compact": 0}

# the stream, compacting before a step that would meet a full stack
l0, level = empty_l0(), empty_level()
snaps, compacted_at = {}, []
for t in range(STEPS):
    if int(np.asarray(l0.k).max()) == SLOTS:
        l0, level = compact(l0, level)
        calls["compact"] += 1
        compacted_at.append(t)
    l0 = ingest(l0, *batch(t))
    calls["ingest"] += 1
    for k, v in host(l0, level).items():
        snaps[f"{t}_{k}"] = v
np.savez(os.path.join(out_dir, "steps.npz"), **snaps)

# the full-stack contract: fill the stack, step once more, then compact
# and re-submit that batch
l0, level = empty_l0(), empty_level()
for t in range(SLOTS):
    l0 = ingest(l0, *batch(t))
full = host(l0, level)
l0 = ingest(l0, *batch(SLOTS))
over = host(l0, level)
l0, level = compact(l0, level)
l0 = ingest(l0, *batch(SLOTS))
calls["ingest"] += SLOTS + 2
calls["compact"] += 1
again = host(l0, level)
np.savez(os.path.join(out_dir, "full.npz"),
         **{f"full_{k}": v for k, v in full.items()},
         **{f"over_{k}": v for k, v in over.items()},
         **{f"again_{k}": v for k, v in again.items()})

snap1 = reg.snapshot()
ring = [r["name"] for r in default_tracer().spans()[ring0:]]


def moved(name):
    a, b = snap0.get(name, 0), snap1.get(name, 0)
    if isinstance(b, dict):
        return b["count"] - (a["count"] if a else 0)
    return b - a


counts = {k: moved(k) for k in (
    "span_s{span=spmd.lsm_ingest}", "span_s{span=spmd.lsm_compact}",
    "spmd_exchange_slots{op=spmd_lsm_ingest}",
    "spmd_compact_entries{op=spmd_lsm_compact}",
    "spmd_steps{op=spmd_lsm_ingest}", "spmd_steps{op=spmd_lsm_compact}",
    "db_op_latency_s{op=spmd_lsm_ingest,table=spmd}")}

# with the registry disabled a step records nothing
set_enabled(False)
off0, ring_off0 = reg.snapshot(), len(default_tracer().spans())
l0 = ingest(empty_l0(), *batch(0))
l0, level = compact(l0, empty_level())
jax.block_until_ready((l0, level))
disabled_quiet = (reg.snapshot() == off0
                  and len(default_tracer().spans()) == ring_off0)
set_enabled(True)

# the program name every SPMD step lowers to
l0, level = empty_l0(), empty_level()
br, bc, bv = batch(0)
splits = jnp.full((8,), ID_CAP, jnp.int32)
owners = jnp.zeros((9,), jnp.int32)
q = jax.device_put(jnp.full((S, 8), -1, jnp.int32), sh2)
bounds = jax.device_put(jnp.zeros((S, 2), jnp.int32), sh2)
lowered = {
    "ingest": ingest.__wrapped__.lower(l0, br, bc, bv),
    "compact": compact.__wrapped__.lower(l0, level),
    "pair": make_spmd_lsm_pair_ingest_step(mesh, "data", S, ID_CAP)
    .__wrapped__.lower(l0, l0, br, bc, bv),
    "tablet": make_spmd_tablet_ingest_step(mesh, "data", S)
    .__wrapped__.lower(l0, br, bc, bv, splits, owners),
    "query": make_spmd_lsm_query_step(mesh, "data", max_return=4)
    .__wrapped__.lower(l0, level, q),
    "scan": make_spmd_lsm_scan_step(mesh, "data", width=8)
    .__wrapped__.lower(l0, level, bounds),
    "legacy": make_spmd_ingest_step(mesh, "data", S, ID_CAP)
    .__wrapped__.lower(stacked_empty(S, LEVEL_CAP), br, bc, bv),
}
names = {k: re.search(r"module @(\S+)", v.as_text()).group(1)
         for k, v in lowered.items()}
print(json.dumps({"compacted_at": compacted_at, "calls": calls,
                  "counts": counts, "ring": ring,
                  "disabled_quiet": disabled_quiet, "names": names}))
"""


def stream(seed: int):
    """Every ingestor's batches: skewed rows (most land on chip 0), few
    columns (so keys repeat within and across steps and ingestors), some
    ``I32_MAX`` padding, dyadic weights 1 + k/256 (every sum exact)."""
    rng = np.random.default_rng(seed)
    shape = (STEPS + 1, S, BCAP)
    rows = (ID_CAP * rng.random(shape) ** 3).astype(np.int32)
    cols = rng.integers(0, 48, shape).astype(np.int32)
    vals = (1.0 + rng.integers(0, 256, shape) / 256.0).astype(np.float32)
    pad = rng.random(shape) < 0.1
    rows[pad], cols[pad], vals[pad] = I32_MAX, I32_MAX, 0.0
    return rows, cols, vals


def owner(r):
    return np.minimum(r.astype(np.int64) * S // ID_CAP, S - 1)


def combine(rows, cols, vals, combiner):
    """Distinct (row, col) keys, sorted, each with the sum of its values or
    with its last value in stream order."""
    keys = rows.astype(np.int64) << 32 | cols.astype(np.int64)
    if combiner == "sum":
        uk, inv = np.unique(keys, return_inverse=True)
        sums = np.bincount(inv, weights=vals.astype(np.float64),
                           minlength=len(uk))
        return uk, sums.astype(np.float32)
    uk, last = np.unique(keys[::-1], return_index=True)
    return uk, vals[::-1][last]


def want(data, steps, chip, combiner):
    """What ``chip`` holds of ``steps``: the stream in step order, each
    step's ingestors in mesh order, each batch in its own order."""
    r, c, v = (data[k][steps].reshape(-1) for k in ("rows", "cols", "vals"))
    m = (r != I32_MAX) & (owner(r) == chip)
    return combine(r[m], c[m], v[m], combiner)


def assert_run(rows, cols, vals, n, wk, wv):
    """A sorted run of ``n`` live entries equal to the reference, then
    padding."""
    assert n == len(wk)
    got = rows[:n].astype(np.int64) << 32 | cols[:n].astype(np.int64)
    np.testing.assert_array_equal(got, wk)
    np.testing.assert_array_equal(vals[:n], wv)
    assert (rows[n:] == I32_MAX).all() and (cols[n:] == I32_MAX).all()
    assert (vals[n:] == 0).all()


def assert_state(snap, pre, data, level_steps, l0_steps, combiner):
    for s in range(S):
        wk, wv = want(data, level_steps, s, combiner)
        assert_run(snap[pre + "lv_rows"][s], snap[pre + "lv_cols"][s],
                   snap[pre + "lv_vals"][s], int(snap[pre + "lv_n"][s]),
                   wk, wv)
        assert int(snap[pre + "l0_k"][s]) == len(l0_steps)
        for j in range(SLOTS):
            if j < len(l0_steps):
                wk, wv = want(data, [l0_steps[j]], s, combiner)
            else:
                wk, wv = np.zeros(0, np.int64), np.zeros(0, np.float32)
            assert_run(snap[pre + "l0_rows"][s, j], snap[pre + "l0_cols"][s, j],
                       snap[pre + "l0_vals"][s, j], len(wk), wk, wv)


@pytest.fixture(scope="module", params=["sum", "last"])
def run(request, tmp_path_factory):
    combiner = request.param
    out = tmp_path_factory.mktemp(f"spmd_{combiner}")
    rows, cols, vals = stream(seed=15 + (combiner == "last"))
    np.savez(out / "stream.npz", rows=rows, cols=cols, vals=vals)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(out), combiner,
         *map(str, (S, BCAP, SLOTS, STEPS, LEVEL_CAP, ID_CAP))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    res.update(combiner=combiner,
               data={"rows": rows, "cols": cols, "vals": vals},
               steps=dict(np.load(out / "steps.npz")),
               full=dict(np.load(out / "full.npz")))
    return res


def test_every_chip_matches_the_numpy_reference_after_every_step(run):
    """Level run and L0 runs of every chip after each of the stream's
    steps, through several compactions."""
    assert run["compacted_at"] == list(range(SLOTS, STEPS, SLOTS))
    assert len(run["compacted_at"]) >= 3
    level_to = 0
    for t in range(STEPS):
        if t in run["compacted_at"]:
            level_to = t
        assert_state(run["steps"], f"{t}_", run["data"], list(range(level_to)),
                     list(range(level_to, t + 1)), run["combiner"])
    # the skew is real: chip 0 holds most of the level
    n = run["steps"][f"{STEPS - 1}_lv_n"]
    assert n[0] > n[1:].sum()


def test_a_step_against_a_full_stack_ingests_nothing(run):
    """``k`` saturates at ``slots`` and the batch is not ingested; after a
    compaction the re-submitted batch lands as one L0 run."""
    full = run["full"]
    steps = list(range(SLOTS))
    assert_state(full, "full_", run["data"], [], steps, run["combiner"])
    assert_state(full, "over_", run["data"], [], steps, run["combiner"])
    for k in ("l0_rows", "l0_cols", "l0_vals", "l0_k"):
        np.testing.assert_array_equal(full["over_" + k], full["full_" + k])
    assert_state(full, "again_", run["data"], steps, [SLOTS],
                 run["combiner"])


def test_each_step_lowers_to_its_own_program_name(run):
    names = run["names"]
    assert names["ingest"] == "jit_spmd_lsm_ingest"
    assert names["compact"] == "jit_spmd_lsm_compact"
    assert names["pair"] == "jit_spmd_lsm_pair_ingest"
    assert names["tablet"] == "jit_spmd_tablet_ingest"
    assert names["query"] == "jit_spmd_lsm_query"
    assert names["scan"] == "jit_spmd_lsm_scan"
    assert names["legacy"] == "jit_spmd_ingest"
    assert len(set(names.values())) == len(names)


def test_each_call_adds_one_span_and_its_shape_counts(run):
    """One ``spmd.lsm_*`` span a call, and the work counters by their shape
    formulas: S x S x bcap slots an exchange, S x (slots x run capacity +
    level capacity) entries a compaction (padding included)."""
    calls, counts = run["calls"], run["counts"]
    ingests, compacts = calls["ingest"], calls["compact"]
    assert counts["span_s{span=spmd.lsm_ingest}"] == ingests
    assert counts["span_s{span=spmd.lsm_compact}"] == compacts
    assert counts["spmd_steps{op=spmd_lsm_ingest}"] == ingests
    assert counts["spmd_steps{op=spmd_lsm_compact}"] == compacts
    # the span's clock reading feeds the step's dispatch histogram
    assert counts["db_op_latency_s{op=spmd_lsm_ingest,table=spmd}"] == ingests
    assert counts["spmd_exchange_slots{op=spmd_lsm_ingest}"] == \
        ingests * S * S * BCAP
    assert counts["spmd_compact_entries{op=spmd_lsm_compact}"] == \
        compacts * S * (SLOTS * S * BCAP + LEVEL_CAP)
    assert run["ring"].count("spmd.lsm_ingest") == ingests
    assert run["ring"].count("spmd.lsm_compact") == compacts


def test_a_disabled_registry_records_nothing(run):
    assert run["disabled_quiet"] is True
