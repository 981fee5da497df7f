"""Faults planted under the timed path for the tests: each must turn the
run's ``correct`` false.

* ``state_unchanged`` -- the write step acknowledges and stores nothing;
* ``half_batch`` -- the write step stores only the first half of its batch;
* ``no_exchange`` -- the SPMD step skips the all-to-all between chips, so
  every chip keeps its own ingestor's edges;
* ``answer_altered`` -- each query answer has one value changed where the
  connector assembles it.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "no_exchange", "answer_altered")


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def _spmd_step(wrap):
    from repro.db import spmd
    make = spmd.make_spmd_lsm_ingest_step

    def factory(*a, **kw):
        return wrap(make(*a, **kw))
    return patched(spmd, "make_spmd_lsm_ingest_step", factory)


@contextlib.contextmanager
def planted(fault: str = None):
    if fault is None:
        yield
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    from repro.db.connector import Table
    from repro.db.kvstore import ShardedTable
    with contextlib.ExitStack() as stack:
        if fault == "state_unchanged":
            stack.enter_context(patched(ShardedTable, "insert",
                                        lambda self, *a, **kw: None))
            stack.enter_context(_spmd_step(
                lambda step: (lambda l0, br, bc, bv: l0)))
        elif fault == "half_batch":
            put = Table.put_triple

            def half(self, rows, cols, vals):
                n = (len(rows) + 1) // 2
                return put(self, rows[:n], cols[:n], vals[:n])
            stack.enter_context(patched(Table, "put_triple", half))

            def halve(step):
                def run(l0, br, bc, bv):
                    import jax.numpy as jnp
                    keep = jnp.arange(br.shape[1]) < br.shape[1] // 2
                    pad = jnp.iinfo(jnp.int32).max
                    return step(l0, jnp.where(keep, br, pad),
                                jnp.where(keep, bc, pad), bv)
                return run
            stack.enter_context(_spmd_step(halve))
        elif fault == "no_exchange":
            import jax
            stack.enter_context(patched(
                jax.lax, "all_to_all", lambda x, *a, **kw: x))
        elif fault == "answer_altered":
            assemble = Table._assemble

            def altered(self, rid, cid, val):
                val = val.copy()
                if len(val):
                    val[0] += 1.0
                return assemble(self, rid, cid, val)
            stack.enter_context(patched(Table, "_assemble", altered))
        yield
