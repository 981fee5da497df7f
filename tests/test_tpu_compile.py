"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each case lowers a kernel (or the fused LSM
read that calls it) at the widths the store uses, compiles it for a
described ``v5e:2x2`` topology with ``interpret=False``, and asserts the
kernel survived as a ``tpu_custom_call``. This catches what interpret mode
cannot: block shapes that break the TPU (8, 128) tiling rule, and ops the
Mosaic lowering does not support (e.g. an in-kernel 1-D gather).

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.db.lsm import engine as lsm_engine
from repro.db.lsm.bloom import num_words
from repro.db.lsm.engine import _fused_query_fn, _fused_scan_fn, fence_block
from repro.kernels.merge_rank import kway_merge, merge_combine_rows, merge_sorted
from repro.kernels.segment_reduce import segment_sum
from repro.kernels.sorted_search import (sorted_search, sorted_search_batched,
                                         sorted_search_endpoints)
from repro.kernels.spmv import spmv_ell

RUN = 1 << 20        # entries per run: a deep level / Graph500 scale-16 table
L0_SLOTS = 4         # StoreConfig.l0_slots
MEMTABLE = 1 << 18   # default memtable (and L0 run) capacity
Q_TILE = 512         # StoreConfig.fused_q_limit: the read path's query tile
ID_CAPACITY = 1 << 22


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _run(sharding, n):
    return (_shape(sharding, (n,)), _shape(sharding, (n,)),
            _shape(sharding, (n,), jnp.float32))


def _resident(sharding):
    """Shapes of one shard's fused-read operands: one leveled run of RUN
    entries plus a full L0 stack of memtable-sized runs."""
    blk, blk0 = fence_block(RUN), fence_block(MEMTABLE)
    level = _run(sharding, RUN) + (
        _shape(sharding, (RUN // blk,)),
        _shape(sharding, (num_words(RUN),), jnp.uint32))
    l0 = (_shape(sharding, (L0_SLOTS, MEMTABLE)),
          _shape(sharding, (L0_SLOTS, MEMTABLE)),
          _shape(sharding, (L0_SLOTS, MEMTABLE), jnp.float32),
          _shape(sharding, (L0_SLOTS, MEMTABLE // blk0)),
          _shape(sharding, (L0_SLOTS, num_words(MEMTABLE)), jnp.uint32))
    return (level,), (blk,), blk0, l0


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _case_sorted_search(sh):
    return _compile(lambda t, q: sorted_search(t, q, "left", interpret=False),
                    _shape(sh, (RUN,)), _shape(sh, (4096,)))


def _case_sorted_search_batched(sh):
    fences = _shape(sh, (L0_SLOTS, MEMTABLE // fence_block(MEMTABLE)))
    return _compile(
        lambda t, q: sorted_search_batched(t, q, "right", interpret=False),
        fences, _shape(sh, (Q_TILE,)))


def _case_sorted_search_endpoints(sh):
    fences = _shape(sh, (L0_SLOTS, MEMTABLE // fence_block(MEMTABLE)))
    return _compile(
        lambda t, lohi: sorted_search_endpoints(t, lohi, interpret=False),
        fences, _shape(sh, (2,)))


def _case_merge_sorted(sh):
    return _compile(lambda *a: merge_sorted(*a, interpret=False),
                    *_run(sh, RUN), *_run(sh, RUN))


def _case_kway_merge(sh):
    def merge(a, b, c):
        return kway_merge([a, b, c], use_pallas=True, interpret=False)
    return _compile(merge, _run(sh, RUN), _run(sh, MEMTABLE),
                    _run(sh, MEMTABLE))


def _case_merge_combine_rows(sh):
    def combine(k, v):
        return merge_combine_rows(k, v, use_pallas=True, interpret=False)
    return _compile(combine, _shape(sh, (Q_TILE, 256)),
                    _shape(sh, (Q_TILE, 256), jnp.float32))


def _case_segment_sum(sh):
    return _compile(
        lambda i, v: segment_sum(i, v, n_segments=ID_CAPACITY,
                                 interpret=False),
        _shape(sh, (1 << 15,)), _shape(sh, (1 << 15,), jnp.float32))


def _case_spmv_ell(sh):
    rows, slots = 1 << 16, 16
    return _compile(lambda c, v, x: spmv_ell(c, v, x, interpret=False),
                    _shape(sh, (rows, slots)),
                    _shape(sh, (rows, slots), jnp.float32),
                    _shape(sh, (rows,), jnp.float32))


def _case_fused_query(sh):
    levels, blocks, blk0, l0 = _resident(sh)
    # __wrapped__: a fresh jit, so no trace cached with interpret mode on
    fn = _fused_query_fn.__wrapped__("last", blocks, (4,), blk0, 4, 4,
                                     "none", True, True)
    return fn.lower(_shape(sh, (Q_TILE,)), levels, l0, None).compile() \
        .as_text()


def _case_fused_scan(sh):
    levels, blocks, blk0, l0 = _resident(sh)
    fn = _fused_scan_fn.__wrapped__("last", blocks, blk0, 64, "none",
                                    ID_CAPACITY, True)
    return fn.lower(_shape(sh, (2,)), levels, l0, None).compile().as_text()


CASES = {name[len("_case_"):]: fn for name, fn in list(globals().items())
         if name.startswith("_case_")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case, monkeypatch):
    # the fused reads pass the engine's import-time INTERPRET (True on this
    # CPU backend) down to their kernels; on a chip it is False
    monkeypatch.setattr(lsm_engine, "INTERPRET", False)
    text = CASES[case](one_chip)
    assert "tpu_custom_call" in text, f"{case}: no Pallas kernel in the HLO"
