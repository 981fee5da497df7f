"""Blocked-ELL SpMV kernel — associative-array matvec (graph BFS, Fig. 1).

The paper's point is that BFS *is* sparse matrix-vector multiply. CSR SpMV
with per-row pointer chasing is a CPU idiom; the TPU adaptation pads rows to
a fixed nnz/row (ELL), tiles x into VMEM, and accumulates per x-tile with
branch-free one-hot compares — fixed shapes, no in-kernel gather.

The ELL arrays arrive slot-major (``cols_t[k, r]`` is row r's k-th column):
row r lives on the lane axis, so slot k of a row block is one dynamic
sublane read, and the x tile is a column whose one-hot compare against
that slot reduces over sublanes straight into a lane-major (1, block_r)
output row.

Grid = (row_blocks, x_tiles), x-tile axis innermost for in-place accumulate.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _spmv_kernel(cols_ref, vals_ref, x_ref, o_ref, *, block_c: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                   # (bc, 1) f32
    cidx = jax.lax.broadcasted_iota(jnp.int32, (block_c, 1), 0)

    def slot(k, acc):
        local = cols_ref[pl.ds(k, 1), :] - j * block_c   # (1, br), pad = -1
        hit = cidx == local                              # (bc, br) one-hot
        xi = jnp.sum(jnp.where(hit, x, 0.0), axis=0, keepdims=True)
        return acc + vals_ref[pl.ds(k, 1), :] * xi

    o_ref[...] += jax.lax.fori_loop(0, cols_ref.shape[0], slot,
                                    jnp.zeros(o_ref.shape, jnp.float32))


def spmv_ell_pallas(cols_t, vals_t, x, *, block_r: int = 256,
                    block_c: int = 2048, interpret: bool = True):
    """cols_t/vals_t: (K, R) slot-major ELL; x: (C, 1); returns (1, R) f32."""
    n_k, n_r = cols_t.shape
    n_c = x.shape[0]
    grid = (n_r // block_r, n_c // block_c)
    return pl.pallas_call(
        functools.partial(_spmv_kernel, block_c=block_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((n_k, block_r), lambda i, j: (0, i)),
            pl.BlockSpec((n_k, block_r), lambda i, j: (0, i)),
            pl.BlockSpec((block_c, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_r), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_r), jnp.float32),
        interpret=interpret,
    )(cols_t, vals_t, x)
