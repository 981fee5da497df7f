"""jit'd public wrapper for blocked-ELL SpMV + CSR->ELL conversion."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..common import INTERPRET, pad_to
from .kernel import spmv_ell_pallas


def ell_from_coo(r: np.ndarray, c: np.ndarray, v: np.ndarray, n_rows: int):
    """Host-side COO (row-major sorted) -> ELL (cols, vals), pad col = -1."""
    counts = np.bincount(r, minlength=n_rows)
    k = max(int(counts.max()) if len(counts) else 1, 1)
    cols = np.full((n_rows, k), -1, dtype=np.int32)
    vals = np.zeros((n_rows, k), dtype=np.float32)
    ends = np.cumsum(counts)
    starts = ends - counts
    slot = np.arange(len(r)) - starts[r]
    cols[r, slot] = c
    vals[r, slot] = v
    return cols, vals


@functools.partial(jax.jit, static_argnames=("block_r", "block_c", "interpret"))
def spmv_ell(cols: jax.Array, vals: jax.Array, x: jax.Array,
             block_r: int = 256, block_c: int = 2048,
             interpret: bool = INTERPRET) -> jax.Array:
    """y = A @ x for blocked-ELL A; pad columns are -1."""
    n_r = cols.shape[0]
    cols_p, _ = pad_to(cols.astype(jnp.int32).T, block_r, 1, -1)
    vals_p, _ = pad_to(vals.astype(jnp.float32).T, block_r, 1, 0.0)
    x_p, _ = pad_to(x.astype(jnp.float32).reshape(-1, 1), block_c, 0, 0.0)
    out = spmv_ell_pallas(cols_p, vals_p, x_p, block_r=block_r,
                          block_c=block_c, interpret=interpret)
    return out[0, :n_r]
