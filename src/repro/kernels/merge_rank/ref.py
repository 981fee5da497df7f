"""Pure-jnp oracle: stable merge of two (row, col, val) sorted runs."""
import jax
import jax.numpy as jnp


def merge_sorted_ref(ar, ac, av, br, bc, bv):
    """Concatenate + stable lexicographic sort (A entries precede ties, and
    equal keys within a run keep their order: ``jnp.lexsort`` is unstable
    on the TPU)."""
    cat = (jnp.concatenate([ar, br]), jnp.concatenate([ac, bc]),
           jnp.concatenate([av, bv]))
    return tuple(jax.lax.sort(cat, num_keys=2, is_stable=True))


def row_rank_ref(keys):
    """Branch-free per-row strict self-rank (the ``row_rank_pallas``
    oracle): ``o[i, j] = |{ k : keys[i, k] < keys[i, j] }|``."""
    return jnp.sum(keys[:, None, :] < keys[:, :, None], axis=2,
                   dtype=jnp.int32)


def merge_combine_rows_ref(keys, vals):
    """Sort-based oracle for ``merge_combine_rows``: row-wise ascending
    key order with vals carried along (valid keys unique per row, so
    stability is irrelevant everywhere except among I32_MAX pads — whose
    vals are garbage either way)."""
    order = jnp.argsort(keys, axis=1)
    return (jnp.take_along_axis(keys, order, axis=1),
            jnp.take_along_axis(vals, order, axis=1))
