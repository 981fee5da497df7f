"""The benchmark's own generator and reference, on the CPU."""
import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import graph500, reference  # noqa: E402

DIGESTS = {
    0: "3110b9dc2839e3772d3fd0994cde6e3c62be4ee89486ccc8340d53176f22b545",
    1: "cda3c14759002209f52595724d0667991651dd5c9dca7a26c6c8ea2b0e5d5ac5",
}


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_generator_matches_committed_digest(seed):
    u, v = graph500.kronecker_edges(10, 16, seed)
    assert len(u) == len(v) == 16 << 10
    assert hashlib.sha256(u.tobytes() + v.tobytes()).hexdigest() == \
        DIGESTS[seed]


@pytest.mark.parametrize("seed", [2**31 + 12345, [2**33 + 7, 3]])
def test_seeds_wider_than_32_bits_are_reproducible(seed):
    a = graph500.kronecker_edges(8, 16, seed)
    b = graph500.kronecker_edges(8, 16, seed)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert int(a[0].max()) < 1 << 8 and int(a[1].max()) < 1 << 8
    assert np.array_equal(graph500.dyadic_weights(64, seed),
                          graph500.dyadic_weights(64, seed))


def test_names_and_weights():
    names = graph500.name_table(4)
    assert list(names[:3]) == ["v00000000", "v00000001", "v00000002"]
    assert all(len(s) == graph500.NAME_WIDTH for s in names)
    w = graph500.dyadic_weights(4096, [3, 4])
    assert w.dtype == np.float32 and ((w >= 1) & (w < 2)).all()
    k = (w - 1) * 256
    assert np.array_equal(k, np.round(k))
    assert np.array_equal(w, graph500.dyadic_weights(4096, [3, 4]))


def _graph(seed=5, n=64, m=3000):
    rng = np.random.default_rng(seed)
    # few vertices, so duplicates (and last-wins) are common
    return (rng.integers(0, n, m).astype(np.int32),
            rng.integers(0, n, m).astype(np.int32),
            np.arange(1, m + 1, dtype=np.float32), n)


def test_edge_reference_agrees_with_a_dict_oracle():
    u, v, vals, n = _graph()
    ref = reference.EdgeReference(u, v, vals, n)
    last = {}
    for a, b, x in zip(u.tolist(), v.tolist(), vals.tolist()):
        last[(a, b)] = x
    for x in range(n):
        keys, got = ref.row(x)
        want = sorted((k, w) for k, w in last.items() if k[0] == x)
        r, c = reference.unpack(keys)
        assert list(zip(r.tolist(), c.tolist())) == [k for k, _ in want]
        assert got.tolist() == [w for _, w in want]
        keys, got = ref.col(x)
        want = sorted((k, w) for k, w in last.items() if k[1] == x)
        r, c = reference.unpack(keys)
        assert list(zip(r.tolist(), c.tolist())) == [k for k, _ in want]
        assert got.tolist() == [w for _, w in want]
    assert np.array_equal(ref.out_deg, np.bincount(u, minlength=n))
    assert np.array_equal(ref.in_deg, np.bincount(v, minlength=n))


def test_sum_combine_and_mismatches():
    u, v, vals, n = _graph(m=500)
    w = graph500.dyadic_weights(len(u), 9)
    keys, sums = reference.sum_combine(u, v, w)
    oracle = {}
    for a, b, x in zip(u.tolist(), v.tolist(), w.tolist()):
        oracle[(a, b)] = oracle.get((a, b), 0.0) + x
    r, c = reference.unpack(keys)
    assert {(a, b): x for a, b, x in zip(r.tolist(), c.tolist(),
                                         sums.tolist())} == oracle
    assert reference.mismatches(keys, sums, keys, sums) == 0
    bad = sums.copy()
    bad[0] += 1
    assert reference.mismatches(keys, bad, keys, sums) == 1
    assert reference.mismatches(keys[1:], sums[1:], keys, sums) == 1
    assert reference.mismatches(np.r_[keys, keys[:1]], np.r_[sums, sums[:1]],
                                keys, sums) == 1


def test_dictionary_order_interns_rows_then_columns_per_batch():
    u = np.asarray([5, 3, 5, 9], np.int32)
    v = np.asarray([7, 3, 1, 2], np.int32)
    order = reference.dictionary_order(u, v, 2, 10)
    assert order.tolist() == [3, 5, 7, 9, 1, 2]
