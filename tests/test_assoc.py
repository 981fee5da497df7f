"""Unit + property tests for associative arrays (paper §II semantics)."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import Assoc, split_str


def dense_oracle(a: Assoc):
    """dict {(row,col): val} oracle."""
    r, c, v = a.triples()
    return {(rr, cc): vv for rr, cc, vv in zip(r, c, v)}


# ------------------------------------------------------------- construction
def test_split_str():
    assert list(split_str("a,b,c,")) == ["a", "b", "c"]
    assert list(split_str("alice bob ")) == ["alice", "bob"]
    assert list(split_str("")) == []


def test_basic_numeric():
    a = Assoc("alice,bob,", "bob,carl,", [1.0, 2.0])
    assert a.shape == (2, 2)
    assert a.nnz() == 2
    assert dense_oracle(a) == {("alice", "bob"): 1.0, ("bob", "carl"): 2.0}


def test_string_values():
    a = Assoc("alice,", "bob,", "cited,")
    r, c, v = a.triples()
    assert v[0] == "cited"
    assert not a.is_numeric()
    assert a.logical().is_numeric()


def test_broadcast_scalar():
    a = Assoc("a,b,c,", "x,", 1.0)
    assert a.nnz() == 3
    assert a.shape == (3, 1)


def test_duplicate_collision_sum():
    a = Assoc("a,a,", "x,x,", [1.0, 2.0])
    assert dense_oracle(a) == {("a", "x"): 3.0}


def test_zero_dropped():
    a = Assoc("a,b,", "x,y,", [0.0, 5.0])
    assert a.nnz() == 1
    assert a.shape == (1, 1)


def test_empty():
    a = Assoc()
    assert a.nnz() == 0 and a.shape == (0, 0)
    b = a + Assoc("a,", "b,", 2.0)
    assert dense_oracle(b) == {("a", "b"): 2.0}


# ----------------------------------------------------------------- indexing
@pytest.fixture
def graph():
    rows = "alice,alice,bob,carl,carl,dan,"
    cols = "bob,carl,alice,alice,dan,alice,"
    return Assoc(rows, cols, [1, 2, 3, 4, 5, 6])


def test_single_row(graph):
    sub = graph["alice,", :]
    assert dense_oracle(sub) == {("alice", "bob"): 1.0, ("alice", "carl"): 2.0}


def test_multi_row(graph):
    sub = graph["alice,bob,", :]
    assert sub.nnz() == 3


def test_prefix(graph):
    sub = graph["ca*,", :]
    assert set(sub.row) == {"carl"}
    assert sub.nnz() == 2


def test_range(graph):
    sub = graph["alice,:,bob,", :]
    assert set(sub.row) == {"alice", "bob"}


def test_positional(graph):
    sub = graph[0:2, :]
    assert set(sub.row) == {"alice", "bob"}  # first two sorted row keys


def test_col_query(graph):
    sub = graph[:, "alice,"]
    assert set(sub.row) == {"bob", "carl", "dan"}


def test_value_filter(graph):
    sub = graph == 4.0
    assert dense_oracle(sub) == {("carl", "alice"): 4.0}
    assert (graph > 4.0).nnz() == 2


def test_missing_key(graph):
    assert graph["zed,", :].nnz() == 0


# ------------------------------------------------------------------ algebra
def test_add(graph):
    two = graph + graph
    assert dense_oracle(two) == {k: 2 * v for k, v in dense_oracle(graph).items()}


def test_sub_cancels(graph):
    z = graph - graph
    assert z.nnz() == 0


def test_and_or():
    a = Assoc("a,b,", "x,y,", [1.0, 2.0])
    b = Assoc("b,c,", "y,z,", [5.0, 7.0])
    assert dense_oracle(a & b) == {("b", "y"): 2.0}
    assert dense_oracle(a | b) == {
        ("a", "x"): 1.0, ("b", "y"): 5.0, ("c", "z"): 7.0,
    }


def test_matmul_matches_dense():
    rng = np.random.default_rng(0)
    keys = np.asarray([f"k{i}" for i in range(6)], dtype=object)
    def rand_assoc():
        n = 12
        return Assoc(keys[rng.integers(0, 6, n)], keys[rng.integers(0, 6, n)],
                     rng.integers(1, 5, n).astype(float))
    a, b = rand_assoc(), rand_assoc()
    c = a * b
    # dense oracle over the full key universe
    da = np.zeros((6, 6)); db = np.zeros((6, 6))
    for (r, cc), v in dense_oracle(a).items():
        da[int(r[1:]), int(cc[1:])] = v
    for (r, cc), v in dense_oracle(b).items():
        db[int(r[1:]), int(cc[1:])] = v
    dc = da @ db
    for (r, cc), v in dense_oracle(c).items():
        assert dc[int(r[1:]), int(cc[1:])] == pytest.approx(v)
        dc[int(r[1:]), int(cc[1:])] = 0.0
    assert np.all(dc == 0.0)  # no entries missed


def test_transpose_involution(graph):
    assert graph.T.T.same_as(graph)


def test_sum(graph):
    assert graph.sum() == 21.0
    out = graph.sum(axis=1)
    assert dense_oracle(out)[("alice", "sum")] == 3.0


def test_bfs_is_matvec(graph):
    """Paper Fig 1: neighbors of a vertex == matrix-vector multiply."""
    v0 = Assoc("seed,", "alice,", 1.0)
    nbrs = v0 * graph
    assert set(nbrs.col) == {"bob", "carl"}


# ----------------------------------------------------- property-based tests
keys_st = st.lists(st.sampled_from([f"v{i:02d}" for i in range(8)]),
                   min_size=1, max_size=12)


def build(rows, cols, vals):
    n = min(len(rows), len(cols), len(vals))
    return Assoc(np.asarray(rows[:n], object), np.asarray(cols[:n], object),
                 np.asarray(vals[:n], float))


@settings(max_examples=60, deadline=None)
@given(keys_st, keys_st, st.lists(st.integers(1, 9), min_size=1, max_size=12),
       keys_st, keys_st, st.lists(st.integers(1, 9), min_size=1, max_size=12))
def test_add_commutes(r1, c1, v1, r2, c2, v2):
    a, b = build(r1, c1, v1), build(r2, c2, v2)
    assert (a + b).same_as(b + a)


@settings(max_examples=60, deadline=None)
@given(keys_st, keys_st, st.lists(st.integers(1, 9), min_size=1, max_size=12))
def test_transpose_involution_prop(r, c, v):
    a = build(r, c, v)
    assert a.T.T.same_as(a)


@settings(max_examples=60, deadline=None)
@given(keys_st, keys_st, st.lists(st.integers(1, 9), min_size=1, max_size=12),
       keys_st, keys_st, st.lists(st.integers(1, 9), min_size=1, max_size=12))
def test_and_subset_or(r1, c1, v1, r2, c2, v2):
    a, b = build(r1, c1, v1), build(r2, c2, v2)
    inter, uni = dense_oracle(a & b), dense_oracle(a | b)
    da, db = dense_oracle(a), dense_oracle(b)
    assert set(inter) == set(da) & set(db)
    assert set(uni) == set(da) | set(db)
    for k, v in inter.items():
        assert v == min(da[k], db[k])
    for k, v in uni.items():
        assert v == max(da.get(k, -1e18), db.get(k, -1e18))


@settings(max_examples=40, deadline=None)
@given(keys_st, keys_st, st.lists(st.integers(1, 9), min_size=1, max_size=12))
def test_query_roundtrip(r, c, v):
    """Row query returns exactly the oracle's entries for that row."""
    a = build(r, c, v)
    oracle = dense_oracle(a)
    for row in a.row:
        sub = a[row + ",", :]
        assert dense_oracle(sub) == {k: w for k, w in oracle.items() if k[0] == row}
