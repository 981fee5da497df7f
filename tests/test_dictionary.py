"""StringDict.decode against the list-of-strings formula it replaces: it
gathers from an object-array mirror that only decode syncs, so every mix of
encode, decode, rebuild and edge-case ids must give the same answers, and
the mirror must copy each key exactly once (``dict_decode_mirrored_keys``)."""
import numpy as np
import pytest

from repro.core.dictionary import StringDict
from repro.obs import default_registry, set_enabled


def _oracle(d, ids):
    return np.asarray(list(d._to_str), dtype=object)[np.asarray(ids)]


def _assert_same(d, ids):
    got, want = d.decode(ids), _oracle(d, ids)
    if isinstance(want, np.ndarray):
        assert isinstance(got, np.ndarray) and got.dtype == object
        assert got.shape == want.shape
        assert got.tolist() == want.tolist()
    else:
        assert type(got) is type(want) and got == want


def _keys(lo, hi):
    return np.asarray([f"v{i:08d}" for i in range(lo, hi)], dtype=object)


def _mirrored():
    return default_registry().counter("dict_decode_mirrored_keys").value


def _nine_keys_with_spare_room():
    """Nine keys whose mirror has room for ten, so an id past the end of
    the dictionary still lies inside the mirror's buffer."""
    d = StringDict()
    d.encode(_keys(0, 5))
    d.decode(np.arange(5))
    d.encode(_keys(5, 9))
    d.decode(np.arange(9))
    assert len(d) == 9 and len(d._mirror) == 10
    return d


@pytest.fixture(autouse=True)
def _registry_on():
    was = default_registry().enabled
    set_enabled(True)
    yield
    set_enabled(was)


# batch sizes between decodes: single keys, a batch that crosses several
# doublings at once, batches that add nothing new, and many small steps
@pytest.mark.parametrize("batches", [
    [1, 1, 1, 1, 1, 1, 1, 1, 1],
    [3, 50, 7, 1000],
    [1000, 0, 0, 1],
    [2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 2, 300],
], ids=["one-at-a-time", "mixed", "no-growth", "many-doublings"])
def test_decode_matches_oracle_across_interleaved_encodes(batches):
    rng = np.random.default_rng(len(batches))
    d = StringDict()
    lo = 0
    for size in batches:
        # repeat some known keys in each batch, so encode also dedups
        new = _keys(lo, lo + size)
        old = rng.choice(_keys(0, lo), size=min(lo, 5)) if lo else new[:0]
        d.encode(np.concatenate([new, old]))
        lo += size
        n = len(d)
        _assert_same(d, np.arange(n))
        _assert_same(d, rng.integers(0, n, size=37))
        _assert_same(d, rng.integers(0, n, size=(3, 4)).astype(np.int64))
        _assert_same(d, [n - 1, 0, n - 1])
    assert len(d) == sum(batches)


@pytest.mark.parametrize("build", ["from_strings", "save_load"])
def test_decode_matches_oracle_on_rebuilt_dict(build, tmp_path):
    strings = [f"k{(i * 7919) % 5003:05d}" for i in range(5003)]
    if build == "from_strings":
        d = StringDict.from_strings(strings)
    else:
        src = StringDict.from_strings(strings)
        src.decode(np.arange(10))          # a synced mirror is not saved
        src.save(str(tmp_path / "keys.json"))
        d = StringDict.load(str(tmp_path / "keys.json"))
    assert d._to_str == strings
    rng = np.random.default_rng(1)
    _assert_same(d, rng.integers(0, len(d), size=500))
    d.encode(np.asarray(["new-a", strings[3], "new-b"], dtype=object))
    _assert_same(d, [len(d) - 2, len(d) - 1, 3])


@pytest.mark.parametrize("ids", [
    -1,
    [-1],
    np.asarray([-1, -2, 0]),
    np.zeros(0, dtype=np.int32),
    np.zeros(0, dtype=np.int64),
    np.asarray(4, dtype=np.int32),
    np.int64(0),
    np.asarray([True, False, True, False, True, False, True, False, True]),
], ids=["scalar-neg", "list-neg", "negatives", "empty-i32", "empty-i64",
        "0d", "np-int", "bool-mask"])
def test_decode_edge_ids_match_oracle(ids):
    _assert_same(_nine_keys_with_spare_room(), ids)


@pytest.mark.parametrize("bad", [9, [0, 9], np.asarray(9), -10])
def test_decode_out_of_range_raises(bad):
    d = _nine_keys_with_spare_room()
    with pytest.raises(IndexError):
        d.decode(bad)
    with pytest.raises(IndexError):
        _oracle(d, bad)


def test_decode_of_empty_dict():
    d = StringDict()
    got = d.decode(np.zeros(0, dtype=np.int32))
    assert got.dtype == object and got.shape == (0,)
    with pytest.raises(IndexError):
        d.decode([0])


def test_decode_result_does_not_alias_mirror():
    d = StringDict()
    d.encode(_keys(0, 20))
    out = d.decode(np.arange(20))
    out[:] = "overwritten"
    one = d.decode(np.asarray(0))
    assert one == "v00000000"
    d.decode(np.asarray([0, 0]))[1] = "overwritten"
    _assert_same(d, np.arange(20))
    assert d.decode(0) == "v00000000"


def test_mirrored_keys_counter_counts_each_key_once():
    d = StringDict()
    d.encode(_keys(0, 1000))
    before = _mirrored()
    for i in range(50):
        d.decode(np.arange(i, i + 10))
    assert _mirrored() - before == len(d) == 1000
    for k in (1, 0, 300, 17):
        d.encode(np.concatenate([_keys(len(d), len(d) + k), _keys(0, 5)]))
        before = _mirrored()
        d.decode(np.arange(3))
        d.decode(np.asarray([len(d) - 1]))
        assert _mirrored() - before == k
    assert len(d) == 1318


def test_encode_and_lookup_leave_the_mirror_alone():
    d = StringDict()
    before = _mirrored()
    d.encode(_keys(0, 100))
    d.lookup(_keys(50, 150))
    d.get("v00000003")
    assert _mirrored() == before and d._mirrored == 0
    d.decode([0])
    assert _mirrored() - before == 100
