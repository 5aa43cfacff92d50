"""Pass working set lookup: the threaded native search against its oracle.

``table/sparse_table.py::lookup_rows`` is the one body of
``PassWorkingSet.lookup`` and ``DistributedWorkingSet.lookup``. A call of
``_LOOKUP_NATIVE_FLOOR`` keys or more, where the native library loaded,
runs ``pbx_lookup_rows`` (csrc/host_table.cc): sixteen binary searches in
lock step a thread, the queries cut into slices over a pool. It is *pure
mechanism*: every row id is bit-for-bit what the numpy body
(``_lookup_rows_numpy``) gives, at every thread count and query count, and
a missing key raises the ``KeyError`` the numpy body raises, word for word.
"""

from __future__ import annotations

import numpy as np
import pytest

from paddlebox_tpu.table import (
    HostSparseTable,
    PassWorkingSet,
    SparseOptimizerConfig,
    ValueLayout,
)
from paddlebox_tpu.table import sparse_table as st
from paddlebox_tpu.table.dist_ws import DistributedWorkingSet
from paddlebox_tpu.utils import native
from paddlebox_tpu.utils.monitor import STAT_GET

needs_native = pytest.mark.skipif(
    not native.available(), reason="native tier unavailable"
)

FLOOR = st._LOOKUP_NATIVE_FLOOR
LAYOUT = ValueLayout(embedx_dim=4)
OPT = SparseOptimizerConfig(embedx_threshold=0.0, initial_range=0.01)


def _working_set(n, seed=0):
    """(sorted unique uint64 keys [n], a row permutation [n] int64)."""
    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, 2**63, size=2 * n + 8, dtype=np.uint64))[:n]
    assert len(keys) == n
    return keys, rng.permutation(n).astype(np.int64)


def _counters():
    return (
        STAT_GET("table.lookup.native_keys"),
        STAT_GET("table.lookup.numpy_keys"),
    )


class _OneRankTransport:
    rank, n_ranks = 0, 1

    def alltoall(self, payloads, tag):
        return list(payloads)

    def allgather(self, payload, tag):
        return [payload]

    def allreduce_max(self, value, tag):
        return int(value)


# ---- bit-equal rows ---------------------------------------------------------


@needs_native
@pytest.mark.parametrize("threads", [1, 2, 3, 16])
@pytest.mark.parametrize("m", [1, 15, 16, 17, 1000, 4099])
def test_native_rows_equal_numpy_at_every_thread_and_query_count(m, threads):
    sorted_keys, rows = _working_set(3001, seed=m)
    rng = np.random.default_rng(m + threads)
    keys = sorted_keys[rng.integers(0, len(sorted_keys), size=m)]
    want = st._lookup_rows_numpy(sorted_keys, rows, keys)
    got, n_missing, first, used = native.lookup_rows(
        sorted_keys, rows, keys, threads
    )
    assert got.dtype == np.int32 and np.array_equal(got, want)
    assert n_missing == 0 and len(first) == 0
    # never more threads than whole blocks of sixteen lanes
    assert used == min(threads, -(-m // 16))


@needs_native
@pytest.mark.parametrize("n", [1, 2, 3, 7, 8, 9, 1023, 1024, 1025])
def test_native_rows_equal_numpy_at_every_working_set_size(n):
    """The halving sequence is a function of n: powers of two, their
    neighbours and the one-key set are its edge cases."""
    sorted_keys, rows = _working_set(n, seed=n)
    keys = np.concatenate([sorted_keys, sorted_keys[::-1], sorted_keys[:1]])
    got, n_missing, _, _ = native.lookup_rows(sorted_keys, rows, keys, 2)
    assert n_missing == 0
    assert np.array_equal(got, st._lookup_rows_numpy(sorted_keys, rows, keys))


@pytest.mark.parametrize("size", ["under", "at", "over"])
def test_the_key_count_selects_the_body(size):
    m = FLOOR + {"under": -1, "at": 0, "over": 1}[size]
    sorted_keys, rows = _working_set(5000)
    keys = sorted_keys[np.random.default_rng(3).integers(0, 5000, size=m)]
    before = _counters()
    got = st.lookup_rows(sorted_keys, rows, keys)
    native_keys, numpy_keys = (a - b for a, b in zip(_counters(), before))
    assert got.dtype == np.int32
    assert np.array_equal(got, st._lookup_rows_numpy(sorted_keys, rows, keys))
    if size == "under" or not native.available():
        assert (native_keys, numpy_keys) == (0, m)
    else:
        assert (native_keys, numpy_keys) == (m, 0)
        assert STAT_GET("table.lookup.threads") >= 1


@needs_native
@pytest.mark.parametrize(
    "case", ["duplicates", "all_smallest", "all_largest", "int64", "strided"]
)
def test_query_shapes(case):
    sorted_keys, rows = _working_set(9000, seed=5)
    m = FLOOR + 37
    if case == "duplicates":
        keys = np.repeat(sorted_keys[[4, 4000, 8999]], m // 3 + 1)[:m]
    elif case == "all_smallest":
        keys = np.full(m, sorted_keys[0])
    elif case == "all_largest":
        keys = np.full(m, sorted_keys[-1])
    elif case == "int64":
        keys = sorted_keys[np.arange(m) % 9000].astype(np.int64)
    else:
        keys = np.tile(sorted_keys, 2)[::2][:m]
        assert not keys.flags.c_contiguous
    before = _counters()
    got = st.lookup_rows(sorted_keys, rows, keys)
    assert _counters()[0] - before[0] == m
    assert np.array_equal(got, st._lookup_rows_numpy(sorted_keys, rows, keys))


# ---- a missing key ----------------------------------------------------------


def _raised(fn, *args):
    with pytest.raises(KeyError) as e:
        fn(*args)
    return e.value.args[0]


@needs_native
@pytest.mark.parametrize("where", ["below", "above", "between", "many"])
def test_a_missing_key_raises_the_numpy_bodys_keyerror(where):
    sorted_keys, rows = _working_set(6000, seed=9)
    sorted_keys = sorted_keys * np.uint64(2) + np.uint64(10)  # gaps, room below
    m = FLOOR + 5
    keys = sorted_keys[np.random.default_rng(1).integers(0, 6000, size=m)]
    if where == "below":
        keys[m // 2] = sorted_keys[0] - np.uint64(1)
    elif where == "above":
        keys[m - 1] = sorted_keys[-1] + np.uint64(1)
    elif where == "between":
        keys[0] = sorted_keys[77] + np.uint64(1)
    else:
        # more than five, in every thread's slice, not in key order
        bad = np.arange(7, m, 97)
        keys[bad] = sorted_keys[bad % 6000] + np.uint64(1)
        keys[bad[3]] = np.uint64(3)
        keys[bad[1]] = sorted_keys[-1] + np.uint64(9)
    want = _raised(st._lookup_rows_numpy, sorted_keys, rows, keys)
    assert "batch keys not in pass working set (e.g. [" in want
    assert _raised(st.lookup_rows, sorted_keys, rows, keys) == want
    for threads in (1, 3, 16):
        _, n_missing, first, _ = native.lookup_rows(
            sorted_keys, rows, keys, threads
        )
        missing = np.flatnonzero(~np.isin(keys, sorted_keys))
        assert n_missing == len(missing)
        assert np.array_equal(first, missing[:5])


def test_a_missing_key_below_the_floor():
    sorted_keys, rows = _working_set(50)
    keys = np.array([sorted_keys[3], 0, sorted_keys[7]], dtype=np.uint64)
    msg = _raised(st.lookup_rows, sorted_keys, rows, keys)
    assert msg == "1 batch keys not in pass working set (e.g. [0])"


# ---- empty sets, no library, both working sets ------------------------------


@pytest.mark.parametrize("m", [0, FLOOR + 1])
def test_empty_working_set_and_empty_query(m):
    none = np.zeros(0, np.uint64)
    sorted_keys, rows = _working_set(10)
    keys = np.full(m, sorted_keys[2])
    if m:
        with pytest.raises(KeyError, match=f"{m} batch keys but .* empty"):
            st.lookup_rows(none, np.zeros(0, np.int64), keys)
    out = st.lookup_rows(none, np.zeros(0, np.int64), none)
    assert out.dtype == np.int32 and len(out) == 0
    out = st.lookup_rows(sorted_keys, rows, none)
    assert out.dtype == np.int32 and len(out) == 0


def test_without_the_library_the_numpy_body_runs(monkeypatch):
    monkeypatch.setattr(native, "_load", lambda: None)
    assert not native.available()
    sorted_keys, rows = _working_set(700)
    m = 2 * FLOOR
    keys = sorted_keys[np.arange(m) % 700]
    before = _counters()
    got = st.lookup_rows(sorted_keys, rows, keys)
    assert np.array_equal(got, rows[np.arange(m) % 700].astype(np.int32))
    assert tuple(a - b for a, b in zip(_counters(), before)) == (0, m)
    keys[5] = sorted_keys[-1] + np.uint64(1)
    assert _raised(st.lookup_rows, sorted_keys, rows, keys).startswith(
        "1 batch keys not in pass working set"
    )


@pytest.mark.parametrize("n_mesh_shards", [1, 2])
def test_both_working_sets_share_the_body_and_the_rows(n_mesh_shards):
    """Two mesh shards: ``row_of_sorted`` is shard * capacity + rank, not
    the identity."""
    rng = np.random.default_rng(4)
    pass_keys = rng.integers(1, 2**62, size=3000, dtype=np.uint64)
    table = HostSparseTable(LAYOUT, OPT, n_shards=2, seed=0)
    ws = PassWorkingSet(n_mesh_shards=n_mesh_shards)
    ws.add_keys(pass_keys)
    ws.finalize(table, round_to=8)
    dws = DistributedWorkingSet(_OneRankTransport(), n_mesh_shards=n_mesh_shards)
    dws.add_keys(pass_keys)
    dws.finalize(HostSparseTable(LAYOUT, OPT, n_shards=2, seed=0), round_to=8)
    if n_mesh_shards == 2:
        assert not np.array_equal(ws.row_of_sorted, np.arange(ws.n_keys))
    keys = pass_keys[rng.integers(0, 3000, size=FLOOR + 11)]
    for few in (keys[:9], keys):
        want = st._lookup_rows_numpy(ws.sorted_keys, ws.row_of_sorted, few)
        assert np.array_equal(ws.lookup(few), want)
        assert np.array_equal(dws.lookup(few), want)
