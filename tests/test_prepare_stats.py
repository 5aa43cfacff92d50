"""Native pass-prepare sweep (pbx_block_stats): the one-call counter sweep
must equal the per-block numpy unique/bincount it replaces (the reference
equalizes pass shapes with counters + one allreduce, data_set.cc:2069-2135
— this is the counter side, off the Python critical path)."""

import types

import numpy as np
import pytest

from paddlebox_tpu.train import resident_step
from paddlebox_tpu.utils import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native tier unavailable"
)


def _synthetic_pass(rng, n_records=200, ns=4, cap=64, max_keys=7):
    counts = rng.integers(1, max_keys, n_records).astype(np.int64)
    base = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int64)
    total = int(counts.sum())
    rows = rng.integers(0, ns * cap, total).astype(np.int32)
    return rows, base, counts, ns, cap


def _oracle(rows, base, counts, blocks, cap, ns):
    Ls, bms = [], []
    for blk in blocks:
        rs = np.concatenate(
            [rows[base[r] : base[r] + counts[r]] for r in blk]
        ) if len(blk) else np.zeros(0, np.int32)
        Ls.append(len(rs))
        if len(rs):
            uniq = np.unique(rs)
            bms.append(int(np.bincount(uniq // cap, minlength=ns).max()))
        else:
            bms.append(0)
    return np.array(Ls), np.array(bms)


def test_block_stats_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    rows, base, counts, ns, cap = _synthetic_pass(rng)
    blocks = rng.integers(0, 200, (12, 16)).astype(np.int64)
    L, bm = native.block_stats(rows, base, counts, blocks, cap, ns)
    oL, obm = _oracle(rows, base, counts, blocks, cap, ns)
    np.testing.assert_array_equal(L, oL)
    np.testing.assert_array_equal(bm, obm)


def test_block_stats_single_shard_counts_total_uniques():
    """ns=1 is the single-device ensure() form: bmax == total uniques."""
    rng = np.random.default_rng(1)
    rows, base, counts, ns, cap = _synthetic_pass(rng, ns=1, cap=256)
    blocks = rng.integers(0, 200, (5, 32)).astype(np.int64)
    _, bm = native.block_stats(rows, base, counts, blocks, cap, 1)
    for i, blk in enumerate(blocks):
        rs = np.concatenate([rows[base[r] : base[r] + counts[r]] for r in blk])
        assert bm[i] == len(np.unique(rs))


def test_block_stats_rejects_out_of_range():
    rng = np.random.default_rng(2)
    rows, base, counts, ns, cap = _synthetic_pass(rng)
    bad = np.array([[0, 1, 10_000]], dtype=np.int64)  # record id OOR
    with pytest.raises(ValueError):
        native.block_stats(rows, base, counts, bad, cap, ns)


def test_block_stats_rejects_key_span_past_rows():
    """A record whose (base, count) span runs past the row array must be
    refused before the gather memcpy reads it (ADVICE r5)."""
    rng = np.random.default_rng(5)
    rows, base, counts, ns, cap = _synthetic_pass(rng)
    blocks = np.arange(16, dtype=np.int64)[None]
    for bad_base, bad_count in ((len(rows) - 1, 2), (-1, 1), (0, len(rows) + 1)):
        b, c = base.copy(), counts.copy()
        b[3], c[3] = bad_base, bad_count
        with pytest.raises(ValueError, match="key span"):
            native.block_stats(rows, b, c, blocks, cap, ns)


def _mk_rp(rng, ns, cap):
    rows, base, counts, _, _ = _synthetic_pass(rng, ns=ns, cap=cap)
    rp = types.SimpleNamespace(
        _host_rows=rows,
        _key_counts=counts,
        _mesh_cache={},
        _uniq_cache={},
        store=types.SimpleNamespace(u64_base=base),
        ws=types.SimpleNamespace(capacity=cap, n_mesh_shards=ns),
        transport=None,
        bucket=32,
        L_pad=0,
        K_pad=0,
        U_pad=0,
        n_table_rows=ns * cap,
        _seq=0,
    )
    return rp


def test_ensure_sharded_native_equals_python_fallback(monkeypatch):
    """The frozen pads must be identical whichever sweep computed them."""
    rng = np.random.default_rng(3)
    batches = [rng.integers(0, 200, 24) for _ in range(6)]

    rp_nat = _mk_rp(np.random.default_rng(3), ns=4, cap=64)
    resident_step.ensure_sharded(rp_nat, batches, n_devices=4)

    rp_py = _mk_rp(np.random.default_rng(3), ns=4, cap=64)
    monkeypatch.setattr(native, "available", lambda: False)
    resident_step.ensure_sharded(rp_py, batches, n_devices=4)

    assert (rp_nat.L_pad, rp_nat.K_pad) == (rp_py.L_pad, rp_py.K_pad)
    assert rp_nat._mesh_cache == rp_py._mesh_cache
    assert rp_nat.L_pad > 0 and rp_nat.K_pad > 0


def test_ensure_native_equals_python_fallback(monkeypatch):
    """Single-device ensure(): L_pad/U_pad identical under both sweeps."""
    rng = np.random.default_rng(4)
    batches = [rng.integers(0, 200, 16) for _ in range(5)]

    rp_nat = _mk_rp(np.random.default_rng(4), ns=1, cap=512)
    resident_step.ResidentPass.ensure(rp_nat, batches)

    rp_py = _mk_rp(np.random.default_rng(4), ns=1, cap=512)
    monkeypatch.setattr(native, "available", lambda: False)
    resident_step.ResidentPass.ensure(rp_py, batches)

    assert (rp_nat.L_pad, rp_nat.U_pad) == (rp_py.L_pad, rp_py.U_pad)
    assert rp_nat._uniq_cache == rp_py._uniq_cache
    assert rp_nat.U_pad > 1
