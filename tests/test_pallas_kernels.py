"""Pallas sparse kernels (interpret mode on CPU; chip_smoke.py compiles
them on the TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.ops.pallas_kernels import (
    pull_rows_pallas,
    write_rows_pallas,
)


def test_gather_matches_take():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(128, 22)).astype(np.float32))
    rows = jnp.asarray(rng.integers(0, 128, 64).astype(np.int32))  # dups fine
    got = pull_rows_pallas(table, rows, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(table)[np.asarray(rows)], rtol=1e-6
    )


def test_writeback_matches_scatter_set():
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.normal(size=(96, 20)).astype(np.float32))
    uniq = jnp.asarray(rng.permutation(96)[:24].astype(np.int32))
    new = jnp.asarray(rng.normal(size=(24, 20)).astype(np.float32))
    got = write_rows_pallas(jnp.array(table), uniq, new, interpret=True)
    want = np.asarray(table).copy()
    want[np.asarray(uniq)] = np.asarray(new)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


def test_writeback_repeated_pad_row_identical_content():
    """The packer repeats the padding row with identical updated contents —
    repeated writes of the same value are well-defined."""
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.normal(size=(32, 8)).astype(np.float32))
    pad = 31
    rows = jnp.asarray([3, pad, 7, pad, pad, pad, pad, pad], np.int32)
    pad_content = jnp.asarray(rng.normal(size=(8,)).astype(np.float32))
    new = jnp.stack(
        [jnp.full((8,), 1.0), pad_content, jnp.full((8,), 2.0)]
        + [pad_content] * 5
    ).astype(jnp.float32)
    got = np.asarray(write_rows_pallas(jnp.array(table), rows, new, interpret=True))
    np.testing.assert_allclose(got[3], np.full(8, 1.0))
    np.testing.assert_allclose(got[7], np.full(8, 2.0))
    np.testing.assert_allclose(got[pad], np.asarray(pad_content), rtol=1e-6)


def test_flag_gating():
    """The legacy use_pallas_sparse opt-in (now the builtin plan's fallback
    preference) must not engage off-TPU, with unaligned widths, or with
    unaligned index counts — the plan's eligibility clamp, exercised
    through the same _impl_for lookup the pull/push ops use."""
    from paddlebox_tpu import config
    from paddlebox_tpu.ops.kernel_plan import invalidate_plan
    from paddlebox_tpu.ops.pull_push import _impl_for

    t_ok = jnp.zeros((64, 128))
    t_narrow = jnp.zeros((64, 21))
    on_tpu = jax.default_backend() == "tpu"  # conftest forces CPU
    config.set_flag("kernel_plan_path", "off")  # builtin defaults only
    config.set_flag("use_pallas_sparse", True)
    invalidate_plan()
    try:
        assert (_impl_for("pull", t_ok, 64) == "pallas") == on_tpu
        assert _impl_for("pull", t_narrow, 64) == "native"  # width unaligned
        assert _impl_for("pull", t_ok, 63) == "native"      # U not 8-aligned
        # pallas push is per-row SET: without dedup'd (unique) rows it
        # must clamp to native even where pull would engage
        assert _impl_for("push", t_ok, 64, unique_rows=False) == "native"
    finally:
        config.set_flag("use_pallas_sparse", False)
        config.set_flag("kernel_plan_path", "auto")
        invalidate_plan()
    assert _impl_for("pull", t_ok, 64) == "native"          # flag off
