"""Resident device feed (train/resident_step.py): parity with the classic
host-packed path on ragged data, plus mode coverage (eval, NaN guard,
wrap-around lockstep batches).

The resident tier reuses make_train_step's body, so any numeric divergence
must come from batch assembly — these tests pin assembly equivalence
through full train_pass outcomes (losses, trained table, AUC)."""

from __future__ import annotations

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddlebox_tpu import config
from paddlebox_tpu.data import BoxPSDataset, SlotInfo, SlotSchema
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.table import (
    HostSparseTable,
    SparseOptimizerConfig,
    ValueLayout,
)
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig

S, B, N = 5, 8, 64


def _schema():
    return SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(S)],
        label_slot="label",
    )


def _write_files(tmp_path, seed=0, n=N, vocab=300):
    """Ragged slot files: 1-3 keys per slot (the line protocol forbids
    zero-count slots — generators pad, slot_parser.cc:205)."""
    rng = np.random.default_rng(seed)
    tmp_path.mkdir(parents=True, exist_ok=True)
    path = tmp_path / "part-000.txt"
    with open(path, "w") as f:
        for _ in range(n):
            parts = [f"1 {float(rng.integers(0, 2))}"]
            for _s in range(S):
                k = int(rng.integers(1, 4))
                vals = rng.integers(1, vocab, k)
                parts.append(f"{k} " + " ".join(str(v) for v in vals))
            f.write(" ".join(parts) + "\n")
    return [str(path)]


def _fresh(tmp_path, seed=0, batch_size=B, embedx=4):
    schema = _schema()
    layout = ValueLayout(embedx_dim=embedx)
    table = HostSparseTable(
        layout, SparseOptimizerConfig(embedx_threshold=0.0), n_shards=2, seed=0
    )
    ds = BoxPSDataset(schema, table, batch_size=batch_size, shuffle_mode="none")
    ds.set_filelist(_write_files(tmp_path, seed))
    ds.load_into_memory()
    ds.begin_pass(round_to=8)
    model = DeepFM(
        num_slots=S, feat_width=layout.pull_width, embedx_dim=embedx, hidden=(8,)
    )
    cfg = TrainStepConfig(
        num_slots=S,
        batch_size=batch_size,
        layout=layout,
        sparse_opt=SparseOptimizerConfig(embedx_threshold=0.0),
        auc_buckets=100,
    )
    tr = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-2))
    tr.init_params(jax.random.PRNGKey(0))
    return ds, tr, table


def _run(tmp_path, resident: bool, n_batches, seed=0, eval_after=False):
    prev_flag = config.get_flag("enable_resident_feed")
    config.set_flag("enable_resident_feed", 1 if resident else 0)
    try:
        ds, tr, table = _fresh(tmp_path, seed)
        out = tr.train_pass(ds, n_batches=n_batches)
        trained = np.asarray(tr.trained_table())
        extra = None
        if eval_after:
            tr.set_test_mode(True)
            eval_out = tr.train_pass(ds, n_batches=n_batches)
            tr.set_test_mode(False)
            after = np.asarray(tr.trained_table())
            extra = (eval_out, after)
        ds.end_pass(tr.trained_table())
        return out, trained, tr, extra
    finally:
        config.set_flag("enable_resident_feed", prev_flag)


def test_resident_matches_classic_full_pass(tmp_path):
    """Losses, AUC, and the trained table agree with host packing (ragged
    records, empty slots, cross-slot duplicate keys)."""
    out_c, table_c, _, _ = _run(tmp_path / "c", resident=False, n_batches=8)
    out_r, table_r, _, _ = _run(tmp_path / "r", resident=True, n_batches=8)
    assert out_r["batches"] == out_c["batches"] == 8
    assert np.isclose(out_r["loss"], out_c["loss"], atol=1e-5)
    assert np.isclose(out_r["auc"], out_c["auc"], atol=1e-6)
    np.testing.assert_allclose(table_r, table_c, atol=1e-4)


def test_resident_wraparound_lockstep(tmp_path):
    """More batches than the pass holds: wrap-around indices must reuse
    records exactly like the classic path (equalized lockstep counts)."""
    out_c, table_c, _, _ = _run(tmp_path / "c", resident=False, n_batches=13)
    out_r, table_r, _, _ = _run(tmp_path / "r", resident=True, n_batches=13)
    assert np.isclose(out_r["loss"], out_c["loss"], atol=1e-5)
    np.testing.assert_allclose(table_r, table_c, atol=1e-4)


def test_resident_eval_mode_is_identity(tmp_path):
    """SetTestMode parity via the resident path: an eval pass changes
    neither the table nor the dense params, and still produces metrics."""
    out, trained, tr, extra = _run(
        tmp_path, resident=True, n_batches=4, eval_after=True
    )
    eval_out, after = extra
    np.testing.assert_array_equal(trained, after)
    assert 0.0 <= eval_out["auc"] <= 1.0 and eval_out["batches"] == 4


def test_resident_scan_chunking_matches_per_batch(tmp_path):
    """resident_scan_batches=1 (per-batch dispatch) and =4 (scan) produce
    identical results — the scan is pure restructuring."""
    prev_k = config.get_flag("resident_scan_batches")
    try:
        config.set_flag("resident_scan_batches", 1)
        out_1, table_1, _, _ = _run(tmp_path / "a", resident=True, n_batches=8)
        config.set_flag("resident_scan_batches", 4)
        out_4, table_4, _, _ = _run(tmp_path / "b", resident=True, n_batches=8)
    finally:
        config.set_flag("resident_scan_batches", prev_k)
    assert np.isclose(out_1["loss"], out_4["loss"], atol=1e-6)
    np.testing.assert_allclose(table_1, table_4, atol=1e-5)


def test_resident_nan_containment(tmp_path):
    """check_nan inside the scan: a poisoned batch is skipped (table
    untouched by it) and reported, matching the classic path."""
    schema = _schema()
    layout = ValueLayout(embedx_dim=4)

    results = {}
    prev_flag = config.get_flag("enable_resident_feed")
    for name, resident in (("classic", 0), ("resident", 1)):
        config.set_flag("enable_resident_feed", resident)
        try:
            table = HostSparseTable(
                layout, SparseOptimizerConfig(embedx_threshold=0.0), n_shards=2,
                seed=0,
            )
            ds = BoxPSDataset(schema, table, batch_size=B, shuffle_mode="none")
            # tiny vocab: batch 0's pushed keys reappear later -> trigger
            ds.set_filelist(_write_files(tmp_path / name, vocab=20))
            ds.load_into_memory()
            ds.begin_pass(round_to=8)
            model = DeepFM(
                num_slots=S, feat_width=layout.pull_width, embedx_dim=4,
                hidden=(8,),
            )

            class PoisonModel:
                """Poison by data, deterministically across both paths:
                feats[..., 0] is log(show+1); a fresh table has show 0
                everywhere, so batch 0 is clean, and once batch 0's push
                lands, key reuse (tiny vocab) makes later batches carry
                positive shows -> NaN -> skipped. Exercises the gflat/param
                zeroing inside the lax.scan body, per iteration."""

                def init(self, rng):
                    return model.init(rng)

                def apply(self, p, feats, dense=None):
                    logits = model.apply(p, feats, dense)
                    trigger = jnp.sum(feats[:, :, 0], axis=1) > 0.3
                    return jnp.where(trigger, jnp.nan, logits)

            cfg = TrainStepConfig(
                num_slots=S, batch_size=B, layout=layout,
                sparse_opt=SparseOptimizerConfig(embedx_threshold=0.0),
                auc_buckets=100, check_nan=True,
            )
            tr = CTRTrainer(PoisonModel(), cfg, dense_opt=optax.adam(1e-2))
            tr.init_params(jax.random.PRNGKey(0))
            out = tr.train_pass(ds, n_batches=4)
            results[name] = (out["nan_batches"], out["loss"])
        finally:
            config.set_flag("enable_resident_feed", prev_flag)
    # the trigger must actually fire (not a vacuous no-NaN comparison) and
    # batch 0 must stay clean (fresh table: shows are all zero)
    assert 0 < results["resident"][0] < 4
    assert results["classic"] == results["resident"]


def test_resident_registry_and_dump_consumers(tmp_path):
    """Registry + on_batch consumers see per-batch metrics identical to the
    classic path (stacked-slice delivery)."""
    from paddlebox_tpu.metrics.registry import MetricRegistry

    per_batch = {}
    prev_flag = config.get_flag("enable_resident_feed")
    for name, resident in (("classic", 0), ("resident", 1)):
        config.set_flag("enable_resident_feed", resident)
        try:
            schema = _schema()
            layout = ValueLayout(embedx_dim=4)
            table = HostSparseTable(
                layout, SparseOptimizerConfig(embedx_threshold=0.0), n_shards=2,
                seed=0,
            )
            ds = BoxPSDataset(schema, table, batch_size=B, shuffle_mode="none")
            ds.set_filelist(_write_files(tmp_path / name))
            ds.load_into_memory()
            ds.begin_pass(round_to=8)
            model = DeepFM(
                num_slots=S, feat_width=layout.pull_width, embedx_dim=4,
                hidden=(8,),
            )
            cfg = TrainStepConfig(
                num_slots=S, batch_size=B, layout=layout,
                sparse_opt=SparseOptimizerConfig(embedx_threshold=0.0),
                auc_buckets=100,
            )
            reg = MetricRegistry()
            reg.init_metric("auc", "auc", phase=-1)
            tr = CTRTrainer(
                model, cfg, dense_opt=optax.adam(1e-2), metric_registry=reg
            )
            tr.init_params(jax.random.PRNGKey(0))
            seen = []
            tr.train_pass(
                ds, n_batches=4,
                on_batch=lambda i, m: seen.append((i, float(m["loss"]))),
            )
            per_batch[name] = (seen, reg.get_metric("auc")["auc"])
        finally:
            config.set_flag("enable_resident_feed", prev_flag)
    (seen_c, auc_c), (seen_r, auc_r) = per_batch["classic"], per_batch["resident"]
    assert [i for i, _ in seen_r] == [i for i, _ in seen_c] == list(range(4))
    for (_, lc), (_, lr) in zip(seen_c, seen_r):
        assert np.isclose(lc, lr, atol=1e-5)
    assert np.isclose(auc_c, auc_r, atol=1e-6)


def test_resident_mesh_matches_host_packed_mesh(tmp_path):
    """Single-host mesh: the device-built route buckets (sort-based shard
    grouping) train to the same losses/table as the host-packed
    pack_batch_sharded path — internal bucket order may differ, sums
    must not."""
    from paddlebox_tpu.parallel import make_mesh

    from paddlebox_tpu.metrics.registry import MetricRegistry

    def run(resident):
        prev = config.get_flag("enable_resident_feed")
        config.set_flag("enable_resident_feed", resident)
        try:
            schema = _schema()
            layout = ValueLayout(embedx_dim=4)
            table = HostSparseTable(
                layout, SparseOptimizerConfig(embedx_threshold=0.0),
                n_shards=4, seed=0,
            )
            plan = make_mesh(4)
            ds = BoxPSDataset(
                schema, table, batch_size=16, n_mesh_shards=4,
                shuffle_mode="none",
            )
            ds.set_filelist(_write_files(tmp_path / f"r{resident}", n=64))
            ds.load_into_memory()
            ds.begin_pass(round_to=16)
            model = DeepFM(
                num_slots=S, feat_width=layout.pull_width, embedx_dim=4,
                hidden=(8,),
            )
            cfg = TrainStepConfig(
                num_slots=S, batch_size=4, layout=layout,
                sparse_opt=SparseOptimizerConfig(embedx_threshold=0.0),
                auc_buckets=100, axis_name=plan.axis,
            )
            reg = MetricRegistry()
            reg.init_metric("auc", "auc", phase=-1)
            tr = CTRTrainer(
                model, cfg, dense_opt=optax.adam(1e-2), plan=plan,
                metric_registry=reg,
            )
            tr.init_params(jax.random.PRNGKey(0))
            out = tr.train_pass(ds)
            return out, np.asarray(tr.trained_table()), reg.get_metric("auc")
        finally:
            config.set_flag("enable_resident_feed", prev)

    out_h, table_h, reg_h = run(0)
    out_r, table_r, reg_r = run(1)
    assert out_r["batches"] == out_h["batches"]
    assert np.isclose(out_r["loss"], out_h["loss"], atol=1e-5)
    assert np.isclose(out_r["auc"], out_h["auc"], atol=1e-6)
    np.testing.assert_allclose(table_r, table_h, atol=1e-4)
    # consumers must see EVERY device's slice of each batch (a wrong
    # scan-axis spec would hand the registry 1/n_dev of the data)
    assert reg_r["ins_num"] == reg_h["ins_num"] == 64
    assert np.isclose(reg_r["auc"], reg_h["auc"], atol=1e-6)


def test_resident_mesh_dense_features_match(tmp_path):
    """Dense float features flow through the mesh resident build (a feed
    that silently dropped them would diverge from the host-packed path)."""
    from paddlebox_tpu.parallel import make_mesh

    def write(tmp):
        rng = np.random.default_rng(3)
        tmp.mkdir(parents=True, exist_ok=True)
        p = tmp / "d.txt"
        with open(p, "w") as f:
            for _ in range(32):
                ks = rng.integers(1, 100, S)
                dvals = rng.random(3)
                f.write(
                    f"1 {int(ks[0]) % 2}.0 "
                    + "3 " + " ".join(f"{v:.3f}" for v in dvals) + " "
                    + " ".join(f"1 {k}" for k in ks)
                    + "\n"
                )
        return [str(p)]

    schema = SlotSchema(
        [
            SlotInfo("label", type="float", dense=True, dim=1),
            SlotInfo("dfeat", type="float", dense=True, dim=3),
        ]
        + [SlotInfo(f"s{i}") for i in range(S)],
        label_slot="label",
    )

    class DenseAwareModel:
        def __init__(self, base):
            self.base = base

        def init(self, rng):
            p = self.base.init(rng)
            p["dw"] = jnp.ones((3,), jnp.float32) * 0.5
            return p

        def apply(self, p, feats, dense=None):
            logit = self.base.apply(
                {k: v for k, v in p.items() if k != "dw"}, feats, None
            )
            if dense is not None:
                logit = logit + dense @ p["dw"]
            return logit

    def run(resident):
        prev = config.get_flag("enable_resident_feed")
        config.set_flag("enable_resident_feed", resident)
        try:
            layout = ValueLayout(embedx_dim=4)
            table = HostSparseTable(
                layout, SparseOptimizerConfig(embedx_threshold=0.0),
                n_shards=4, seed=0,
            )
            plan = make_mesh(4)
            ds = BoxPSDataset(
                schema, table, batch_size=16, n_mesh_shards=4,
                shuffle_mode="none",
            )
            ds.set_filelist(write(tmp_path / f"r{resident}"))
            ds.load_into_memory()
            ds.begin_pass(round_to=16)
            base = DeepFM(
                num_slots=S, feat_width=layout.pull_width, embedx_dim=4,
                hidden=(8,),
            )
            cfg = TrainStepConfig(
                num_slots=S, batch_size=4, layout=layout,
                sparse_opt=SparseOptimizerConfig(embedx_threshold=0.0),
                auc_buckets=100, axis_name=plan.axis,
            )
            tr = CTRTrainer(
                DenseAwareModel(base), cfg, dense_opt=optax.adam(1e-2),
                plan=plan, dense_slot="dfeat", dense_dim=3,
            )
            tr.init_params(jax.random.PRNGKey(0))
            out = tr.train_pass(ds)
            return out, np.asarray(tr.trained_table())
        finally:
            config.set_flag("enable_resident_feed", prev)

    out_h, table_h = run(0)
    out_r, table_r = run(1)
    assert np.isclose(out_r["loss"], out_h["loss"], atol=1e-5)
    np.testing.assert_allclose(table_r, table_h, atol=1e-4)


def test_prepare_pass_prefreezes_shapes(tmp_path):
    """After prepare_pass over the full partition, train_pass must not grow
    the pads or build a second superstep (the warm-start contract the
    benchmark relies on to keep compiles out of its timed window)."""
    ds, tr, _ = _fresh(tmp_path)
    tr.prepare_pass(ds, n_batches=8)
    rp = tr._get_resident(ds)
    pads_before = (rp.L_pad, rp.U_pad)
    assert pads_before[0] > 0 and pads_before[1] > 0
    tr.train_pass(ds, n_batches=8)
    assert (rp.L_pad, rp.U_pad) == pads_before
    assert len(tr._sstep_cache) == 1  # one train superstep, no regrowth


def test_resident_rows_by_the_native_lookup_equal_the_numpy_bodys(
    tmp_path, monkeypatch
):
    """A store large enough to take the native body resolves to the rows
    the numpy body gives, and says how fast and by which body."""
    from paddlebox_tpu.table import sparse_table
    from paddlebox_tpu.train.resident_step import ResidentPass
    from paddlebox_tpu.utils import native
    from paddlebox_tpu.utils.monitor import STAT_GET, STAT_RESET

    if not native.available():
        pytest.skip("native tier unavailable")
    schema = _schema()
    table = HostSparseTable(
        ValueLayout(embedx_dim=4), SparseOptimizerConfig(), n_shards=2, seed=0
    )
    ds = BoxPSDataset(schema, table, batch_size=B, shuffle_mode="none")
    ds.set_filelist(_write_files(tmp_path, n=500, vocab=5000))
    ds.load_into_memory()
    ds.begin_pass(round_to=8)
    n_keys = len(ds.store.u64_values)
    assert n_keys >= sparse_table._LOOKUP_NATIVE_FLOOR

    def build():
        ds.store.invalidate_rows()
        STAT_RESET("resident.resolve_keys_per_s")
        before = (
            STAT_GET("table.lookup.native_keys"),
            STAT_GET("table.lookup.numpy_keys"),
        )
        rp = ResidentPass(ds.store, ds.ws, schema, label_slot="label")
        assert STAT_GET("resident.resolve_keys_per_s") > 0
        return rp._host_rows, (
            STAT_GET("table.lookup.native_keys") - before[0],
            STAT_GET("table.lookup.numpy_keys") - before[1],
        )

    rows_native, counted = build()
    assert counted == (n_keys, 0)
    monkeypatch.setattr(native, "_load", lambda: None)
    rows_numpy, counted = build()
    assert counted == (0, n_keys)
    assert rows_native.dtype == np.int32
    assert np.array_equal(rows_native, rows_numpy)


# ---- _ragged_rows alone, against a plain loop over the segments ------------


def _ragged_case(name):
    """(rows, off [N, S+1], idx [B], L_pad) of one batch shape."""
    rng = np.random.default_rng(27)
    n_rec, s, b = 40, 4, 6
    counts = rng.integers(0, 4, (n_rec, s))
    idx = rng.integers(0, n_rec, b)
    if name.startswith("ones"):
        counts[:] = 1
    elif name == "empty_batch":
        counts[idx] = 0
    elif name.startswith("zero_head_mid_tail"):
        counts[idx[0], 0] = 0  # segment 0
        counts[idx[2], 1] = counts[idx[3], 1] = 0  # two in a row, mid-stream
        counts[idx[-1], s - 1] = 0  # segment S*B-1
        counts[idx[-2], s - 1] = 0
    elif name == "repeated_records":
        idx = np.array([7, 7, 3, 7, 3, 11])
        counts[7] = [2, 0, 3, 1]
    elif name == "one_slot_one_record":
        counts, idx, s, b = counts[:, :1] + 1, idx[:1], 1, 1
    per = counts.sum(1)
    base = np.concatenate([[0], np.cumsum(per)[:-1]])
    off = base[:, None] + np.concatenate(
        [np.zeros((n_rec, 1), np.int64), np.cumsum(counts, 1)], axis=1
    )
    rows = rng.integers(0, 1000, int(per.sum()) + 1).astype(np.int32)
    l_real = int(per[idx].sum())
    l_pad = {"exact": l_real, "short": l_real - 3}.get(
        name.rsplit("-", 1)[-1], l_real + 5
    )
    return rows, off.astype(np.int32), idx.astype(np.int32), max(l_pad, 1)


def _ragged_rows_loop(rows, off, idx, l_pad, pad_value):
    s, b = off.shape[1] - 1, len(idx)
    rows_flat, segments = [], []
    for slot in range(s):
        for ins, rec in enumerate(idx):
            for k in range(off[rec, slot], off[rec, slot + 1]):
                rows_flat.append(rows[k])
                segments.append(slot * b + ins)
    n = min(len(rows_flat), l_pad)
    tail = l_pad - n
    return (
        np.array(rows_flat[:n] + [pad_value] * tail, np.int32),
        np.array(segments[:n] + [s * b] * tail, np.int32),
        np.arange(l_pad) < n,
    )


@pytest.mark.parametrize("pad", ["pad_row", "mesh_sentinel"])
@pytest.mark.parametrize(
    "name",
    [
        "ragged", "ragged-exact", "ragged-short",
        "zero_head_mid_tail", "zero_head_mid_tail-exact",
        "ones", "ones-exact", "repeated_records", "empty_batch",
        "one_slot_one_record",
    ],
)
def test_ragged_rows_equals_a_loop_over_the_segments(name, pad):
    from paddlebox_tpu.train.resident_step import _ragged_rows

    rows, off, idx, l_pad = _ragged_case(name)
    s, b = off.shape[1] - 1, len(idx)
    # the one-chip tier pads with a real row of the table, the mesh tier
    # with ns*cap, one past every row id (a traced scalar there)
    pad_value = 999 if pad == "pad_row" else jnp.int32(1000)
    got = jax.jit(_ragged_rows, static_argnums=(2, 3, 4))(
        jnp.asarray(rows), jnp.asarray(off)[idx], s, b, l_pad, pad_value
    )
    want = _ragged_rows_loop(rows, off, idx, l_pad, int(pad_value))
    for g, w, what in zip(got, want, ("rows_flat", "segments", "valid")):
        assert g.dtype == w.dtype and g.shape == w.shape, what
        np.testing.assert_array_equal(np.asarray(g), w, err_msg=what)
    if name == "zero_head_mid_tail":  # the case is what it says
        lens = np.diff(off[idx], axis=1).T.reshape(-1)
        assert lens[0] == 0 and lens[-1] == 0 and lens[-2] == 0
        assert ((lens[1:-1] == 0) & (np.roll(lens, 1)[1:-1] == 0)).any()
