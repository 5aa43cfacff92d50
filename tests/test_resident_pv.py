"""Resident join-phase (pv) feed: PvPlan + device-resident rank_offset/
ins_weight stacks (train/resident_step.py pv tier).

Equality contract: the resident pv tier, the plan-driven host packer, and
the original record-level pv path all train to the same losses / AUC /
trained table — batch composition is identical by construction (PvPlan is
pack_pv_batches materialized), so any divergence is a batch-assembly bug.
"""

from __future__ import annotations

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")

from paddlebox_tpu import config
from paddlebox_tpu.data import BoxPSDataset, SlotInfo, SlotSchema
from paddlebox_tpu.data.pv_instance import build_pv_plan, pack_pv_batches
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.table import (
    HostSparseTable,
    SparseOptimizerConfig,
    ValueLayout,
)
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig
from tests.test_pv_phase import RankDeepFM, _logkey

S = 3  # sparse slots


def _write_pv_file(path, rng, n_queries=40, n_slots=S):
    lines = []
    for q in range(1, n_queries + 1):
        n_ads = int(rng.integers(1, 4))
        for r in range(1, n_ads + 1):
            keys = rng.integers(1, 150, n_slots)
            label = 1.0 if (keys % 5 == 0).any() else 0.0
            parts = [f"1 {_logkey(q, 222, r)}", f"1 {label}"] + [
                f"1 {k}" for k in keys
            ]
            lines.append(" ".join(parts))
    # fixture writer: path derives from tmp_path (helper param hides it)
    # pbox-lint: disable=IO004
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def _schema():
    return SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(S)],
        label_slot="label",
        parse_logkey=True,
    )


def _fresh(tmp_path, batch_size=16, mesh=None, n_shards=2, check_nan=False):
    layout = ValueLayout(embedx_dim=4)
    table = HostSparseTable(
        layout, SparseOptimizerConfig(embedx_threshold=0.0),
        n_shards=n_shards, seed=0,
    )
    kw = {"n_mesh_shards": n_shards} if mesh is not None else {}
    ds = BoxPSDataset(
        _schema(), table, batch_size=batch_size, shuffle_mode="none", **kw
    )
    path = tmp_path / "pv.txt"
    tmp_path.mkdir(parents=True, exist_ok=True)
    _write_pv_file(str(path), np.random.default_rng(0))
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    ds.begin_pass(round_to=16)
    model = RankDeepFM(S, layout.pull_width, layout.embedx_dim)
    per_dev = batch_size // (mesh.n_devices if mesh is not None else 1)
    cfg = TrainStepConfig(
        num_slots=S, batch_size=per_dev, layout=layout,
        sparse_opt=SparseOptimizerConfig(embedx_threshold=0.0),
        auc_buckets=1000, model_takes_rank_offset=True,
        axis_name=mesh.axis if mesh is not None else None, check_nan=check_nan,
    )
    tr = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-2), plan=mesh)
    tr.init_params(jax.random.PRNGKey(0))
    return ds, tr


def _train_join(tmp_path, resident: bool, plan_feed: bool = True, mesh_n: int = 0):
    """One join-phase pass; returns (metrics, trained table)."""
    prev = config.get_flag("enable_resident_feed")
    config.set_flag("enable_resident_feed", 1 if resident else 0)
    try:
        mesh = None
        if mesh_n:
            from paddlebox_tpu.parallel import make_mesh

            mesh = make_mesh(mesh_n)
        ds, tr = _fresh(tmp_path, mesh=mesh, n_shards=mesh_n or 2)
        if not plan_feed:
            # force the original record-level pv path
            ds.pv_plan = lambda *a, **k: None
        ds.set_current_phase(1)
        ds.preprocess_instance()
        out = tr.train_pass(ds)
        return out, np.asarray(tr.trained_table())
    finally:
        config.set_flag("enable_resident_feed", prev)


def test_pv_plan_materializes_pack_pv_batches(tmp_path):
    """plan.idx/rank_offset/ins_weight == the record-level pack stream."""
    ds, _ = _fresh(tmp_path)
    ds.set_current_phase(1)
    ds.preprocess_instance()
    plan = build_pv_plan(ds.pvs, ds.batch_size, n_devices=2)
    ref = list(pack_pv_batches(ds.pvs, ds.batch_size, n_devices=2))
    assert plan.n_batches == len(ref)
    for i, (recs, ro, w) in enumerate(ref):
        np.testing.assert_array_equal(
            plan.idx[i], [r._store_idx for r in recs]
        )
        np.testing.assert_array_equal(plan.rank_offset[i], ro)
        np.testing.assert_array_equal(plan.ins_weight[i], w)


def test_resident_pv_matches_host_packed(tmp_path):
    """Three-way equality: resident pv == plan-driven packer == original
    record-level path (losses, AUC, trained table)."""
    out_rec, tab_rec = _train_join(tmp_path / "rec", resident=False, plan_feed=False)
    out_pln, tab_pln = _train_join(tmp_path / "pln", resident=False)
    out_res, tab_res = _train_join(tmp_path / "res", resident=True)
    assert out_res["batches"] == out_pln["batches"] == out_rec["batches"]
    assert out_res["ins_num"] == out_pln["ins_num"] == out_rec["ins_num"]
    for a, b in ((out_pln, out_rec), (out_res, out_rec)):
        assert np.isclose(a["loss"], b["loss"], atol=1e-5)
        assert np.isclose(a["auc"], b["auc"], atol=1e-6)
    np.testing.assert_allclose(tab_pln, tab_rec, atol=1e-4)
    np.testing.assert_allclose(tab_res, tab_rec, atol=1e-4)


def test_resident_pv_mesh_matches_host_packed(tmp_path):
    """Single-host mesh join phase: resident pv (device-sharded plan
    stacks) == host-packed mesh pv."""
    out_h, tab_h = _train_join(tmp_path / "h", resident=False, mesh_n=4)
    out_r, tab_r = _train_join(tmp_path / "r", resident=True, mesh_n=4)
    assert out_r["batches"] == out_h["batches"]
    assert out_r["ins_num"] == out_h["ins_num"]
    assert np.isclose(out_r["loss"], out_h["loss"], atol=1e-5)
    assert np.isclose(out_r["auc"], out_h["auc"], atol=1e-6)
    np.testing.assert_allclose(tab_r, tab_h, atol=1e-4)


def test_resident_pv_eval_mode_is_identity(tmp_path):
    """Join-phase EVAL (set_test_mode) on the resident pv tier: metrics
    match the host-packed eval and state returns bit-identical."""
    prev = config.get_flag("enable_resident_feed")
    try:
        outs = {}
        for resident in (0, 1):
            config.set_flag("enable_resident_feed", resident)
            ds, tr = _fresh(tmp_path / f"e{resident}")
            ds.set_current_phase(1)
            ds.preprocess_instance()
            tr.train_pass(ds)  # one trained epoch first
            before = np.asarray(tr.trained_table())
            tr.set_test_mode(True)
            ev = tr.train_pass(ds)
            tr.set_test_mode(False)
            after = np.asarray(tr.trained_table())
            np.testing.assert_array_equal(before, after)  # eval writes nothing
            outs[resident] = ev
        assert np.isclose(outs[1]["loss"], outs[0]["loss"], atol=1e-5)
        assert np.isclose(outs[1]["auc"], outs[0]["auc"], atol=1e-6)
        assert outs[1]["ins_num"] == outs[0]["ins_num"]
    finally:
        config.set_flag("enable_resident_feed", prev)


def test_resident_pv_then_update_phase(tmp_path):
    """The resident join phase hands off to a resident update phase within
    one pass (two-phase lifecycle on the fast tier end-to-end)."""
    prev = config.get_flag("enable_resident_feed")
    config.set_flag("enable_resident_feed", 1)
    try:
        ds, tr = _fresh(tmp_path)
        ds.set_current_phase(1)
        n_pvs = ds.preprocess_instance()
        assert n_pvs == 40
        m_join = tr.train_pass(ds)
        assert np.isfinite(m_join["loss"])
        assert m_join["ins_num"] == ds.memory_data_size()  # ghosts masked
        tr.handoff_table(ds)
        ds.set_current_phase(0)
        ds.postprocess_instance()
        layout = ValueLayout(embedx_dim=4)
        cfg_upd = TrainStepConfig(
            num_slots=S, batch_size=16, layout=layout,
            sparse_opt=SparseOptimizerConfig(embedx_threshold=0.0),
            auc_buckets=1000,
        )
        model2 = DeepFM(
            num_slots=S, feat_width=layout.pull_width, embedx_dim=4, hidden=(8,)
        )
        tr2 = CTRTrainer(model2, cfg_upd, dense_opt=optax.adam(1e-2))
        tr2.init_params(jax.random.PRNGKey(0))
        m_upd = tr2.train_pass(ds)
        assert np.isfinite(m_upd["loss"])
        ds.end_pass(tr2.trained_table())
    finally:
        config.set_flag("enable_resident_feed", prev)
