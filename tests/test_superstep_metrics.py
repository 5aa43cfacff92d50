"""A superstep hands back its K batches' metrics itself
(``resident_step.per_batch_metrics``): the stepper indexes a tuple between two
dispatches and launches nothing.

(a) every superstep builder that runs on the CPU (flat, pv, mesh, pv-mesh,
and the flat one under the toy sequence model for ``counters``): what the
stepper yields a batch equals, digit for digit, the slice of the stacked scan
outputs, and the pass ends in the same table, dense leaves and AUC buckets as
the parent's form of the loop (stacks out of the program, sliced eagerly on
the host). (b) a resident ``train_pass`` of three supersteps completes while
eager primitive dispatch raises; the parent's form does not.
"""

from __future__ import annotations

import contextlib

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")
from jax._src import dispatch  # noqa: E402

from paddlebox_tpu import config  # noqa: E402
from paddlebox_tpu.data import BoxPSDataset  # noqa: E402
from paddlebox_tpu.models import DeepFM  # noqa: E402
from paddlebox_tpu.table import HostSparseTable, SparseOptimizerConfig, ValueLayout  # noqa: E402
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig, resident_step  # noqa: E402
from tests import test_glm_moe_lite as toy  # noqa: E402
from tests import test_resident as flat  # noqa: E402
from tests import test_resident_pv as pv  # noqa: E402

K = 2  # batches a superstep here: three supersteps are six batches


@pytest.fixture(autouse=True)
def _short_supersteps():
    prev = config.get_flag("resident_scan_batches"), config.get_flag("enable_resident_feed")
    config.set_flag("resident_scan_batches", K)
    config.set_flag("enable_resident_feed", 1)
    yield
    config.set_flag("resident_scan_batches", prev[0])
    config.set_flag("enable_resident_feed", prev[1])


def _mesh(n):
    if not n:
        return None
    from paddlebox_tpu.parallel import make_mesh

    return make_mesh(n)


def _ctr(tmp_path, mesh_n=0):
    """test_resident's ragged pass under DeepFM with the NaN guard on (the
    step then carries ``nan_skipped``), on one device or a mesh of four."""
    plan = _mesh(mesh_n)
    layout = ValueLayout(embedx_dim=4)
    opt = SparseOptimizerConfig(embedx_threshold=0.0)
    table = HostSparseTable(layout, opt, n_shards=mesh_n or 2, seed=0)
    kw = {"n_mesh_shards": mesh_n} if mesh_n else {}
    ds = BoxPSDataset(flat._schema(), table, batch_size=16, shuffle_mode="none", **kw)
    ds.set_filelist(flat._write_files(tmp_path, n=96))
    ds.load_into_memory()
    ds.begin_pass(round_to=16)
    cfg = TrainStepConfig(
        num_slots=flat.S, batch_size=16 // (mesh_n or 1), layout=layout, sparse_opt=opt,
        auc_buckets=100, check_nan=True, axis_name=plan.axis if plan else None)
    model = DeepFM(num_slots=flat.S, feat_width=layout.pull_width, embedx_dim=4, hidden=(8,))
    tr = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-2), plan=plan)
    tr.init_params(jax.random.PRNGKey(0))
    return ds, tr


def _join(tmp_path, mesh_n=0):
    ds, tr = pv._fresh(tmp_path, mesh=_mesh(mesh_n), n_shards=mesh_n or 2, check_nan=True)
    ds.set_current_phase(1)
    ds.preprocess_instance()
    return ds, tr


def _tokens(tmp_path):
    tmp_path.mkdir(parents=True, exist_ok=True)
    ids = np.random.default_rng(0).integers(0, toy.V, (6 * toy.B, toy.T))
    box, ds = toy._dataset(toy._token_files(tmp_path, ids))
    tr = toy._trainer(box, toy.ref.init(jax.random.PRNGKey(1), toy.TINY, 3 + toy.H))
    return ds, tr


CTR_FIELDS = {"loss", "step", "preds", "labels", "nan_skipped"}
TIERS = {
    "flat": (_ctr, {}, CTR_FIELDS),
    "pv": (_join, {}, CTR_FIELDS),
    "mesh": (_ctr, {"mesh_n": 4}, CTR_FIELDS),
    "pv_mesh": (_join, {"mesh_n": 4}, CTR_FIELDS),
    "tokens": (_tokens, {}, {"loss", "step", "counters"}),
}


class ParentForm:
    """What a superstep returned before: the scan's stacks as they are, with
    batch j's metrics an eager slice of each on the host. Put in place of
    ``per_batch_metrics``, the stepper of today drives the parent's loop."""

    made: list = []

    def __init__(self, stacks):
        self.stacks = stacks

    def __len__(self):
        return len(next(iter(self.stacks.values())))

    def __getitem__(self, j):
        j = range(len(self))[j]
        return {f: v[j] for f, v in self.stacks.items()}

    def __iter__(self):
        return (self[j] for j in range(len(self)))

    @classmethod
    def _unflatten(cls, _, children):
        self = cls(children[0])
        if all(isinstance(v, jax.Array) for v in self.stacks.values()):
            cls.made.append(self)  # a call's outputs (not a lowering's avals), in the calls' order
        return self


jax.tree_util.register_pytree_node(
    ParentForm, lambda p: ((p.stacks,), None), ParentForm._unflatten)


def _pass(ds, tr, n_batches):
    seen = []
    out = tr.train_pass(ds, n_batches=n_batches, on_batch=lambda i, m: seen.append(dict(m)))
    assert out["batches"] == n_batches == len(seen) and out["nan_batches"] == 0
    end = {
        "table": np.asarray(tr.trained_table()),
        "dense": [np.asarray(a) for a in jax.tree.leaves((tr.params, tr.opt_state))],
        "auc": [np.asarray(tr._state.auc.pos), np.asarray(tr._state.auc.neg)],
    }
    return seen, end


@pytest.mark.parametrize("tier", list(TIERS))
def test_a_batch_s_metrics_are_the_slices_of_the_scan_s_stacks(tier, tmp_path, monkeypatch):
    make, kw, fields = TIERS[tier]
    n = 3 * K
    with monkeypatch.context() as mp:
        mp.setattr(resident_step, "per_batch_metrics", ParentForm)
        ParentForm.made.clear()
        ds, tr = make(tmp_path / "parent", **kw)
        want, want_end = _pass(ds, tr, n)
        # a program's first call rebuilds its outputs twice: one entry a first step
        by_step = {int(np.asarray(p.stacks["step"])[0]): p.stacks for p in ParentForm.made}
        stacks = [by_step[s] for s in sorted(by_step)]
    assert len(stacks) == 3 and all(set(s) == fields for s in stacks)
    assert all(len(v) == K for s in stacks for v in s.values())

    ds, tr = make(tmp_path / "change", **kw)
    got, got_end = _pass(ds, tr, n)
    for i, (g, w) in enumerate(zip(got, want)):
        assert set(g) == set(w) == fields
        for f in fields:
            a = np.asarray(g[f])
            assert a.shape == stacks[i // K][f].shape[1:] and a.dtype == stacks[i // K][f].dtype
            assert np.array_equal(a, np.asarray(stacks[i // K][f])[i % K]), (i, f)
            assert np.array_equal(a, np.asarray(w[f])), (i, f)
    assert [int(g["step"]) for g in got] == list(range(1, n + 1))
    assert np.array_equal(got_end["table"], want_end["table"])
    assert len(got_end["dense"]) == len(want_end["dense"])
    assert all(np.array_equal(a, b) for a, b in zip(got_end["dense"], want_end["dense"]))
    assert all(np.array_equal(a, b) for a, b in zip(got_end["auc"], want_end["auc"]))


@contextlib.contextmanager
def _no_eager_programs(monkeypatch):
    """Every eagerly applied primitive builds its one-primitive program
    through ``dispatch.xla_primitive_callable``: refuse there. Jitted calls
    and transfers do not pass it."""

    def refuse(prim, **params):
        raise AssertionError(f"eager {prim} between two supersteps")

    with monkeypatch.context() as mp:
        mp.setattr(dispatch, "xla_primitive_callable", refuse)
        yield


def _guard_the_stepper(tr, monkeypatch):
    """From the stepper's first line (before the first dispatch) to its
    exhaustion (before the call's tail), the consumer's work included."""
    stepper = tr._resident_stepper

    def guarded(*a, **kw):
        with _no_eager_programs(monkeypatch):
            yield from stepper(*a, **kw)

    monkeypatch.setattr(tr, "_resident_stepper", guarded)


@pytest.mark.parametrize("tier", ["flat", "pv"])
def test_three_supersteps_launch_nothing_eager(tier, tmp_path, monkeypatch):
    make, kw, _ = TIERS[tier]
    ds, tr = make(tmp_path, **kw)
    tr.train_pass(ds, n_batches=3 * K)  # warm: the programs exist
    _guard_the_stepper(tr, monkeypatch)
    losses = []
    out = tr.train_pass(ds, n_batches=3 * K, on_batch=lambda i, m: losses.append(m["loss"]))
    assert out["batches"] == 3 * K == len(losses)
    assert np.isclose(out["loss"], np.mean([float(x) for x in losses]), rtol=1e-6)


def test_the_guard_refuses_the_parent_s_form(tmp_path, monkeypatch):
    monkeypatch.setattr(resident_step, "per_batch_metrics", ParentForm)
    ds, tr = _ctr(tmp_path)
    tr.train_pass(ds, n_batches=3 * K)
    _guard_the_stepper(tr, monkeypatch)
    with pytest.raises(AssertionError, match="eager .* between two supersteps"):
        tr.train_pass(ds, n_batches=3 * K)
