"""Xing4.0's share (``paddlebox_tpu/models/xing4.py``) at toy widths on the
CPU: 64 hidden in 4 streams, 4 heads of nope / rope / v 16 / 8 / 16 under YaRN,
8 experts top 2 with 2 held beside a shared one, 1 dense + 2 expert layers,
vocabulary 64.

(a) the program model against the plain reference on seeded weights; (b) a
hyper-connection alone: its maps against the reference's, Sinkhorn's manifold
after 20 rounds and not after 2, the clamp; (c) the eight shares of an
expert-parallel group add up to the uncut branch; (d) YaRN at the published
numbers; (e) through ``BoxPSDataset`` / ``CTRTrainer.train_pass`` against the
reference step loop; (f) scopes and counters.
"""

from __future__ import annotations

import json
import os

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.models import xing4 as build  # noqa: E402
from benchmark.reference import token_step  # noqa: E402
from benchmark.reference import xing4 as ref  # noqa: E402
from paddlebox_tpu import BoxWrapper  # noqa: E402
from paddlebox_tpu.data import SlotInfo, SlotSchema  # noqa: E402
from paddlebox_tpu.models import glm_moe_lite as glm  # noqa: E402
from paddlebox_tpu.models import lm_layers  # noqa: E402
from paddlebox_tpu.models import xing4  # noqa: E402
from paddlebox_tpu.models import Xing4  # noqa: E402
from paddlebox_tpu.obs.program_scopes import scope_map  # noqa: E402
from paddlebox_tpu.table import SparseOptimizerConfig  # noqa: E402
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402

from benchmark.tests import toy_xing4  # noqa: E402

TINY = toy_xing4.cell()["cfg"]
TINY["dense_opt"] = {**TINY["dense_opt"], "lr": 3e-4, "warmup_steps": 4}
T, B, V, H, N = (TINY[k] for k in ("seq_len", "batch_size", "vocab_size", "hidden_size", "hc_mult"))
HELD = [2, 3]  # experts_offset 2, two held
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def program_model(**over) -> Xing4:
    return build.build({**TINY, **over}, 3 + H)


@pytest.fixture(scope="module")
def seeded():
    params = ref.init(jax.random.PRNGKey(1), TINY, 3 + H)
    emb = jax.random.normal(jax.random.PRNGKey(2), (B, T, H)) * 0.5
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, V)
    return params, emb, ids


def _rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _streams(key=4):
    """Four streams that differ, as [B, T, n, C] and as the program's tuple."""
    X = jax.random.normal(jax.random.PRNGKey(key), (B, T, N, H)) * 0.7
    return X, tuple(X[:, :, i] for i in range(N))


# ---- (a) program against reference -------------------------------------------

def test_program_model_agrees_with_the_plain_reference(seeded):
    params, emb, ids = seeded
    model = program_model()
    mine = model.init(jax.random.PRNGKey(5))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(params)]
    assert "mtp" not in mine and set(mine["moe"]) >= {"hc_attn", "hc_mlp", "attn", "experts", "shared"}
    (loss, out), (gp, ge) = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True))(
        params, emb, ids.astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        (rloss, rout), (rgp, rge) = jax.jit(jax.value_and_grad(
            lambda p, e: ref.forward(p, e, ids, TINY), argnums=(0, 1), has_aux=True))(params, emb)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-6)
    assert set(out) == {"counters"} and out["counters"].shape == (len(model.counter_names),) == (6,)
    counters = dict(zip(model.counter_names, np.asarray(out["counters"], np.float64)))
    # the first two counters are what the token driver reads as the loss's parts
    assert [counters["loss_main"], counters["tokens"]] == pytest.approx(
        np.asarray(rout["parts"]), rel=1e-6)
    fwd = jax.jit(model.forward)(params, emb, ids)
    assert np.asarray(fwd["parts"]) == pytest.approx(np.asarray(out["counters"][:2]), rel=1e-6)
    assert fwd["token_logits"].shape == (2, B, T) and fwd["router_choices"].shape == (2, B, T, 2)
    assert float(jnp.max(jnp.abs(fwd["token_logits"] - rout["token_logits"]))) < 1e-4
    chosen = np.asarray(rout["router_choices"])
    assert np.array_equal(np.sort(fwd["router_choices"], -1), np.sort(chosen, -1))
    assert counters["tokens"] == B * T and counters["held_assignments"] == np.isin(chosen, HELD).sum()
    loads = np.stack([[(chosen[l] == e).sum() for e in HELD] for l in range(2)])
    assert counters["expert_load_max_over_mean"] == pytest.approx(loads.max() / loads.mean())
    # the streams mix (B_res 2 on the diagonal: about 0.7 stays) and end on the manifold
    assert 0.1 < counters["hc_res_offdiag"] < 0.5 and 0 <= counters["hc_sinkhorn_residual"] < 1e-4
    # gradients of every leaf and of the pulled rows: the two differ by where a
    # bfloat16 cotangent is rounded, a few parts in a thousand of a leaf's norm
    flat, rflat = jax.tree_util.tree_flatten_with_path(gp)[0], jax.tree.leaves(rgp)
    floor = float(np.median([float(jnp.linalg.norm(r)) for r in rflat]))
    for (path, g), r in zip(flat, rflat):
        name = jax.tree_util.keystr(path)
        assert (float(jnp.linalg.norm(r)) > 0) != name.endswith("['bias']"), name  # the bias is a buffer
        err = float(jnp.linalg.norm(g - r)) / max(float(jnp.linalg.norm(r)), 1e-3 * floor)
        assert err < 0.02, (name, err)
    assert _rel(ge, rge) < 5e-3


def test_the_counters_are_published_and_the_builder_refuses_what_it_does_not_compute():
    assert Xing4.counter_names == ("loss_main", "tokens", "held_assignments",
                                   "expert_load_max_over_mean", "hc_res_offdiag", "hc_sinkhorn_residual")
    Xing4.record_counters([1.5, 128.0, 255.0, 1.25, 0.29, 2e-6])
    got = [STAT_GET(f"model.{n}") for n in (
        "loss_main", "tokens_per_step", "held_assignments_per_step", "expert_load_max_over_mean",
        "hc_res_offdiag", "hc_sinkhorn_residual")]
    assert got == [1.5, 128.0, 255.0, 1.25, 0.29, 2e-6]
    with pytest.raises(ValueError, match="sigmoid \\+ bias"):
        program_model(scoring_func="softmax")
    with pytest.raises(ValueError, match="no MTP module"):
        program_model(num_nextn_predict_layers=1)
    with pytest.raises(ValueError, match="rope_scaling"):
        program_model(rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(ValueError, match="mscale"):
        program_model(rope_scaling={**TINY["rope_scaling"], "mscale_all_dim": 0.5})


# ---- (b) a hyper-connection alone -------------------------------------------------

def _maps(p, X, tup, c, **over):
    """The program's maps as [B, T, ...] beside the reference's."""
    pre, post, res, left = xing4.hc_maps(p, tup, c, "model/hc_attn")
    m = ref._Math(jnp.float32, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = ref.hc_maps(p, X, {**TINY, **over}, m)
    got = (pre.T.reshape(B, T, N), post.T.reshape(B, T, N),
           res.transpose(2, 0, 1).reshape(B, T, N, N))
    return got, want, left


def test_the_maps_agree_with_the_reference_and_sinkhorn_reaches_the_manifold(seeded):
    params, _, _ = seeded
    p = jax.tree.map(lambda a: a[0], params["moe"]["hc_attn"])
    p = {**p, "alpha": jnp.asarray([0.5, 0.7, 0.5])}  # maps that read the token, well off their seed
    X, tup = _streams()
    c = program_model().cfg
    got, want, left = _maps(p, X, tup, c)
    for a, b in zip(got, want):
        assert a.shape == b.shape and float(jnp.max(jnp.abs(a - b))) < 2e-5
    pre, post, res = got
    assert 0 < float(pre.min()) and float(pre.max()) < 1 and float(post.max()) < 2
    assert float(jnp.std(pre)) > 0.02  # not the constant 0.5 of a map that reads nothing
    # 20 rounds: rows and columns sum to 1; the program's own reading of what is left says so
    assert float(jnp.max(jnp.abs(res.sum(-1) - 1))) < 1e-5
    assert float(jnp.max(jnp.abs(res.sum(-2) - 1))) < 1e-5
    assert float(jnp.max(left)) == pytest.approx(float(jnp.max(jnp.abs(res.sum(-2) - 1))), abs=1e-7)
    # 2 rounds (the control's fault): visibly not
    two = xing4.Xing4Config.from_dict({**c.__dict__, "hc_sinkhorn_iters": 2})
    res2 = _maps(p, X, tup, two, hc_sinkhorn_iters=2)[0][2]
    assert float(jnp.max(jnp.abs(res2.sum(-2) - 1))) > 1e-3
    assert float(jnp.max(jnp.abs(res2 - res))) > 1e-3


def test_the_clamp_is_reached_before_exp_and_the_rows_still_sum_to_one(seeded):
    params, _, _ = seeded
    p = jax.tree.map(lambda a: a[0], params["moe"]["hc_mlp"])
    p = {**p, "alpha": jnp.asarray([0.1, 0.1, 400.0])}  # alpha_2 z far past +-30
    X, tup = _streams(6)
    (_, _, res), (_, _, want), _ = _maps(p, X, tup, program_model().cfg)
    z = 400.0 * np.asarray(jnp.matmul(
        X.reshape(B, T, -1) / jnp.sqrt(jnp.mean(X.reshape(B, T, -1) ** 2, -1, keepdims=True) + 1e-6),
        p["phi"], precision="highest"))[..., 2 * N:]
    assert (np.abs(z) > 30).mean() > 0.5  # most entries sit on the clamp
    assert np.all(np.isfinite(np.asarray(res))) and float(jnp.max(jnp.abs(res.sum(-1) - 1))) < 1e-5
    assert float(jnp.max(jnp.abs(res - want))) < 2e-5
    # unclamped, exp(+-400 z) overflows float32 and the matrix is lost
    wide = xing4.Xing4Config.from_dict({**program_model().cfg.__dict__, "mhc_h_res_clamp_min": -1e4,
                                        "mhc_h_res_clamp_max": 1e4})
    assert not np.all(np.isfinite(np.asarray(xing4.hc_maps(p, tup, wide, "model/hc_mlp")[2])))


def test_a_hyper_connection_reads_a_mix_writes_to_every_stream_and_adds_no_input(seeded):
    params, _, _ = seeded
    p = jax.tree.map(lambda a: a[0], params["moe"]["hc_attn"])
    X, tup = _streams(8)
    c = program_model().cfg
    seen = []

    def branch(h):
        seen.append(h)
        return jnp.tanh(h), None

    out, _, reading = xing4.hyper_connection(p, tup, branch, c, "model/hc_attn")
    m = ref._Math(jnp.float32, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.hyper_connection(p, X, lambda h: (jnp.tanh(h), None), TINY, m)
        pre, post, res = ref.hc_maps(p, X, TINY, m)
    assert _rel(jnp.stack(out, axis=2), want) < 1e-5
    assert _rel(seen[0], jnp.einsum("btn,btnc->btc", pre, X, precision="highest")) < 1e-5
    # a branch that gives nothing leaves the mixed streams: no stream is its input plus anything
    zero, _, _ = xing4.hyper_connection(p, tup, lambda h: (jnp.zeros_like(h), None), c, "model/hc_attn")
    mixed = jnp.einsum("btij,btjc->btic", res, X, precision="highest")
    assert _rel(jnp.stack(zero, axis=2), mixed) < 1e-5 and _rel(mixed, X) > 0.1
    assert float(reading[0]) == pytest.approx(1 - float(jnp.mean(jnp.trace(res, axis1=-2, axis2=-1))) / N,
                                              rel=1e-4)


# ---- (c) the shares add up ---------------------------------------------------

def test_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_branch(seeded):
    _, emb, _ = seeded
    whole_cfg = {**TINY, "n_routed_experts": 8, "experts_offset": 0}
    layer = ref._layers_init(jax.random.PRNGKey(9), whole_cfg, True)  # all 8 experts
    m = ref._Math(jnp.float32, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        whole, chosen = ref.expert_branch(layer, emb, whole_cfg, m)
        normed = m.norm(emb, layer["ln2"], TINY["rms_norm_eps"])
        shared = ref.glm.glu(layer["shared"], normed, m)
    total, loads = jnp.zeros_like(whole), []
    for off in range(8):  # eight chips of one expert each
        c = program_model(n_routed_experts=1, experts_offset=off).cfg
        part = {**layer, "experts": jax.tree.map(lambda a: a[off:off + 1], layer["experts"])}
        y, idx, counts = glm.moe_branch(part, emb, c)
        assert np.array_equal(np.sort(idx.reshape(B, T, -1), -1), np.sort(chosen, -1))  # every chip routes alike
        with jax.default_matmul_precision("highest"):  # and the reference is given the same share
            ref_share = ref.expert_branch(part, emb, {**TINY, "n_routed_experts": 1,
                                                      "experts_offset": off}, m)[0]
        assert _rel(y, ref_share) < 1e-5
        total, loads = total + (y - shared), loads + [np.asarray(counts)]
    assert _rel(total + shared, whole) < 1e-5
    # every assignment lands on exactly one chip's expert: none dropped, none twice
    assert np.concatenate(loads).sum() == B * T * 2
    assert np.array_equal(np.concatenate(loads), np.bincount(np.asarray(chosen).ravel(), minlength=8))


# ---- (d) YaRN at the published numbers ---------------------------------------------

def test_yarn_tables_and_scale_at_the_published_numbers():
    with open(os.path.join(ROOT, "benchmark", "configs", "xing4_29b_a4b_ep8.json")) as f:
        cfg = json.load(f)
    c = build.build(cfg, 3 + cfg["hidden_size"]).cfg
    d, L, factor = 64, 4096, 64
    f = 10000.0 ** (-np.arange(32) * 2 / d)
    dim = lambda r: d * np.log(L / (2 * np.pi * r)) / (2 * np.log(10000.0))  # noqa: E731
    low, high = int(np.floor(dim(32))), int(np.ceil(dim(1)))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    want = f / factor * ramp + f * (1 - ramp)
    assert np.array_equal(want[:11], f[:11]) and np.allclose(want[23:], f[23:] / 64)
    assert np.allclose(ref.yarn_inv_freq(cfg), want, rtol=1e-12)
    cos, sin = lm_layers.yarn_rope_tables(256, d, c.rope_theta, c.rope_factor, c.rope_original,
                                    c.beta_fast, c.beta_slow)
    ang = np.arange(256, dtype=np.float32)[:, None] * want.astype(np.float32)[None, :]  # float32, as there
    assert np.allclose(cos, np.cos(ang), atol=1e-5) and np.allclose(sin, np.sin(ang), atol=1e-5)
    # without scaling the tables are the plain ones
    plain = lm_layers.yarn_rope_tables(256, d, 1e4, 1.0, 4096, 32, 1)
    assert all(np.allclose(a, b, atol=1e-6) for a, b in zip(plain, lm_layers.rope_tables(256, d, 1e4)))
    m = 0.1 * np.log(64) + 1
    assert lm_layers.yarn_mscale(64, 1) == pytest.approx(m) and lm_layers.yarn_mscale(1, 1) == 1.0
    assert c.softmax_scale == pytest.approx(192 ** -0.5 * m * m) == pytest.approx(0.14468, rel=1e-4)
    assert ref.softmax_scale(cfg) == pytest.approx(c.softmax_scale, rel=1e-12)
    assert ref.softmax_scale({**cfg, "yarn_scale_left_out": True}) == pytest.approx(192 ** -0.5)
    assert (c.qk_head_dim, c.v_head_dim, c.hc_mult, c.hc_sinkhorn_iters) == (192, 128, 4, 20)


# ---- (e) through the normal path -------------------------------------------------

def _token_files(tmp_path, ids):
    path = tmp_path / "tokens-000.txt"
    with open(path, "w") as f:
        for row in ids.tolist():
            f.write(f"1 0.0 {T} " + " ".join(f"{i}.0" for i in row) + f" {T} "
                    + " ".join(str(token_step.KEY_BASE + i) for i in row) + "\n")
    return [str(path)]


def _dataset(files):
    box = BoxWrapper(embedx_dim=H, sparse_opt=SparseOptimizerConfig(**TINY["sparse_opt"]), seed=7)
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1),
         SlotInfo("ids", type="float", dense=True, dim=T), SlotInfo("tokens")],
        label_slot="label")
    ds = box.make_dataset(schema, batch_size=B)
    ds.set_date("20260930")
    ds.set_filelist(files)
    ds.load_into_memory()
    ds.begin_pass()
    return box, ds


def test_token_pass_through_dataset_and_trainer_against_the_reference_steps(tmp_path):
    ids = np.random.default_rng(0).integers(0, V, (8 * B, T))
    box, ds = _dataset(_token_files(tmp_path, ids))
    params = ref.init(jax.random.PRNGKey(1), TINY, 3 + H)
    ad = TINY["dense_opt"]
    tr = CTRTrainer(
        program_model(),
        TrainStepConfig(num_slots=1, batch_size=B, layout=box.layout, sparse_opt=box.sparse_opt,
                        auc_buckets=1000),
        dense_opt=optax.adam(lambda n: ad["lr"] * jnp.minimum(1.0, (n + 1) / ad["warmup_steps"]),
                             b1=ad["b1"], b2=ad["b2"], eps=ad["eps"]),
        dense_slot="ids", dense_dim=T)
    assert tr.cfg.sequence_len == T  # the model object said so; no flag was set
    tr.hand_over_dense(jax.tree.map(jnp.copy, params))
    assert tr._use_resident(ds, False, False)
    seen = []
    out = tr.train_pass(ds, n_batches=8, on_batch=lambda i, m: seen.append(m))
    assert out["batches"] == 8 and out["nan_batches"] == 0
    assert set(seen[0]) == {"loss", "step", "counters"}
    assert out["tokens"] == B * T and out["loss_main"] > 0 and 0.1 < out["hc_res_offdiag"] < 0.5
    assert STAT_GET("model.tokens_per_step") == B * T
    assert STAT_GET("model.hc_res_offdiag") == pytest.approx(out["hc_res_offdiag"])

    keys = np.unique(ids + token_step.KEY_BASE).astype(np.uint64)
    with jax.default_matmul_precision("highest"):
        want = token_step.run_steps(ref.forward, params, TINY, 7, ids.reshape(8, B, T), keys)
    losses = np.asarray([float(m["loss"]) for m in seen])
    assert losses == pytest.approx(want["losses"], rel=2e-4)
    W = ds.table.layout.width
    rows = ds.ws.row_of_sorted[np.searchsorted(ds.ws.sorted_keys, keys)]
    open_rows = np.asarray(ds.device_table).reshape(-1, W)[rows]
    got = np.asarray(tr.trained_table_device().reshape(-1, W))[rows]
    assert np.array_equal(got[:, 0], want["rows"][:, 0])  # show counts the occurrences
    moved = np.linalg.norm(want["rows"][:, 3:3 + H] - open_rows[:, 3:3 + H])
    assert moved > 0 and np.linalg.norm(got[:, 3:3 + H] - want["rows"][:, 3:3 + H]) < 0.02 * moved
    for (path, a), b, o in zip(jax.tree_util.tree_flatten_with_path(tr.params)[0],
                               jax.tree.leaves(want["params"]), jax.tree.leaves(want["open_params"])):
        step = np.linalg.norm(b - o)
        if step > 0:  # the correction bias does not move, on either side
            assert np.linalg.norm(np.asarray(a) - b) < 0.25 * step, jax.tree_util.keystr(path)
        else:
            assert np.array_equal(np.asarray(a), o)


# ---- (f) scopes and trace-time counters ---------------------------------------------

def test_every_leaf_scope_is_named_in_full_and_the_sublayers_count_themselves(seeded):
    params, emb, ids = seeded
    model = program_model()
    stats = ("model.hc.sublayers", "model.mla.blocked_scores", "model.mla.fused_scores",
             "model.mla.keep_scores_sites")
    before = [STAT_GET(s) for s in stats]
    text = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True)).lower(
        params, emb, ids.astype(jnp.float32)).compile().as_text()
    # a dense layer and one scan body, two sublayers each; the CPU takes the blocked scores
    assert [STAT_GET(s) - b for s, b in zip(stats, before)] == [4, 2, 0, 2]
    scopes = set(scope_map(text).values())
    want = {f"model/hc_{s}/{part}" for s in ("attn", "mlp") for part in ("maps", "pre", "post_res")}
    want |= {f"model/mla/{s}" for s in ("q_proj", "kv_proj", "rope", "scores", "out_proj")}
    want |= {f"model/moe/{s}" for s in ("router", "shared", "dispatch", "experts", "combine")}
    want |= {"model/dense_mlp", "model/hc_out", "loss/head"}
    assert want <= scopes, sorted(want - scopes)
    assert not any(s.startswith("model/mtp") for s in scopes)
