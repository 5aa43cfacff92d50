"""GLM-4.7-Flash's share (``models/glm_moe_lite.py``) at a tiny preset with
every mechanism: hidden 64, 4 heads, q/kv ranks 24/16, nope/rope/v 12/4/16,
8 experts top 2 with 2 held, 1 dense + 2 expert layers + MTP, vocabulary 64.

(a) the program model against the plain reference on seeded weights; (b)
latent attention against a per-head, per-position loop; (c) the shares of an
expert-parallel group add up to the uncut layer; (d) routing; (e) through
``BoxPSDataset`` / ``CTRTrainer.train_pass`` against the reference step loop.
"""

from __future__ import annotations

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.reference import glm_moe_lite as ref  # noqa: E402
from benchmark.reference import token_step  # noqa: E402
from paddlebox_tpu import BoxWrapper  # noqa: E402
from paddlebox_tpu.data import SlotInfo, SlotSchema  # noqa: E402
from paddlebox_tpu.models import glm_moe_lite as glm  # noqa: E402
from paddlebox_tpu.models import lm_layers, moe  # noqa: E402
from paddlebox_tpu.models import GlmMoeLite, GlmMoeLiteConfig  # noqa: E402
from paddlebox_tpu.table import SparseOptimizerConfig  # noqa: E402
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402

from benchmark.tests import toy_tokens  # noqa: E402

# the benchmark's toy of the configuration file (n_routed_experts = held,
# router_experts = the router's width), with a warm-up short enough to end
TINY = toy_tokens.cell()["cfg"]
TINY["dense_opt"] = {**TINY["dense_opt"], "lr": 3e-4, "warmup_steps": 4}
T, B, V, H = TINY["seq_len"], TINY["batch_size"], TINY["vocab_size"], TINY["hidden_size"]


def program_config(**over) -> GlmMoeLiteConfig:
    d = {**TINY, **over}
    return GlmMoeLiteConfig.from_dict(
        {**d, "n_routed_experts": d["router_experts"], "experts_held": d["n_routed_experts"]})


@pytest.fixture(scope="module")
def seeded():
    params = ref.init(jax.random.PRNGKey(1), TINY, 3 + H)
    emb = jax.random.normal(jax.random.PRNGKey(2), (B, T, H)) * 0.5
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, V)
    return params, emb, ids


def _rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ---- (a) program against reference -------------------------------------------

def test_program_model_agrees_with_the_plain_reference(seeded):
    params, emb, ids = seeded
    model = GlmMoeLite(program_config())
    mine = model.init(jax.random.PRNGKey(5))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(params)]
    (loss, out), (gp, ge) = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True))(
        params, emb, ids.astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        (rloss, rout), (rgp, rge) = jax.jit(jax.value_and_grad(
            lambda p, e: ref.forward(p, e, ids, TINY), argnums=(0, 1), has_aux=True))(params, emb)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-6)
    assert set(out) == {"counters"}  # the one array the step carries out
    assert np.asarray(out["counters"][:2]) == pytest.approx(np.asarray(rout["parts"]), rel=1e-6)
    # both heads' logit terms, every position; the same experts chosen
    fwd = jax.jit(model.forward)(params, emb, ids)
    assert np.asarray(fwd["parts"]) == pytest.approx(np.asarray(out["counters"][:2]), rel=1e-6)
    assert float(jnp.max(jnp.abs(fwd["token_logits"] - rout["token_logits"]))) < 1e-5
    assert np.array_equal(np.sort(fwd["router_choices"], -1), np.sort(rout["router_choices"], -1))
    assert float(out["counters"][2]) == B * T
    held = np.isin(np.asarray(rout["router_choices"]), [2, 3]).sum()
    assert float(out["counters"][3]) == held
    # gradients of every leaf and of the pulled rows: the two differ by where a
    # bfloat16 cotangent is rounded, a few parts in a thousand of a leaf's norm
    flat, rflat = jax.tree_util.tree_flatten_with_path(gp)[0], jax.tree.leaves(rgp)
    floor = float(np.median([float(jnp.linalg.norm(r)) for r in rflat]))
    for (path, g), r in zip(flat, rflat):
        err = float(jnp.linalg.norm(g - r)) / max(float(jnp.linalg.norm(r)), 1e-3 * floor)
        assert err < 0.02, (jax.tree_util.keystr(path), err)
    assert _rel(ge, rge) < 5e-3
    for tree in (gp, rgp):  # the correction bias is a buffer: no gradient
        assert not np.any(np.asarray(tree["moe"]["router"]["bias"]))
        assert not np.any(np.asarray(tree["mtp"]["block"]["router"]["bias"]))


# ---- (b) latent attention against a loop --------------------------------------

def test_latent_attention_against_a_per_head_per_position_loop(seeded):
    params, emb, _ = seeded
    c = program_config()
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params["dense"][0]["attn"])
    ln = np.asarray(params["dense"][0]["ln1"], np.float64)
    x = np.asarray(emb, np.float64)
    rope = lm_layers.rope_tables(T, c.qk_rope_head_dim, c.rope_theta)
    got = np.asarray(glm.mla(params["dense"][0]["attn"], emb, params["dense"][0]["ln1"], c, rope,
                             "model"), np.float64) - x

    def norm(v, w):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + c.rms_norm_eps) * w

    def rot(v, t):  # rope on all of v's dims, halves paired, position t
        half = v.shape[-1] // 2
        ang = t / c.rope_theta ** (np.arange(half) * 2.0 / v.shape[-1])
        a, b = v[:half], v[half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang), b * np.cos(ang) + a * np.sin(ang)])

    nh, dn, dr, dv = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    want = np.zeros_like(x)
    for b in range(B):
        xn = norm(x[b], ln)
        q = (norm(xn @ p["q_a"], p["q_a_norm"]) @ p["q_b"]).reshape(T, nh, dn + dr)
        ckv = xn @ p["kv_a"]
        kv = (norm(ckv[:, :c.kv_lora_rank], p["kv_a_norm"]) @ p["kv_b"]).reshape(T, nh, dn + dv)
        k_r = np.stack([rot(ckv[t, c.kv_lora_rank:], t) for t in range(T)])  # one key, all heads
        heads = np.zeros((T, nh, dv))
        for h in range(nh):
            for t in range(T):
                qt = np.concatenate([q[t, h, :dn], rot(q[t, h, dn:], t)])  # rope on the rope dims only
                s = np.array([qt @ np.concatenate([kv[u, h, :dn], k_r[u]]) for u in range(t + 1)])
                w = np.exp((s - s.max()) / np.sqrt(dn + dr))
                heads[t, h] = (w / w.sum()) @ kv[: t + 1, h, dn:]  # causal: no later position
        want[b] = heads.reshape(T, nh * dv) @ p["o"]
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 0.02  # bfloat16 operands
    # causal: a later token changes no earlier output
    emb2 = emb.at[:, T // 2:].add(1.0)
    got2 = glm.mla(params["dense"][0]["attn"], emb2, params["dense"][0]["ln1"], c, rope, "model") - emb2
    assert np.array_equal(np.asarray(got2[:, : T // 2]), (got[:, : T // 2]).astype(np.float32)) or \
        np.allclose(np.asarray(got2[:, : T // 2]), got[:, : T // 2], atol=1e-6)


# ---- (c) the shares add up ---------------------------------------------------

def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(seeded):
    params, emb, _ = seeded
    whole_cfg = {**TINY, "n_routed_experts": 8, "experts_offset": 0}
    layer = ref._layer_init(jax.random.PRNGKey(9), whole_cfg, True)  # all 8 experts
    x = emb.reshape(B * T, H)
    m = ref._Math(jnp.float32, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        whole, chosen = ref.experts_part(layer, x, whole_cfg, m)
        shared = ref.glu(layer["shared"], x, m)
    total = jnp.zeros_like(whole)
    loads = []
    for off in range(0, 8, 2):  # four chips of two experts each
        c = program_config(experts_offset=off)
        part = {**layer, "experts": jax.tree.map(lambda a: a[off:off + 2], layer["experts"])}
        idx, g = moe.route(part["router"], x, c.num_experts_per_tok, scale=c.routed_scaling_factor)
        assert np.array_equal(np.sort(idx, -1), np.sort(chosen, -1))  # every chip routes alike
        routed, counts = moe.routed_experts(part["experts"], x, idx, g, c.experts_held,
                                            c.experts_offset, c.expert_block, "model")
        with jax.default_matmul_precision("highest"):  # and the reference is given the same share
            share_cfg = {**TINY, "experts_offset": off}
            ref_share = ref.experts_part(part, x, share_cfg, m)[0] - shared
        assert _rel(routed, ref_share) < 1e-5
        total, loads = total + routed, loads + [np.asarray(counts)]
    assert _rel(total + shared, whole) < 1e-5
    # every assignment lands on exactly one chip's experts: none dropped, none twice
    assert np.concatenate(loads).sum() == B * T * 2
    assert np.array_equal(np.concatenate(loads), np.bincount(np.asarray(chosen).ravel(), minlength=8))


# ---- (d) routing -----------------------------------------------------------------

def test_routing_picks_by_score_plus_bias_weighs_by_score_and_drops_no_token():
    c = program_config(experts_offset=0)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(40, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(H, 8)) * 0.3, jnp.float32)
    bias = jnp.asarray([3.0, 0, 0, 0, 0, 0, 0, -3.0])  # expert 0 always chosen, 7 never
    idx, g = moe.route({"w": w, "bias": bias}, x, c.num_experts_per_tok, scale=c.routed_scaling_factor)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(w, np.float64))))
    want = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :2]
    assert np.array_equal(np.sort(idx, -1), np.sort(want, -1))
    assert np.all(np.any(np.asarray(idx) == 0, axis=1)) and not np.any(np.asarray(idx) == 7)
    picked = np.take_along_axis(s, np.asarray(idx), axis=1)  # weights from s, not s + b
    assert np.asarray(g) == pytest.approx(picked / picked.sum(1, keepdims=True) * 1.8, rel=1e-5)
    assert np.asarray(g).sum(1) == pytest.approx(1.8, rel=1e-5)
    # a skewed router: every token on held expert 0, 40 rows in blocks of 8, none dropped
    experts = GlmMoeLite(c)._mlp_init(jax.random.PRNGKey(0), 48, lead=(2,))
    y, counts = moe.routed_experts(experts, x, idx, g, c.experts_held, c.experts_offset,
                                   c.expert_block, "model")
    assert counts[0] == 40 and counts.sum() == 40 + int(np.sum(np.asarray(idx) == 1))
    one = jax.tree.map(lambda a: a[0], experts)
    g0 = jnp.sum(jnp.where(idx == 0, g, 0.0), axis=1, keepdims=True)
    g1 = jnp.sum(jnp.where(idx == 1, g, 0.0), axis=1, keepdims=True)
    two = jax.tree.map(lambda a: a[1], experts)
    want_y = lm_layers.swiglu(one, x) * g0 + lm_layers.swiglu(two, x) * g1
    assert _rel(y, want_y) < 1e-5
    # the layout: every block one expert's, rows by the blocks in use
    src, blk, n_blocks, cnt = moe.group_layout(jnp.asarray([1, 2, 0, 0, 2, 1, 0, 2, 2]), 2, 4)
    assert cnt.tolist() == [3, 2] and int(n_blocks) == 2 and blk[:2].tolist() == [0, 1]
    assert src[:8].tolist() == [2, 3, 6, 9, 0, 5, 9, 9] and np.all(np.asarray(src[8:]) == 9)


# ---- (e) through the normal path -------------------------------------------------

def _token_files(tmp_path, ids):
    path = tmp_path / "tokens-000.txt"
    with open(path, "w") as f:
        for row in ids.tolist():
            f.write(f"1 0.0 {T} " + " ".join(f"{i}.0" for i in row) + f" {T} "
                    + " ".join(str(token_step.KEY_BASE + i) for i in row) + "\n")
    return [str(path)]


def _dataset(files, seq_len=T):
    so = TINY["sparse_opt"]
    box = BoxWrapper(embedx_dim=H, sparse_opt=SparseOptimizerConfig(**so), seed=7)
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1),
         SlotInfo("ids", type="float", dense=True, dim=seq_len), SlotInfo("tokens")],
        label_slot="label")
    ds = box.make_dataset(schema, batch_size=B)
    ds.set_date("20260930")
    ds.set_filelist(files)
    ds.load_into_memory()
    ds.begin_pass()
    return box, ds


def _trainer(box, params):
    ad = TINY["dense_opt"]
    tr = CTRTrainer(
        GlmMoeLite(program_config()),
        TrainStepConfig(num_slots=1, batch_size=B, layout=box.layout, sparse_opt=box.sparse_opt,
                        auc_buckets=1000),
        dense_opt=optax.adam(lambda n: ad["lr"] * jnp.minimum(1.0, (n + 1) / ad["warmup_steps"]),
                             b1=ad["b1"], b2=ad["b2"], eps=ad["eps"]),
        dense_slot="ids", dense_dim=T)
    if params is not None:
        tr.params = params
        tr.opt_state = tr.dense_opt.init(params)
    return tr


def test_token_pass_through_dataset_and_trainer_against_the_reference_steps(tmp_path):
    ids = np.random.default_rng(0).integers(0, V, (16, T))
    box, ds = _dataset(_token_files(tmp_path, ids))
    assert ds.store is not None and ds.ws.n_keys == len(np.unique(ids))
    params = ref.init(jax.random.PRNGKey(1), TINY, 3 + H)
    tr = _trainer(box, None)
    assert tr.cfg.sequence_len == T  # the model object said so; no flag was set
    tr.hand_over_dense(jax.tree.map(jnp.copy, params))  # as a state too large to hold twice
    assert tr._use_resident(ds, False, False)
    seen = []
    out = tr.train_pass(ds, n_batches=8, on_batch=lambda i, m: seen.append(m))
    assert out["batches"] == 8 and out["nan_batches"] == 0
    assert tr.params is not None and tr._state.params is tr.params  # re-pointed, one copy
    assert set(seen[0]) == {"loss", "step", "counters"}
    assert out["tokens"] == B * T and out["loss_main"] > 0 and out["loss_mtp"] > 0
    assert STAT_GET("model.tokens_per_step") == B * T
    assert out["auc"] == 0.5  # no AUC is computed: the buckets stay empty

    keys = np.unique(ids[:16] + token_step.KEY_BASE).astype(np.uint64)
    with jax.default_matmul_precision("highest"):
        want = token_step.run_steps(ref.forward, params, TINY, 7,
                                    ids.reshape(8, B, T), keys)
    losses = np.asarray([float(m["loss"]) for m in seen])
    assert losses == pytest.approx(want["losses"], rel=2e-4)
    parts = np.stack([np.asarray(m["counters"][:2]) for m in seen])
    assert parts == pytest.approx(want["parts"], rel=2e-4)
    W = ds.table.layout.width
    rows = ds.ws.row_of_sorted[np.searchsorted(ds.ws.sorted_keys, keys)]
    open_rows = np.asarray(ds.device_table).reshape(-1, W)[rows]
    assert np.array_equal(open_rows, want["open_rows"])  # the table's per-key rule
    got = np.asarray(tr.trained_table_device().reshape(-1, W))[rows]
    assert np.array_equal(got[:, 0], want["rows"][:, 0])  # show counts the occurrences
    assert np.array_equal(got[:, 0] - open_rows[:, 0],
                          np.bincount(np.searchsorted(keys, (ids + token_step.KEY_BASE).ravel()),
                                      minlength=len(keys)))
    assert not np.any(got[:, 1])  # clk 0
    assert np.array_equal(got[:, 2], open_rows[:, 2])  # embed_w is unused: no gradient
    moved = np.linalg.norm(want["rows"][:, 3:3 + H] - open_rows[:, 3:3 + H])
    assert moved > 0 and np.linalg.norm(got[:, 3:3 + H] - want["rows"][:, 3:3 + H]) < 0.02 * moved
    for (path, a), b, o in zip(jax.tree_util.tree_flatten_with_path(tr.params)[0],
                               jax.tree.leaves(want["params"]), jax.tree.leaves(want["open_params"])):
        step = np.linalg.norm(b - o)
        if step > 0:  # the correction bias does not move, on either side
            assert np.linalg.norm(np.asarray(a) - b) < 0.25 * step, jax.tree_util.keystr(path)
        else:
            assert np.array_equal(np.asarray(a), o)


def test_a_record_of_another_length_is_refused_when_the_superstep_is_built(tmp_path):
    ids = np.random.default_rng(1).integers(0, V, (16, T))
    path = _token_files(tmp_path, ids)[0]
    with open(path, "a") as f:  # one record a key short (its dense slot keeps T values)
        f.write(f"1 0.0 {T} " + " ".join(["1.0"] * T) + f" {T - 1} "
                + " ".join(str(token_step.KEY_BASE + 1) for _ in range(T - 1)) + "\n")
        f.write(f"1 0.0 {T} " + " ".join(["1.0"] * T) + f" {T} "
                + " ".join(str(token_step.KEY_BASE + 1) for _ in range(T)) + "\n")
    box, ds = _dataset([path])
    tr = _trainer(box, ref.init(jax.random.PRNGKey(1), TINY, 3 + H))
    with pytest.raises(ValueError, match="sequence feed of 32 keys a record"):
        tr.train_pass(ds, n_batches=8)


def test_sequence_feed_refuses_what_it_cannot_serve():
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.table import ValueLayout

    model = GlmMoeLite(program_config())
    lay = ValueLayout(embedx_dim=H)
    ok = dict(num_slots=1, batch_size=B, layout=lay)
    # the declaration travels on the config, from the model object
    assert CTRTrainer(model, TrainStepConfig(**ok)).cfg.sequence_len == T
    assert CTRTrainer(model, TrainStepConfig(**ok, sequence_len=T)).cfg.sequence_len == T
    ctr = DeepFM(num_slots=1, feat_width=3 + H, embedx_dim=H)
    assert CTRTrainer(ctr, TrainStepConfig(**ok)).cfg.sequence_len == 0
    with pytest.raises(ValueError, match="sequence_len 8 against the model's seq_len 32"):
        CTRTrainer(model, TrainStepConfig(**ok, sequence_len=8))
    for bad in (dict(num_slots=2), dict(adjust_ins_weight=(0, 5.0, 1.0)), dict(axis_name="dp"),
                dict(dense_sync_mode="kstep"), dict(use_expand=True),
                dict(model_takes_rank_offset=True)):
        with pytest.raises(NotImplementedError):
            TrainStepConfig(**{**ok, "sequence_len": T, **bad})
        with pytest.raises(NotImplementedError):  # and through the trainer, from the model
            CTRTrainer(model, TrainStepConfig(**{**ok, **bad}))


# ---- the dense state handed over (no second copy) -----------------------------------

@pytest.fixture()
def handed_over(tmp_path):
    """A trainer whose dense state is handed over, one pass trained, and a
    checkpoint of it."""
    ids = np.random.default_rng(2).integers(0, V, (16, T))
    box, ds = _dataset(_token_files(tmp_path, ids))
    tr = _trainer(box, None)
    tr.hand_over_dense(ref.init(jax.random.PRNGKey(1), TINY, 3 + H))
    tr.train_pass(ds, n_batches=8)
    ckpt = str(tmp_path / "dense.npz")
    tr.save_dense(ckpt)
    return tr, ds, ckpt


def _leaves(path):
    with np.load(path) as data:
        return {k: data[k] for k in data.files if k.startswith("leaf_")}


def test_save_dense_in_the_middle_of_a_handed_over_pass_raises_and_keeps_the_checkpoint(handed_over):
    tr, ds, ckpt = handed_over
    before, caught = _leaves(ckpt), []

    def on_batch(i, m):
        assert tr.params is None and tr.opt_state is None  # the pass holds them
        try:
            tr.save_dense(ckpt)
        except RuntimeError as e:
            caught.append(str(e))

    tr.train_pass(ds, n_batches=8, on_batch=on_batch)
    assert len(caught) == 8 and "save after train_pass returns" in caught[0]
    after = _leaves(ckpt)  # not replaced by an npz of no leaves
    assert len(after) == len(before) > 0 and all(np.array_equal(after[k], before[k]) for k in before)
    tr.save_dense(ckpt)  # between passes it saves what the pass returned
    assert any(not np.array_equal(_leaves(ckpt)[k], before[k]) for k in before)


def test_a_handed_over_pass_that_fails_returns_the_state_if_it_survived(handed_over):
    tr, ds, _ = handed_over

    def on_batch(i, m):
        if i == 3:
            raise KeyError("the host's own failure")

    with pytest.raises(KeyError):
        tr.train_pass(ds, n_batches=8, on_batch=on_batch)
    assert tr.params is not None and tr._state.params is tr.params and not tr._dense_with_pass
    assert tr.train_pass(ds, n_batches=8)["batches"] == 8  # and trains on


def test_a_handed_over_pass_that_takes_the_state_along_says_how_to_recover(handed_over, monkeypatch):
    tr, ds, ckpt = handed_over
    saved = _leaves(ckpt)

    def dying(dataset, n_batches, holder, *a, **kw):
        jax.tree.map(lambda x: x.delete(), holder["state"])  # an XLA error after donation
        raise RuntimeError("the device's own failure")
        yield

    with monkeypatch.context() as mp:
        mp.setattr(tr, "_resident_stepper", dying)
        with pytest.raises(RuntimeError, match="the device's own failure"):
            tr.train_pass(ds, n_batches=8)
    assert tr.params is None and tr._state is None
    with pytest.raises(RuntimeError, match="init_params\\(\\) and load_dense"):
        tr.save_dense(ckpt)
    assert all(np.array_equal(v, saved[k]) for k, v in _leaves(ckpt).items())
    with pytest.raises(RuntimeError, match="init_params\\(\\), then load_dense"):
        tr.train_pass(ds, n_batches=8)  # not a silent fresh start
    tr.init_params()
    tr.load_dense(ckpt)
    assert all(np.array_equal(np.asarray(a), saved[f"leaf_{i}"]) for i, a in enumerate(
        jax.tree.leaves((tr.params, tr.opt_state))))
    assert tr.train_pass(ds, n_batches=8)["batches"] == 8
    assert tr.params is not None


def test_without_hand_over_a_pass_trains_copies_and_params_stay_readable(tmp_path):
    ids = np.random.default_rng(3).integers(0, V, (16, T))
    box, ds = _dataset(_token_files(tmp_path, ids))
    tr = _trainer(box, ref.init(jax.random.PRNGKey(1), TINY, 3 + H))
    opened = jax.tree.map(np.asarray, tr.params)
    read = []
    tr.train_pass(ds, n_batches=8, on_batch=lambda i, m: read.append(
        jax.tree.map(np.asarray, tr.params)))
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(read[0]), jax.tree.leaves(opened)))
    with pytest.raises(NotImplementedError, match="mesh"):
        tr.plan = object()
        tr.hand_over_dense()
