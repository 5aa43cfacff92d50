"""Olmo-Hybrid's share (``paddlebox_tpu/models/olmo_hybrid.py``) at toy widths on
the CPU: 64 hidden, two periods of three Gated DeltaNet layers (2 heads, keys
of 8, values of 16) and a full layer (2 heads of 16), a 96-wide SwiGLU,
vocabulary 64, records of 32 tokens in chunks of 8.

(a) the chunked delta rule (``models/linear_attention.py``) against the
per-token recurrence of the plain reference, forward and gradients, beta near
2 and decays near 0 and near 1; (b) the program model against the plain
reference on seeded weights; (c) three head shares add up to the uncut
layer; (d) the period scan against the layers unrolled; (e) through
``BoxPSDataset`` / ``CTRTrainer.train_pass`` against the reference step loop;
(f) scopes, counters and what ``build`` refuses.
"""

from __future__ import annotations

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.models import olmo_hybrid as build  # noqa: E402
from benchmark.reference import olmo_hybrid as ref  # noqa: E402
from benchmark.reference import token_step  # noqa: E402
from paddlebox_tpu import BoxWrapper  # noqa: E402
from paddlebox_tpu.data import SlotInfo, SlotSchema  # noqa: E402
from paddlebox_tpu.models import OlmoHybrid, linear_attention, olmo_hybrid  # noqa: E402
from paddlebox_tpu.obs.program_scopes import scope_map  # noqa: E402
from paddlebox_tpu.table import SparseOptimizerConfig  # noqa: E402
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402

from benchmark.tests import toy_olmo_hybrid  # noqa: E402

TINY = toy_olmo_hybrid.cell()["cfg"]
TINY["dense_opt"] = {**TINY["dense_opt"], "lr": 3e-4, "warmup_steps": 4}
T, B, V, H = (TINY[k] for k in ("seq_len", "batch_size", "vocab_size", "hidden_size"))


def program_model(**over) -> OlmoHybrid:
    return build.build({**TINY, **over}, 3 + H)


@pytest.fixture(scope="module")
def seeded():
    params = ref.init(jax.random.PRNGKey(1), TINY, 3 + H)
    emb = jax.random.normal(jax.random.PRNGKey(2), (B, T, H)) * 0.5
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, V)
    return params, emb, ids


def _rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ---- (a) the chunked rule against the per-token recurrence ----------------------------

REGIMES = {  # (beta's range, the log-decay's range)
    "beta_near_2_decay_near_1": ((1.9, 2.0), (-0.01, 0.0)),
    "beta_anywhere_decay_near_0": ((0.0, 2.0), (-8.0, -5.0)),
    "beta_near_2_decays_mixed": ((1.9, 2.0), (-3.0, 0.0)),
}


@pytest.mark.parametrize("chunk", [64, 16])
@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_chunked_rule_is_the_per_token_recurrence_forward_and_backward(chunk, regime):
    (blo, bhi), (glo, ghi) = REGIMES[regime]
    n, h, dk, dv = 128, 3, 16, 32  # two or eight chunks
    ks = jax.random.split(jax.random.PRNGKey(chunk), 6)
    q = linear_attention.l2_normalize(jax.random.normal(ks[0], (1, n, h, dk))) * dk ** -0.5
    k = linear_attention.l2_normalize(jax.random.normal(ks[1], (1, n, h, dk)))
    v = jax.random.normal(ks[2], (1, n, h, dv))
    beta = jax.random.uniform(ks[3], (1, n, h), minval=blo, maxval=bhi)
    g = jax.random.uniform(ks[4], (1, n, h), minval=glo, maxval=ghi)
    cot = jax.random.normal(ks[5], (1, n, h, dv))
    mine = lambda *a: jnp.sum(linear_attention.delta_rule(*a, chunk, "s") * cot)  # noqa: E731
    want = lambda q, k, v, b, g: jnp.sum(ref.recurrence(q, k, v, b, jnp.exp(g)) * cot)  # noqa: E731
    args = (q, k, v, beta, g)
    o = linear_attention.delta_rule(*args, chunk, "s")
    assert o.shape == (1, n, h, dv) and _rel(o, ref.recurrence(q, k, v, beta, jnp.exp(g))) < 1e-5
    grads = jax.grad(mine, argnums=range(5))(*args)
    for name, a, b in zip("q k v beta g".split(), grads, jax.grad(want, argnums=range(5))(*args)):
        # the log-decay's gradient where alpha is e^-8 a token is a sum of terms that vanish
        assert _rel(a, b) < (1e-3 if name == "g" else 1e-5), (name, _rel(a, b))
    # the state does pass: past the first chunk the output is not the chunks' own alone
    alone = jnp.concatenate([linear_attention.delta_rule(*(a[:, i:i + chunk] for a in args), chunk, "s")
                             for i in range(0, n, chunk)], axis=1)
    assert _rel(alone[:, :chunk], o[:, :chunk]) < 1e-6
    assert _rel(alone[:, chunk:], o[:, chunk:]) > (1e-6 if glo < -5 else 1e-2)


def test_the_inverse_is_the_triangular_systems_and_the_conv_is_causal():
    A = jnp.tril(jax.random.uniform(jax.random.PRNGKey(0), (3, 64, 64), minval=-0.3, maxval=0.3), -1)
    inv = linear_attention.unit_lower_inverse(A)
    eye = jnp.eye(64)
    assert float(jnp.max(jnp.abs(jnp.matmul(eye + A, inv, precision="highest") - eye))) < 1e-5
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 5))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 5))
    y = linear_attention.short_conv(x, w)
    assert _rel(y, ref.short_conv(x, w)) < 1e-6
    later = linear_attention.short_conv(x.at[:, 9].add(1.0), w)
    assert np.array_equal(y[:, :9], later[:, :9]) and not np.allclose(y[:, 9:13], later[:, 9:13])
    assert np.array_equal(y[:, 13:], later[:, 13:])  # kernel 4: positions 9 .. 12 see it


# ---- (b) program against reference -------------------------------------------

def test_program_model_agrees_with_the_plain_reference(seeded):
    params, emb, ids = seeded
    model = program_model()
    mine = model.init(jax.random.PRNGKey(5))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(params)]
    assert mine["linear"]["q"].shape == (6, H, 16) and mine["full"]["q"].shape == (2, H, 32)
    (loss, out), (gp, ge) = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True))(
        params, emb, ids.astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        (rloss, rout), (rgp, rge) = jax.jit(jax.value_and_grad(
            lambda p, e: ref.forward(p, e, ids, TINY), argnums=(0, 1), has_aux=True))(params, emb)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-4)
    assert set(out) == {"counters"} and out["counters"].shape == (len(model.counter_names),) == (5,)
    counters = dict(zip(model.counter_names, np.asarray(out["counters"], np.float64)))
    assert [counters["loss_first_chunk"], counters["loss_past_first_chunk"]] == pytest.approx(
        np.asarray(rout["parts"]), rel=1e-4)
    assert counters["tokens"] == B * T
    # beta = 2 sigmoid(.) about the seed's 0 and alpha near 1: about half the steps have beta > 1
    assert 0.3 < counters["beta_over_one_share"] < 0.7 and 0.5 < counters["decay_mean"] < 1.0
    fwd = jax.jit(model.forward)(params, emb, ids)
    assert np.asarray(fwd["parts"]) == pytest.approx(np.asarray(out["counters"][:2]), rel=1e-6)
    assert fwd["token_logits"].shape == (2, B, T)
    # a bfloat16 operand a rounding apart on the two sides moves a token by 2^-8 of an element
    assert float(jnp.max(jnp.abs(fwd["token_logits"] - rout["token_logits"]))) < 5e-3
    assert fwd["router_choices"].shape == (1, B, T, 1) and not np.any(fwd["router_choices"])
    # gradients of every leaf and of the pulled rows: the two differ by where a bfloat16
    # cotangent is rounded, a few parts in a hundred of a leaf's norm at these widths (the
    # decay's two leaves: a sum over every token and head of terms that cancel, twice that)
    flat, rflat = jax.tree_util.tree_flatten_with_path(gp)[0], jax.tree.leaves(rgp)
    for (path, g), r in zip(flat, rflat):
        name = jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(r)) > 0, name
        assert _rel(g, r) < (0.06 if "A_log" in name or "dt_bias" in name else 0.03), (name, _rel(g, r))
    assert _rel(ge, rge) < 0.02


def test_in_float32_the_program_is_the_reference_to_rounding(seeded, monkeypatch):
    """With float32 operands on both sides the two are one computation: the
    bfloat16 casts are the only difference that (b) allows for."""
    from paddlebox_tpu.models import attention, lm_layers

    params, emb, ids = seeded
    monkeypatch.setattr(lm_layers, "BF16", jnp.float32)
    monkeypatch.setattr(attention, "BF16", jnp.float32)
    cfg = {**TINY, "matmul_dtype": "float32"}
    with jax.default_matmul_precision("highest"):
        (loss, _), (gp, ge) = jax.value_and_grad(program_model().apply, argnums=(0, 1), has_aux=True)(
            params, emb, ids.astype(jnp.float32))
        (rloss, _), (rgp, rge) = jax.value_and_grad(
            lambda p, e: ref.forward(p, e, ids, cfg), argnums=(0, 1), has_aux=True)(params, emb)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-6)
    assert max(_rel(a, b) for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(rgp))) < 1e-4
    assert _rel(ge, rge) < 1e-4


# ---- (c) the shares add up ---------------------------------------------------

def _share(p, i, n, linear: bool):
    """Share i of n of a layer's parameters, by heads."""
    def cols(a, per):  # columns (the last axis) of head block i
        return a[..., i * per:(i + 1) * per]
    if linear:
        dk, dv = TINY["linear_key_head_dim"], TINY["linear_value_head_dim"]
        q = lambda a: cols(a, a.shape[-1] // n)  # noqa: E731
        s = {key: q(p[key]) for key in ("q", "k", "v", "A_log", "dt_bias", "conv_q", "conv_k",
                                        "conv_v", "g")}
        half = p["ba"].shape[-1] // 2  # [w_b | w_a]: each half a column a head
        s["ba"] = jnp.concatenate([cols(p["ba"][:, :half], half // n),
                                   cols(p["ba"][:, half:], half // n)], axis=-1)
        s["o"] = p["o"][i * p["o"].shape[0] // n:(i + 1) * p["o"].shape[0] // n]
        assert s["q"].shape[-1] == dk * 2 and s["v"].shape[-1] == dv * 2
        return {**p, **s}
    q = lambda a: cols(a, a.shape[-1] // n)  # noqa: E731
    s = {key: q(p[key]) for key in ("q", "k", "v", "q_norm", "k_norm")}
    s["o"] = p["o"][i * p["o"].shape[0] // n:(i + 1) * p["o"].shape[0] // n]
    return {**p, **s}


@pytest.mark.parametrize("linear", [True, False], ids=["linear", "full"])
def test_three_head_shares_and_the_feed_forward_once_add_up_to_the_uncut_layer(seeded, linear):
    _, emb, _ = seeded
    whole = {**TINY, "num_attention_heads": 6, "num_key_value_heads": 6,
             "linear_num_key_heads": 6, "linear_num_value_heads": 6}
    p = ref._layer_init(jax.random.PRNGKey(9), whole, linear)
    m = ref._Math(jnp.float32, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        uncut = ref.layer(p, emb, whole, m, linear)
    c = program_model().cfg  # a chip's share: 2 heads of each kind
    if linear:
        parts = [olmo_hybrid.linear_attention(_share(p, i, 3, True), emb, c)[0] for i in range(3)]
    else:
        q, k = (jnp.matmul(emb.astype(jnp.bfloat16), p[w].astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32) for w in ("q", "k"))
        whole_ms = (olmo_hybrid.mean_square(q), olmo_hybrid.mean_square(k))  # the group's all-reduce
        parts = [olmo_hybrid.full_attention(_share(p, i, 3, False), emb, c, whole_ms) for i in range(3)]
        own = olmo_hybrid.full_attention(_share(p, 0, 3, False), emb, c)  # the one-chip statistic
        assert _rel(own, parts[0]) > 1e-3
    eps = TINY["rms_norm_eps"]
    h = emb + olmo_hybrid.rms_norm(sum(parts), p["ln_post_attn"], eps)
    out = h + olmo_hybrid.rms_norm(olmo_hybrid.swiglu(p["mlp"], h), p["ln_post_mlp"], eps)
    assert _rel(out, uncut) < 2e-5
    assert min(_rel(sum(parts) - y, sum(parts)) for y in parts) > 0.1  # every share adds its part


# ---- (d) the period scan ----------------------------------------------------------

def test_the_period_scan_is_the_layers_unrolled(seeded, monkeypatch):
    """In float32 operands: in bfloat16 the compiled scan and the layers run
    one by one round an operand differently now and then, and 8 layers carry
    that to a part in a thousand."""
    from paddlebox_tpu.models import attention, lm_layers

    monkeypatch.setattr(lm_layers, "BF16", jnp.float32)
    monkeypatch.setattr(attention, "BF16", jnp.float32)
    params, emb, _ = seeded
    model = program_model()
    c = model.cfg
    x, readings = model.hidden_states(params, emb)
    y, rs = emb, []
    for i, kind in enumerate(c.layer_types):
        period, j = divmod(i, 4)
        if kind == olmo_hybrid.LINEAR:
            p = jax.tree.map(lambda a: a[3 * period + j], params["linear"])
            y, r = olmo_hybrid.linear_layer(p, y, c)
            rs.append(r)
        else:
            y = olmo_hybrid.full_layer(jax.tree.map(lambda a: a[period], params["full"]), y, c)
    assert c.layer_types == ("linear_attention",) * 3 + ("full_attention",) + (
        "linear_attention",) * 3 + ("full_attention",)
    assert _rel(x, y) < 1e-5 and readings.shape == (6, 2) and _rel(readings, jnp.stack(rs)) < 1e-5


# ---- (e) through the normal path -------------------------------------------------

def _dataset(tmp_path, ids):
    path = tmp_path / "tokens-000.txt"
    with open(path, "w") as f:
        for row in ids.tolist():
            f.write(f"1 0.0 {T} " + " ".join(f"{i}.0" for i in row) + f" {T} "
                    + " ".join(str(token_step.KEY_BASE + i) for i in row) + "\n")
    box = BoxWrapper(embedx_dim=H, sparse_opt=SparseOptimizerConfig(**TINY["sparse_opt"]), seed=7)
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1),
         SlotInfo("ids", type="float", dense=True, dim=T), SlotInfo("tokens")],
        label_slot="label")
    ds = box.make_dataset(schema, batch_size=B)
    ds.set_date("20260930")
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    ds.begin_pass()
    return box, ds


def test_token_pass_through_dataset_and_trainer_against_the_reference_steps(tmp_path, monkeypatch):
    """In float32 operands on both sides, so that 8 steps of the pass hold the
    program to the reference step loop tightly: at these widths one bfloat16
    operand rounded the other way is a part in ten thousand of a step's loss,
    and 8 steps of Adam carry it to a few parts in a thousand (in float32, a
    few parts in a million: Adam's step is as large for a leaf whose gradient
    is round-off as for any other)."""
    from paddlebox_tpu.models import attention, lm_layers

    monkeypatch.setattr(lm_layers, "BF16", jnp.float32)
    monkeypatch.setattr(attention, "BF16", jnp.float32)
    cfg = {**TINY, "matmul_dtype": "float32"}
    ids = np.random.default_rng(0).integers(0, V, (8 * B, T))
    box, ds = _dataset(tmp_path, ids)
    params = ref.init(jax.random.PRNGKey(1), TINY, 3 + H)
    ad = TINY["dense_opt"]
    tr = CTRTrainer(
        program_model(),
        TrainStepConfig(num_slots=1, batch_size=B, layout=box.layout, sparse_opt=box.sparse_opt,
                        auc_buckets=1000),
        dense_opt=optax.adam(lambda n: ad["lr"] * jnp.minimum(1.0, (n + 1) / ad["warmup_steps"]),
                             b1=ad["b1"], b2=ad["b2"], eps=ad["eps"]),
        dense_slot="ids", dense_dim=T)
    assert tr.cfg.sequence_len == T
    tr.hand_over_dense(jax.tree.map(jnp.copy, params))
    assert tr._use_resident(ds, False, False)
    seen = []
    out = tr.train_pass(ds, n_batches=8, on_batch=lambda i, m: seen.append(m))
    assert out["batches"] == 8 and out["nan_batches"] == 0
    assert set(seen[0]) == {"loss", "step", "counters"}
    assert out["tokens"] == B * T and out["loss_first_chunk"] > 0 and out["loss_past_first_chunk"] > 0
    assert STAT_GET("model.tokens_per_step") == B * T
    assert STAT_GET("model.beta_over_one_share") == pytest.approx(out["beta_over_one_share"])
    assert STAT_GET("model.decay_mean") == pytest.approx(out["decay_mean"])

    keys = np.unique(ids + token_step.KEY_BASE).astype(np.uint64)
    with jax.default_matmul_precision("highest"):
        want = token_step.run_steps(ref.forward, params, cfg, 7, ids.reshape(8, B, T), keys)
    losses = np.asarray([float(m["loss"]) for m in seen])
    assert losses == pytest.approx(want["losses"], rel=5e-5)
    W = ds.table.layout.width
    rows = ds.ws.row_of_sorted[np.searchsorted(ds.ws.sorted_keys, keys)]
    open_rows = np.asarray(ds.device_table).reshape(-1, W)[rows]
    got = np.asarray(tr.trained_table_device().reshape(-1, W))[rows]
    assert np.array_equal(got[:, 0], want["rows"][:, 0])  # show counts the occurrences
    moved = np.linalg.norm(want["rows"][:, 3:3 + H] - open_rows[:, 3:3 + H])
    assert moved > 0 and np.linalg.norm(got[:, 3:3 + H] - want["rows"][:, 3:3 + H]) < 5e-3 * moved
    for (path, a), b, o in zip(jax.tree_util.tree_flatten_with_path(tr.params)[0],
                               jax.tree.leaves(want["params"]), jax.tree.leaves(want["open_params"])):
        step = np.linalg.norm(b - o)
        assert step > 0 and np.linalg.norm(np.asarray(a) - b) < 0.02 * step, jax.tree_util.keystr(path)


# ---- (f) scopes, counters, refusals ---------------------------------------------

def test_every_leaf_scope_is_named_in_full_and_the_sites_count_themselves(seeded):
    params, emb, ids = seeded
    model = program_model()
    stats = ("model.linear_attn.chunked_sites", "model.attn.blocked_scores",
             "model.attn.fused_full_scores", "model.attn.keep_scores_sites")
    before = [STAT_GET(s) for s in stats]
    text = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True)).lower(
        params, emb, ids.astype(jnp.float32)).compile().as_text()
    # one linear and one full layer traced in the period's body; the CPU takes the blocked scores
    assert [STAT_GET(s) - b for s, b in zip(stats, before)] == [1, 1, 0, 1]
    assert STAT_GET("model.linear_attn.chunk") == TINY["chunk"]
    scopes = set(scope_map(text).values())
    want = {f"model/linear_attn/{s}" for s in ("qkv_proj", "short_conv", "gates", "delta_rule",
                                               "out_norm_gate", "out_proj")}
    want |= {f"model/attn/{s}" for s in ("qkv_proj", "qk_norm_rope", "scores_full", "out_proj")}
    want |= {"model/mlp", "loss/head"}
    assert want <= scopes, sorted(want - scopes)
    assert not [s for s in scopes if s.startswith("model/") and s not in want]


def test_the_counters_are_published_and_build_refuses_what_it_does_not_compute():
    assert OlmoHybrid.counter_names == ("loss_first_chunk", "loss_past_first_chunk", "tokens",
                                        "beta_over_one_share", "decay_mean")
    OlmoHybrid.record_counters([4.5, 4.25, 4096.0, 0.5, 0.8])
    got = [STAT_GET(f"model.{n}") for n in ("loss_first_chunk", "loss_past_first_chunk",
                                            "tokens_per_step", "beta_over_one_share", "decay_mean")]
    assert got == [4.5, 4.25, 4096.0, 0.5, 0.8]
    with pytest.raises(ValueError, match="without positions"):
        program_model(rope_parameters={"rope_theta": 10000})
    with pytest.raises(ValueError, match="silu SwiGLU"):
        program_model(hidden_act="gelu")
    with pytest.raises(ValueError, match="whole periods"):
        program_model(num_hidden_layers=6)
    with pytest.raises(ValueError, match="key-value heads"):
        program_model(num_key_value_heads=1)
