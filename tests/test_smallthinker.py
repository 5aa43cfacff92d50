"""SmallThinker's share (``models/smallthinker.py``) at a tiny preset with every
mechanism: hidden 64, 6 query heads over 2 key-value heads of 16 (a group of
3: no power of two), a window of 16 in records of 32, 4 expert layers of kinds
full, sliding, sliding, sliding, 8 experts top 2 with 2 held and no shared
one, the router ahead of attention, ReLU gates, vocabulary 64.

(a) the program model against the plain reference on seeded weights; (b) the
router stands ahead of attention; (c) the shares of an expert-parallel group
add up to the uncut layer, and a token with no held expert adds nothing; (d)
the shared code: ``relu`` through the grouped product against a loop, the
router's second form, and GLM's and Trinity's calls held to the jaxprs they
had; (e) through ``BoxPSDataset`` / ``CTRTrainer.train_pass`` against the
reference step loop.
"""

from __future__ import annotations

import hashlib
import re

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.reference import smallthinker as ref  # noqa: E402
from benchmark.reference import token_step  # noqa: E402
from paddlebox_tpu import BoxWrapper  # noqa: E402
from paddlebox_tpu.data import SlotInfo, SlotSchema  # noqa: E402
from paddlebox_tpu.models import lm_layers, moe  # noqa: E402
from paddlebox_tpu.models import smallthinker as st  # noqa: E402
from paddlebox_tpu.models import (  # noqa: E402
    Afmoe, AfmoeConfig, GlmMoeLiteConfig, SmallThinker, SmallThinkerConfig)
from paddlebox_tpu.table import SparseOptimizerConfig  # noqa: E402
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402

from benchmark import gen_tokens  # noqa: E402
from benchmark.models import smallthinker as build  # noqa: E402
from benchmark.tests import toy_smallthinker  # noqa: E402

# the benchmark's toy of the configuration file (moe_num_primary_experts = held,
# router_experts = the router's width), with a warm-up short enough to end
TINY = toy_smallthinker.cell()["cfg"]
TINY["dense_opt"] = {**TINY["dense_opt"], "lr": 3e-4, "warmup_steps": 4}
T, B, V, H = TINY["seq_len"], TINY["batch_size"], TINY["vocab_size"], TINY["hidden_size"]
W, K = TINY["sliding_window_size"], TINY["moe_num_active_primary_experts"]
HELD = [2, 3]  # experts_offset 2, two held


def program_config(**over) -> SmallThinkerConfig:
    return build.build({**TINY, **over}, 3 + H).cfg


@pytest.fixture(scope="module")
def seeded():
    params = ref.init(jax.random.PRNGKey(1), TINY, 3 + H)
    emb = jax.random.normal(jax.random.PRNGKey(2), (B, T, H)) * 0.5
    ids = jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, V)
    return params, emb, ids


def _rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def _routed(experts, x, idx, g, c, act="silu"):
    """``moe.routed_experts`` as a layer calls it, its share read off the config."""
    return moe.routed_experts(experts, x, idx, g, c.experts_held, c.experts_offset, c.expert_block,
                              "model", act)


def _one_layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"])


# ---- (a) program against reference -------------------------------------------

def test_program_model_agrees_with_the_plain_reference(seeded):
    params, emb, ids = seeded
    model = SmallThinker(program_config())
    mine = model.init(jax.random.PRNGKey(5))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(params)]
    (loss, out), (gp, ge) = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True))(
        params, emb, ids.astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        (rloss, rout), (rgp, rge) = jax.jit(jax.value_and_grad(
            lambda p, e: ref.forward(p, e, ids, TINY), argnums=(0, 1), has_aux=True))(params, emb)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-6)
    assert set(out) == {"counters"} and out["counters"].shape == (len(model.counter_names),)
    counters = dict(zip(model.counter_names, np.asarray(out["counters"], np.float64)))
    # both parts: the targets inside the first window and those past it
    assert [counters["loss_in_window"], counters["loss_past_window"]] == pytest.approx(
        np.asarray(rout["parts"]), rel=1e-6)
    assert float(loss) == pytest.approx(
        (counters["loss_in_window"] * W + counters["loss_past_window"] * (T - 1 - W)) / (T - 1),
        rel=1e-6)
    fwd = jax.jit(model.forward)(params, emb, ids)
    assert fwd["token_logits"].shape == (2, B, T) and fwd["router_choices"].shape == (4, B, T, K)
    gap = np.abs(np.asarray(fwd["token_logits"] - rout["token_logits"]))
    assert np.median(gap) < 1e-6 and gap.max() < 2e-3
    chosen = np.asarray(rout["router_choices"])
    assert np.array_equal(np.sort(fwd["router_choices"], -1), np.sort(chosen, -1))
    # the counters against the reference's choices: the held assignments, the (token, layer)
    # pairs no chosen expert of which is held, the rows of the blocks in use
    held = np.isin(chosen, HELD)
    assert counters["tokens"] == B * T and counters["held_assignments"] == held.sum()
    assert counters["unrouted_tokens"] == (~held.any(-1)).sum() > 0
    loads = np.stack([[(chosen[l] == e).sum() for e in HELD] for l in range(4)])
    R = TINY["expert_block"]
    assert counters["block_rows"] == (-(-loads // R) * R).sum() >= counters["held_assignments"]
    assert counters["expert_load_max_over_mean"] == pytest.approx(loads.max() / loads.mean())
    # gradients of every leaf and of the pulled rows: the two differ by where a
    # bfloat16 cotangent is rounded, a few parts in a thousand of a leaf's norm
    flat, rflat = jax.tree_util.tree_flatten_with_path(gp)[0], jax.tree.leaves(rgp)
    floor = float(np.median([float(jnp.linalg.norm(r)) for r in rflat]))
    for (path, g), r in zip(flat, rflat):
        assert float(jnp.linalg.norm(r)) > 0, jax.tree_util.keystr(path)  # the router's too: through w
        err = float(jnp.linalg.norm(g - r)) / max(float(jnp.linalg.norm(r)), 1e-3 * floor)
        assert err < 0.02, (jax.tree_util.keystr(path), err)
    assert _rel(ge, rge) < 5e-3


def test_a_scan_step_told_its_kind_is_the_layer_of_that_kind(seeded):
    params, emb, _ = seeded
    c = program_config()
    rope = lm_layers.rope_tables(T, c.head_dim, c.rope_theta)
    p = _one_layer(params)
    outs = {}
    for sliding in (True, False):
        want = jax.jit(lambda: st.layer(p, emb, c, rope, sliding)[0])()
        got = jax.jit(lambda s: st.layer(p, emb, c, rope, s)[0])(jnp.asarray(sliding))
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
        outs[sliding] = want
    assert _rel(outs[True], outs[False]) > 1e-3  # and the two kinds are two layers


def test_the_layers_kinds_are_the_files_full_first_then_three_sliding():
    c = program_config()
    assert c.layer_kinds == (0, 1, 1, 1) and c.group == 3 and c.sliding_window == W
    with pytest.raises(ValueError, match="rope is on the sliding layers alone"):
        program_config(held_rope_layout=[1, 1, 1, 1])
    with pytest.raises(ValueError, match="softmax over the chosen"):
        program_config(norm_topk_prob=False)


# ---- (b) the router stands ahead of attention ----------------------------------

def test_the_routers_choice_reads_the_layers_input_and_not_what_attention_made_of_it(seeded):
    params, emb, _ = seeded
    c = program_config()
    rope = lm_layers.rope_tables(T, c.head_dim, c.rope_theta)
    p = _one_layer(params, 1)
    loud = {**p, "attn": jax.tree.map(lambda a: a * 40.0, p["attn"])}  # another attention block
    run = jax.jit(lambda p, s: st.layer(p, emb, c, rope, s))
    for sliding in (True, False):
        x1, idx1, _ = run(p, jnp.asarray(sliding))
        x2, idx2, _ = run(loud, jnp.asarray(sliding))
        assert _rel(x2, x1) > 0.1  # the stream did change, and the experts' input with it
        assert np.array_equal(idx1, idx2)  # the choice did not
    want, _ = moe.route(p["router"], emb.reshape(B * T, H), c.num_experts_per_tok,
                        form="softmax_of_chosen")
    assert np.array_equal(idx1.reshape(B * T, K), want)  # the bare input: no norm before it
    # the reference with the planted fault (the router after attention) chooses otherwise
    m = ref._Math(jnp.float32, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        sound = ref.layer(loud, emb, TINY, m, True)[1]
        fault = ref.layer(loud, emb, {**TINY, "router_after_attention": True}, m, True)[1]
    assert np.array_equal(np.sort(sound, -1), np.sort(idx2, -1))
    assert np.mean(np.sort(sound, -1) != np.sort(fault, -1)) > 0.2


# ---- (c) the shares add up, and no shared expert stands in -----------------------

def test_four_shares_add_up_to_the_uncut_layer_and_nothing_is_counted_twice(seeded):
    params, emb, _ = seeded
    whole_cfg = {**TINY, "moe_num_primary_experts": 8, "experts_offset": 0}
    layer = ref._layer_init(jax.random.PRNGKey(9), whole_cfg)  # all 8 experts
    x = emb.reshape(B * T, H)
    m = ref._Math(jnp.float32, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        chosen, w = ref.route(layer, x, whole_cfg, m)
        whole = ref.experts_part(layer, x, chosen, w, whole_cfg, m)
    total, loads, served = jnp.zeros_like(whole), [], np.zeros(B * T, int)
    for off in range(0, 8, 2):  # four chips of two experts each
        c = program_config(experts_offset=off)
        experts = jax.tree.map(lambda a: a[off:off + 2], layer["experts"])
        idx, g = moe.route(layer["router"], x, c.num_experts_per_tok, form="softmax_of_chosen")
        assert np.array_equal(np.sort(idx, -1), np.sort(chosen, -1))  # every chip routes alike
        routed, counts = _routed(experts, x, idx, g, c, "relu")
        with jax.default_matmul_precision("highest"):  # and the reference is given the same share
            ref_share = ref.experts_part({"experts": experts}, x, chosen, w,
                                         {**TINY, "experts_offset": off}, m)
        assert _rel(routed, ref_share) < 1e-5
        # a token none of whose experts is held here adds exactly zero on this chip
        here = np.any((np.asarray(idx) >= off) & (np.asarray(idx) < off + 2), axis=1)
        assert 0 < (~here).sum() < B * T
        assert not np.any(np.asarray(routed)[~here]) and np.all(np.any(np.asarray(routed)[here], 1))
        total, loads, served = total + routed, loads + [np.asarray(counts)], served + here
    assert _rel(total, whole) < 1e-5
    assert served.min() >= 1  # every token is served somewhere, by 1 or 2 of the chips
    # every assignment lands on exactly one chip's experts: none dropped, none twice
    assert np.concatenate(loads).sum() == B * T * K
    assert np.array_equal(np.concatenate(loads), np.bincount(np.asarray(chosen).ravel(), minlength=8))


# ---- (d) the shared code ---------------------------------------------------------

def _loop_experts(experts, x, idx, g, offset, act):
    fn = {"silu": jax.nn.silu, "relu": jax.nn.relu}[act]
    y = jnp.zeros_like(x)
    for e in range(experts["gate"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == offset + e, g, 0.0), axis=1, keepdims=True)
        h = fn(lm_layers._mm(x, experts["gate"][e])) * lm_layers._mm(x, experts["up"][e])
        y = y + lm_layers._mm(h, experts["down"][e]) * w_e
    return y


@pytest.mark.parametrize("act", ["relu", "silu"])
def test_the_gates_activation_through_the_grouped_product_against_a_loop(act):
    c = SmallThinkerConfig(hidden_size=H, num_experts=16, num_experts_per_tok=4, experts_held=4,
                           experts_offset=4, moe_intermediate_size=48, expert_block=8)
    ks = jax.random.split(jax.random.PRNGKey(35), 5)
    x = jax.random.normal(ks[0], (40, H))
    experts = {n: jax.random.normal(k, s) * 0.2 for n, k, s in (
        ("gate", ks[1], (4, H, 48)), ("up", ks[2], (4, H, 48)), ("down", ks[3], (4, 48, H)))}
    router = {"w": jax.random.normal(ks[4], (H, 16)) * 0.3}
    idx, _ = moe.route(router, x, c.num_experts_per_tok, form="softmax_of_chosen")

    def grouped(experts, x, router):
        g = moe.route(router, x, c.num_experts_per_tok, form="softmax_of_chosen")[1]
        return jnp.sum(_routed(experts, x, idx, g, c, act)[0] ** 2)

    def loop(experts, x, router):
        g = moe.route(router, x, c.num_experts_per_tok, form="softmax_of_chosen")[1]
        return jnp.sum(_loop_experts(experts, x, idx, g, 4, act) ** 2)

    got, dgot = jax.jit(jax.value_and_grad(grouped, argnums=(0, 1, 2)))(experts, x, router)
    want, dwant = jax.jit(jax.value_and_grad(loop, argnums=(0, 1, 2)))(experts, x, router)
    assert float(got) == pytest.approx(float(want), rel=1e-4)
    for a, b in zip(jax.tree.leaves(dgot), jax.tree.leaves(dwant)):
        assert float(jnp.linalg.norm(b)) > 0 and _rel(a, b) < 0.02  # where a bfloat16 cotangent rounds
    other = {"relu": "silu", "silu": "relu"}[act]  # and the other activation is another function
    assert abs(float(grouped(experts, x, router)) - float(jnp.sum(
        _loop_experts(experts, x, idx, moe.route(router, x, 4, form="softmax_of_chosen")[1], 4,
                      other) ** 2))
               ) > 0.05 * float(want)
    with pytest.raises(ValueError, match="gate activation"):
        _routed(experts, x, idx, jnp.ones(idx.shape), c, "gelu")


def test_the_softmax_router_picks_6_by_logit_weighs_by_a_softmax_over_them_and_reads_no_bias():
    c = SmallThinkerConfig(hidden_size=H, num_experts=64, num_experts_per_tok=6)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(40, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(H, 64)) * 0.3, jnp.float32)
    idx, g = moe.route({"w": w}, x, c.num_experts_per_tok, form="softmax_of_chosen")  # no bias, no scale
    r = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    assert idx.shape == g.shape == (40, 6) and idx.dtype == jnp.int32
    assert np.array_equal(np.sort(idx, -1), np.sort(np.argsort(-r, axis=1)[:, :6], -1))
    picked = np.take_along_axis(r, np.asarray(idx), axis=1)
    soft = np.exp(r - r.max(1, keepdims=True))
    soft /= soft.sum(1, keepdims=True)  # over all 64, renormalised over the chosen: the same numbers
    renorm = np.take_along_axis(soft, np.asarray(idx), axis=1)
    assert np.asarray(g) == pytest.approx(renorm / renorm.sum(1, keepdims=True), rel=1e-5)
    assert np.asarray(g) == pytest.approx(np.exp(picked) / np.exp(picked).sum(1, keepdims=True),
                                          rel=1e-5)
    assert np.asarray(g).sum(1) == pytest.approx(1.0, rel=1e-6)
    with pytest.raises(ValueError, match="router form"):
        moe.route({"w": w}, x, c.num_experts_per_tok, form="softmax")


# GLM's and Trinity's calls name neither the router's form nor the gate's activation
SETTINGS = {
    "glm": GlmMoeLiteConfig(hidden_size=32, moe_intermediate_size=16, n_routed_experts=8,
                            num_experts_per_tok=2, experts_held=4, experts_offset=2, expert_block=8),
    "trinity": AfmoeConfig(hidden_size=32, moe_intermediate_size=16, num_experts=16,
                           num_experts_per_tok=4, experts_held=4, experts_offset=4, expert_block=8),
}
DIGESTS = {
    ("glm", "route"): "541f59a1a72a87dda56d813372dd2cb3568830e2ecc1770a5b73b000c02ce351",
    ("glm", "routed_experts"): "a5e937e29dab0278040f5a243e1b0bba5fa5b2e2fb4eaf44bffd639219fb0b5c",
    ("trinity", "route"): "96ebd8050053cb75935e7e239e07a4f42ec9cd1c07835ad4966f7737e02e01be",
    ("trinity", "routed_experts"): "6e9cfa47d6d7dbb42841fb0466deb1f4649e1fbfa8d353a422752e80e61edf88",
}


@pytest.mark.parametrize("model,piece", sorted(DIGESTS))
def test_glms_and_trinitys_calls_trace_to_the_jaxprs_they_had_before_form_and_activation(
        monkeypatch, model, piece):
    """``route`` and ``routed_experts`` (forward and gradient) as GLM's and
    Trinity's layers call them trace to the jaxprs of the parent commit
    (f7c5868, before the router's form and the gate's activation became
    arguments), source locations aside, and but for the name
    ``routed_experts`` gives its output (``moe.EXPERTS_OUT``, which a layer
    checkpoint keeps): once, and with it read back as the identity the digest
    holds. The digests were taken from that commit with these lines; a change
    to what those two cells run has to change them."""
    c = SETTINGS[model]
    N, Hc, I = 24, c.hidden_size, c.moe_intermediate_size
    E = getattr(c, "n_routed_experts", None) or c.num_experts
    scale = getattr(c, "routed_scaling_factor", None) or c.route_scale
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    router = {"w": f32(Hc, E), "bias": f32(E)}
    G = c.experts_held
    experts = {"gate": f32(G, Hc, I), "up": f32(G, Hc, I), "down": f32(G, I, Hc)}

    def routed(p, x):
        idx, g = moe.route(p["router"], x, c.num_experts_per_tok, scale=scale)
        y, counts = _routed(p["experts"], x, idx, g, c)
        return jnp.sum(y * y), (idx, counts)

    def routef(p, x):
        idx, g = moe.route(p, x, c.num_experts_per_tok, scale=scale)
        return jnp.sum(g * g), idx

    f, p = {"route": (routef, router),
            "routed_experts": (routed, {"router": router, "experts": experts})}[piece]
    trace = lambda: str(jax.make_jaxpr(  # noqa: E731
        jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(p, f32(N, Hc)))
    assert trace().count(f"name[name={moe.EXPERTS_OUT}]") == (piece == "routed_experts")
    monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
    text = trace()
    digest = hashlib.sha256(re.sub(r"\S+\.py:\d+", "", text).encode()).hexdigest()
    assert digest == DIGESTS[model, piece]


def test_trinitys_step_traces_to_the_jaxpr_it_had_but_for_what_its_two_checkpoints_keep(monkeypatch):
    """``Afmoe.apply`` (loss, counters and the gradient of every leaf and of
    the rows) traces to the jaxpr of commit f7c5868, source locations aside,
    but for what its two checkpoints keep: the dense layer's checkpoint
    carries ``ops/pallas_kernels.py::KEEP_SCORES`` and the scanned
    body's ``models/moe.py::KEEP_EXPERTS`` where they carried no policy (a
    policy prints as a function with an address, so those two are read back
    as ``policy=None`` before hashing), and the grouped product's output
    carries the name that the latter keeps (``moe.EXPERTS_OUT``), read back
    as the identity (the digest is the one taken from f7c5868, when
    ``feed_ids``, ``window_loss``, ``window_counters`` and
    ``record_window_counters`` moved out of the class for ``smallthinker`` to
    call). On the CPU the scores take the blocked form, which gives no names:
    there the policy keeps the expert output alone."""
    c = AfmoeConfig(
        hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8, sliding_window=16,
        layer_types=("sliding_attention", "full_attention", "sliding_attention"), num_dense_layers=1,
        intermediate_size=48, moe_intermediate_size=16, num_experts=16, num_experts_per_tok=4,
        vocab_size=64, experts_held=4, experts_offset=4, seq_len=32, attn_block=8, loss_block=16,
        expert_block=8)
    model = Afmoe(c)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    trace = lambda: str(jax.make_jaxpr(  # noqa: E731
        jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True))(p, f32(2, 32, 32), f32(2, 32)))
    assert f"name[name={moe.EXPERTS_OUT}]" in trace()
    monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
    text = trace()
    text, kept = re.subn(r"policy=<function save_only_these_names\S* at 0x[0-9a-f]+>", "policy=None", text)
    assert kept == 2 and "name[name=" not in text
    digest = hashlib.sha256(re.sub(r"\S+\.py:\d+", "", text).encode()).hexdigest()
    assert digest == "a657419c4fbed03ee795240caecefd875ba9df11195afd3e5d433944f995122b"
    assert model.counter_names == st.COUNTERS[:5] and SmallThinker.counter_names == st.COUNTERS


# ---- (e) through the normal path, one record a batch ------------------------------

ONE = {**TINY, "batch_size": 1}


def _token_files(tmp_path, ids):
    path = tmp_path / "tokens-000.txt"
    path.write_text(gen_tokens.encode_lines(ids))  # the benchmark's own record lines
    return [str(path)]


def test_token_pass_of_one_record_a_batch_through_dataset_and_trainer_against_the_reference(tmp_path):
    ids = np.random.default_rng(0).integers(0, V, (8, T))
    box = BoxWrapper(embedx_dim=H, sparse_opt=SparseOptimizerConfig(**ONE["sparse_opt"]), seed=7)
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1),
         SlotInfo("ids", type="float", dense=True, dim=T), SlotInfo("tokens")], label_slot="label")
    ds = box.make_dataset(schema, batch_size=1)
    ds.set_date("20260930")
    ds.set_filelist(_token_files(tmp_path, ids))
    ds.load_into_memory()
    ds.begin_pass()
    assert ds.store is not None and ds.ws.n_keys == len(np.unique(ids))
    ad = ONE["dense_opt"]
    tr = CTRTrainer(
        build.build(ONE, box.layout.pull_width),
        TrainStepConfig(num_slots=1, batch_size=1, layout=box.layout, sparse_opt=box.sparse_opt,
                        auc_buckets=1000),
        dense_opt=optax.adam(lambda n: ad["lr"] * jnp.minimum(1.0, (n + 1) / ad["warmup_steps"]),
                             b1=ad["b1"], b2=ad["b2"], eps=ad["eps"]),
        dense_slot="ids", dense_dim=T)
    assert tr.cfg.sequence_len == T  # the model object said so; no flag was set
    params = ref.init(jax.random.PRNGKey(1), ONE, 3 + H)
    tr.hand_over_dense(jax.tree.map(jnp.copy, params))
    assert tr._use_resident(ds, False, False)
    seen = []
    out = tr.train_pass(ds, n_batches=8, on_batch=lambda i, m: seen.append(m))
    assert out["batches"] == 8 and out["nan_batches"] == 0
    assert out["tokens"] == T and out["loss_in_window"] > 0 and out["loss_past_window"] > 0
    assert 0 < out["unrouted_tokens"] < 4 * T and out["block_rows"] >= out["held_assignments"] > 0
    assert STAT_GET("model.tokens_per_step") == T
    assert STAT_GET("model.unrouted_tokens_per_step") == pytest.approx(out["unrouted_tokens"])
    assert STAT_GET("model.block_rows_per_step") == pytest.approx(out["block_rows"])
    assert STAT_GET("model.attn.blocked_scores") > 0  # the CPU: the blocked form

    keys = np.unique(ids + token_step.KEY_BASE).astype(np.uint64)
    with jax.default_matmul_precision("highest"):
        want = token_step.run_steps(ref.forward, params, ONE, 7, ids.reshape(8, 1, T), keys)
    losses = np.asarray([float(m["loss"]) for m in seen])
    assert losses == pytest.approx(want["losses"], rel=2e-4)
    parts = np.stack([np.asarray(m["counters"][:2]) for m in seen])
    assert parts == pytest.approx(want["parts"], rel=2e-4)
    width = ds.table.layout.width
    rows = ds.ws.row_of_sorted[np.searchsorted(ds.ws.sorted_keys, keys)]
    open_rows = np.asarray(ds.device_table).reshape(-1, width)[rows]
    got = np.asarray(tr.trained_table_device().reshape(-1, width))[rows]
    assert np.array_equal(got[:, 0], want["rows"][:, 0])  # show counts the occurrences
    moved = np.linalg.norm(want["rows"][:, 3:3 + H] - open_rows[:, 3:3 + H])
    assert moved > 0 and np.linalg.norm(got[:, 3:3 + H] - want["rows"][:, 3:3 + H]) < 0.02 * moved
    for (path, a), b, o in zip(jax.tree_util.tree_flatten_with_path(tr.params)[0],
                               jax.tree.leaves(want["params"]), jax.tree.leaves(want["open_params"])):
        step = np.linalg.norm(b - o)
        assert step > 0, jax.tree_util.keystr(path)  # no buffer among the leaves: every one trains
        assert np.linalg.norm(np.asarray(a) - b) < 0.25 * step, jax.tree_util.keystr(path)
