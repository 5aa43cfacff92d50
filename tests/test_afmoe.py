"""Trinity's share (``models/afmoe.py``) at a tiny preset with every mechanism:
hidden 64, 4 query heads over 2 key-value heads of 16, a window of 16 in
records of 32, 1 dense + 3 expert layers of kinds sliding, full, sliding, 8
experts top 2 with 2 held beside the shared one, vocabulary 64.

(a) the program model against the plain reference on seeded weights; (b) the
attention block against a per-head, per-position loop; (c) the shares of an
expert-parallel group add up to the uncut layer; (d) routing; (e) through
``BoxPSDataset`` / ``CTRTrainer.train_pass`` with one record a batch against
the reference step loop.
"""

from __future__ import annotations

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.reference import afmoe as ref  # noqa: E402
from benchmark.reference import token_step  # noqa: E402
from paddlebox_tpu import BoxWrapper  # noqa: E402
from paddlebox_tpu.data import SlotInfo, SlotSchema  # noqa: E402
from paddlebox_tpu.models import afmoe  # noqa: E402
from paddlebox_tpu.models import lm_layers, moe  # noqa: E402
from paddlebox_tpu.models import Afmoe, AfmoeConfig  # noqa: E402
from paddlebox_tpu.table import SparseOptimizerConfig  # noqa: E402
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402

from benchmark.models import afmoe as build  # noqa: E402
from benchmark.tests import toy_trinity  # noqa: E402

# the benchmark's toy of the configuration file (num_experts = held,
# router_experts = the router's width), with a warm-up short enough to end
TINY = toy_trinity.cell()["cfg"]
TINY["dense_opt"] = {**TINY["dense_opt"], "lr": 3e-4, "warmup_steps": 4}
T, B, V, H = TINY["seq_len"], TINY["batch_size"], TINY["vocab_size"], TINY["hidden_size"]
W = TINY["sliding_window"]


def program_config(**over) -> AfmoeConfig:
    return build.build({**TINY, **over}, 3 + H).cfg


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
    model = Afmoe(program_config())
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
    # both parts: the targets inside the first window and those past it
    assert np.asarray(out["counters"][:2]) == pytest.approx(np.asarray(rout["parts"]), rel=1e-6)
    n_in, n_past = W, T - 1 - W  # the loss is their position-weighted mean: the plain mean
    assert float(loss) == pytest.approx(
        float(out["counters"][0] * n_in + out["counters"][1] * n_past) / (T - 1), rel=1e-6)
    fwd = jax.jit(model.forward)(params, emb, ids)
    assert fwd["token_logits"].shape == (2, B, T)  # one head: the target's logit, the logsumexp
    assert fwd["router_choices"].shape == (3, B, T, 2)
    # the same float32 but where a bfloat16 operand rounds the other way (the scores' blocks
    # sum in another order): most terms bit-equal, the rest a rounding of one operand apart
    gap = np.abs(np.asarray(fwd["token_logits"] - rout["token_logits"]))
    assert np.median(gap) < 1e-6 and gap.max() < 2e-3
    assert np.array_equal(np.sort(fwd["router_choices"], -1), np.sort(rout["router_choices"], -1))
    assert float(out["counters"][2]) == B * T
    assert float(out["counters"][3]) == np.isin(np.asarray(rout["router_choices"]), [2, 3]).sum()
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


def test_a_scan_step_told_its_kind_is_the_layer_of_that_kind(seeded):
    """The stack's one compiled body takes its kind as a traced flag; each
    kind given as a plain bool is the same layer."""
    params, emb, _ = seeded
    c = program_config()
    rope = lm_layers.rope_tables(T, c.head_dim, c.rope_theta)
    p = jax.tree.map(lambda a: a[0], params["moe"])
    outs = {}
    for sliding in (True, False):
        want = afmoe.moe_layer(p, emb, c, rope, sliding)[0]
        got = jax.jit(lambda s: afmoe.moe_layer(p, emb, c, rope, s)[0])(jnp.asarray(sliding))
        assert float(jnp.max(jnp.abs(got - want))) < 1e-5
        outs[sliding] = want
    assert _rel(outs[True], outs[False]) > 1e-3  # and the two kinds are two layers


# ---- (b) the attention block against a loop ----------------------------------

def _attention_loop(p, x, ln_in, ln_post, c, sliding, gate=True, qk_norm=True, window=True):
    """float64, one head and one position at a time."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    x, ln_in, ln_post = (np.asarray(a, np.float64) for a in (x, ln_in, ln_post))

    def norm(v, w):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + c.rms_norm_eps) * w

    def rot(v, t):  # rope on all of v's dims, halves paired, position t
        half = v.shape[-1] // 2
        ang = t / c.rope_theta ** (np.arange(half) * 2.0 / v.shape[-1])
        a, b = v[:half], v[half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang), b * np.cos(ang) + a * np.sin(ang)])

    nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    out = np.zeros_like(x)
    for b in range(x.shape[0]):
        a = norm(x[b], ln_in)
        q, k = (a @ p["q"]).reshape(T, nh, d), (a @ p["k"]).reshape(T, nkv, d)
        v, g = (a @ p["v"]).reshape(T, nkv, d), a @ p["gate"]
        if qk_norm:
            q, k = norm(q, p["q_norm"]), norm(k, p["k_norm"])
        heads = np.zeros((T, nh, d))
        for h in range(nh):
            kv = h // (nh // nkv)  # the group's one key-value head
            for t in range(T):
                first = max(0, t - c.sliding_window + 1) if sliding and window else 0
                qt = rot(q[t, h], t) if sliding else q[t, h]  # a full layer has no position
                s = np.array([qt @ (rot(k[u, kv], u) if sliding else k[u, kv])
                              for u in range(first, t + 1)]) / np.sqrt(d)
                w = np.exp(s - s.max())
                heads[t, h] = (w / w.sum()) @ v[first:t + 1, kv]
        o = heads.reshape(T, nh * d)
        if gate:
            o = o / (1.0 + np.exp(-g))
        out[b] = x[b] + norm(o @ p["o"], ln_post)
    return out


@pytest.fixture(scope="module")
def block(seeded):
    """One layer's attention weights, scaled so that scores, gate and norms
    are of order one (at 0.02 every softmax is flat and nothing shows)."""
    params, emb, _ = seeded
    rng = np.random.default_rng(6)
    p = jax.tree.map(lambda a: a * 8 if a.ndim == 2 else jnp.asarray(
        rng.uniform(0.5, 1.5, a.shape), jnp.float32), params["dense"][0]["attn"])
    lns = [jnp.asarray(rng.uniform(0.5, 1.5, (H,)), jnp.float32) for _ in range(2)]
    return p, emb, lns


@pytest.mark.parametrize("sliding", [True, False])
def test_attention_block_against_a_per_head_per_position_loop(block, sliding):
    p, emb, (ln_in, ln_post) = block
    c = program_config()
    rope = lm_layers.rope_tables(T, c.head_dim, c.rope_theta)
    got = np.asarray(afmoe.attention(p, emb, ln_in, ln_post, c, rope, sliding), np.float64)
    want = _attention_loop(p, emb, ln_in, ln_post, c, sliding)
    x = np.asarray(emb, np.float64)
    assert np.linalg.norm(got - want) / np.linalg.norm(want - x) < 0.02  # bfloat16 operands
    # each piece shown to matter: the loop without it is another function
    for off in ("gate", "qk_norm") + (("window",) if sliding else ()):
        other = _attention_loop(p, emb, ln_in, ln_post, c, sliding, **{off: False})
        assert np.linalg.norm(got - other) / np.linalg.norm(want - x) > 0.1, off
    # and rope is on the sliding layer alone: the loop of the other kind is another function
    other = _attention_loop(p, emb, ln_in, ln_post, c, not sliding, window=sliding)
    assert np.linalg.norm(got - other) / np.linalg.norm(want - x) > 0.1


def test_a_key_is_seen_at_window_minus_one_behind_and_not_at_window(block):
    """i - j = 2,047 is seen and 2,048 is not, at the toy's window of 16."""
    p, emb, (ln_in, ln_post) = block
    c = program_config()
    rope = lm_layers.rope_tables(T, c.head_dim, c.rope_theta)
    run = lambda e, s: np.asarray(afmoe.attention(p, e, ln_in, ln_post, c, rope, s) - e)  # noqa: E731
    j = 5
    emb2 = emb.at[:, j].add(1.0)
    for sliding in (True, False):
        moved = np.abs(run(emb2, sliding) - run(emb, sliding)).max(axis=(0, 2)) > 0  # by position
        assert not moved[:j].any() and moved[j]  # causal: no earlier query sees key j
        assert moved[j + W - 1]  # i - j = W - 1: seen
        assert moved[j + W:].any() == (not sliding)  # i - j >= W: only a full layer sees it
        if not sliding:
            assert moved[j:].all()


# ---- (c) the shares add up ---------------------------------------------------

def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(seeded):
    params, emb, _ = seeded
    whole_cfg = {**TINY, "num_experts": 8, "experts_offset": 0}
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
        idx, g = moe.route(part["router"], x, c.num_experts_per_tok, scale=c.route_scale)
        assert np.array_equal(np.sort(idx, -1), np.sort(chosen, -1))  # every chip routes alike
        routed, counts = moe.routed_experts(part["experts"], x, idx, g, c.experts_held,
                                            c.experts_offset, c.expert_block, "model")
        with jax.default_matmul_precision("highest"):  # and the reference is given the same share
            ref_share = ref.experts_part(part, x, {**TINY, "experts_offset": off}, m)[0] - shared
        assert _rel(routed, ref_share) < 1e-5
        total, loads = total + routed, loads + [np.asarray(counts)]
    assert _rel(total + shared, whole) < 1e-5
    # every assignment lands on exactly one chip's experts: none dropped, none twice
    assert np.concatenate(loads).sum() == B * T * 2
    assert np.array_equal(np.concatenate(loads), np.bincount(np.asarray(chosen).ravel(), minlength=8))


# ---- (d) routing -----------------------------------------------------------------

def test_routing_picks_8_by_score_plus_bias_weighs_by_normalised_score_and_drops_no_token():
    c = AfmoeConfig(hidden_size=H, num_experts=128, num_experts_per_tok=8, experts_held=16,
                    experts_offset=32, moe_intermediate_size=48, expert_block=8)
    assert c.route_scale == 2.826  # the published scale, the one ``moe.route`` is handed
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(40, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(H, 128)) * 0.3, jnp.float32)
    bias = np.zeros(128, np.float32)
    bias[32], bias[127] = 3.0, -3.0  # held expert 32 always chosen, 127 never
    idx, g = moe.route({"w": w, "bias": jnp.asarray(bias)}, x, c.num_experts_per_tok,
                       scale=c.route_scale)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(w, np.float64))))
    assert idx.shape == (40, 8)
    assert np.array_equal(np.sort(idx, -1), np.sort(np.argsort(-(s + bias), axis=1)[:, :8], -1))
    assert np.all(np.any(np.asarray(idx) == 32, axis=1)) and not np.any(np.asarray(idx) == 127)
    picked = np.take_along_axis(s, np.asarray(idx), axis=1)  # weights from s, not s + b
    assert np.asarray(g) == pytest.approx(picked / picked.sum(1, keepdims=True) * 2.826, rel=1e-5)
    assert np.asarray(g).sum(1) == pytest.approx(2.826, rel=1e-5)
    # every token on held expert 32 (local 0): 40 rows in blocks of 8, no capacity, none dropped
    experts = Afmoe(c)._mlp_init(jax.random.PRNGKey(0), 48, lead=(16,))
    y, counts = moe.routed_experts(experts, x, idx, g, c.experts_held, c.experts_offset,
                                   c.expert_block, "model")
    held = (np.asarray(idx) >= 32) & (np.asarray(idx) < 48)
    assert counts[0] == 40 and counts.sum() == held.sum()
    want = sum(lm_layers.swiglu(jax.tree.map(lambda a, e=e: a[e], experts), x)
               * jnp.sum(jnp.where(idx == 32 + e, g, 0.0), axis=1, keepdims=True) for e in range(16))
    assert _rel(y, want) < 1e-5


# ---- (e) through the normal path, one record a batch ------------------------------

ONE = {**TINY, "batch_size": 1}


def _token_files(tmp_path, ids):
    path = tmp_path / "tokens-000.txt"
    with open(path, "w") as f:
        for row in ids.tolist():
            f.write(f"1 0.0 {T} " + " ".join(f"{i}.0" for i in row) + f" {T} "
                    + " ".join(str(token_step.KEY_BASE + i) for i in row) + "\n")
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
    assert STAT_GET("model.tokens_per_step") == T
    assert STAT_GET("model.loss_past_window") == pytest.approx(out["loss_past_window"])
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
        if step > 0:  # the correction bias does not move, on either side
            assert np.linalg.norm(np.asarray(a) - b) < 0.25 * step, jax.tree_util.keystr(path)
        else:
            assert np.array_equal(np.asarray(a), o)
