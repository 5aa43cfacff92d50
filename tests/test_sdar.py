"""SDAR's share (``models/sdar.py``) at a tiny preset with every mechanism:
hidden 64, 6 query heads over 2 key-value heads of 16 with QK-norm, records of
32 tokens twice (clean, then noised) in blocks of 4 under the block-diffusion
mask, query blocks of 8, 4 layers of 8 experts top 2 with 2 held and no shared
one, vocabulary 64 whose last id is MASK.

(a) the program model against the plain reference on seeded weights; (b) the
visible set against a brute-force table, and what a position's logits may and
may not depend on; (c) the loss's weight against a hand count; (d) the shares
of an expert-parallel group add up to the uncut layer; (e) through
``BoxPSDataset`` / ``CTRTrainer.train_pass`` against the reference step loop,
one record a batch, the MASK key a third of the slot.
"""

from __future__ import annotations

import numpy as np
import optax
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark import gen_diffusion, gen_tokens  # noqa: E402
from benchmark.models import sdar as build  # noqa: E402
from benchmark.reference import sdar as ref  # noqa: E402
from benchmark.reference import token_step  # noqa: E402
from benchmark.tests import toy_sdar  # noqa: E402
from paddlebox_tpu import BoxWrapper  # noqa: E402
from paddlebox_tpu.data import SlotInfo, SlotSchema  # noqa: E402
from paddlebox_tpu.models import Sdar, SdarConfig, SmallThinker  # noqa: E402
from paddlebox_tpu.models import attention, lm_layers, moe  # noqa: E402
from paddlebox_tpu.models import sdar  # noqa: E402
from paddlebox_tpu.ops.pallas_kernels import diffusion_visible  # noqa: E402
from paddlebox_tpu.table import SparseOptimizerConfig  # noqa: E402
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402

CELL = toy_sdar.cell()
TINY = CELL["cfg"]
T, L, B, V, H = TINY["seq_len"], TINY["data_len"], TINY["batch_size"], TINY["vocab_size"], TINY["hidden_size"]
N, K, MASK = TINY["block_length"], TINY["num_experts_per_tok"], TINY["mask_id"]
HELD = [2, 3]  # experts_offset 2, two held


def program_config(**over) -> SdarConfig:
    return build.build({**TINY, **over}, 3 + H).cfg


@pytest.fixture(scope="module")
def seeded():
    params = ref.init(jax.random.PRNGKey(1), TINY, 3 + H)
    emb = jax.random.normal(jax.random.PRNGKey(2), (B, T, H)) * 0.5
    ids = jnp.asarray(gen_diffusion.make_pass(None, CELL["mix"], 7)[1][:B])
    return params, emb, ids


def _rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ---- (a) program against reference -------------------------------------------

def test_program_model_agrees_with_the_plain_reference(seeded):
    params, emb, ids = seeded
    model = Sdar(program_config())
    mine = model.init(jax.random.PRNGKey(5))
    assert jax.tree.structure(mine) == jax.tree.structure(params)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(params)]
    (loss, out), (gp, ge) = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True))(
        params, emb, ids.astype(jnp.float32))
    with jax.default_matmul_precision("highest"):
        (rloss, rout), (rgp, rge) = jax.jit(jax.value_and_grad(
            lambda p, e: ref.forward(p, e, ids, TINY), argnums=(0, 1), has_aux=True))(params, emb)
    assert float(loss) == pytest.approx(float(rloss), rel=1e-6)
    assert set(out) == {"counters"} and out["counters"].shape == (len(model.counter_names),) == (8,)
    counters = dict(zip(model.counter_names, np.asarray(out["counters"], np.float64)))
    # both parts, each over its L / 2 positions: their mean is the loss
    assert [counters["loss_first_half"], counters["loss_second_half"]] == pytest.approx(
        np.asarray(rout["parts"]), rel=1e-6)
    assert float(loss) == pytest.approx(
        (counters["loss_first_half"] + counters["loss_second_half"]) / 2, rel=1e-6)
    fwd = jax.jit(model.forward)(params, emb, ids)
    assert fwd["token_logits"].shape == (2, B, L) and fwd["router_choices"].shape == (4, B, T, K)
    gap = np.abs(np.asarray(fwd["token_logits"] - rout["token_logits"]))
    assert np.median(gap) < 1e-6 and gap.max() < 2e-3
    chosen = np.asarray(rout["router_choices"])
    assert np.array_equal(np.sort(fwd["router_choices"], -1), np.sort(chosen, -1))
    # the counters against the reference's choices and the record's ids: a record's rows are
    # its 2 L keys, the positions that carry loss the MASK ids of its second half
    held = np.isin(chosen, HELD)
    assert counters["tokens"] == B * T and counters["held_assignments"] == held.sum()
    assert counters["unrouted_tokens"] == (~held.any(-1)).sum() > 0
    assert counters["masked_positions"] == (np.asarray(ids)[:, L:] == MASK).sum() > 0
    loads = np.stack([[(chosen[l] == e).sum() for e in HELD] for l in range(4)])
    R = TINY["expert_block"]
    assert counters["block_rows"] == (-(-loads // R) * R).sum() >= counters["held_assignments"]
    assert counters["expert_load_max_over_mean"] == pytest.approx(loads.max() / loads.mean())
    # gradients of every leaf and of the pulled rows: the two differ by where a
    # bfloat16 cotangent is rounded, a few parts in a thousand of a leaf's norm
    flat, rflat = jax.tree_util.tree_flatten_with_path(gp)[0], jax.tree.leaves(rgp)
    floor = float(np.median([float(jnp.linalg.norm(r)) for r in rflat]))
    for (path, g), r in zip(flat, rflat):
        assert float(jnp.linalg.norm(r)) > 0, jax.tree_util.keystr(path)  # the QK-norm weights' too
        err = float(jnp.linalg.norm(g - r)) / max(float(jnp.linalg.norm(r)), 1e-3 * floor)
        assert err < 0.02, (jax.tree_util.keystr(path), err)
    assert _rel(ge, rge) < 5e-3
    # the clean half is context: its rows get a gradient through the noisy queries that see
    # them, and the last clean block, which no noisy query sees, gets none at all
    reached = np.any(np.asarray(ge)[:, :L] != 0, axis=-1)
    assert reached[:, :L - N].all() and not reached[:, L - N:].any()


def test_the_counters_are_published_under_their_own_names_and_smallthinkers(seeded):
    assert Sdar.counter_names == ("loss_first_half", "loss_second_half") + SmallThinker.counter_names[2:] + (
        "masked_positions",)
    Sdar.record_counters([1.5, 2.5, 128.0, 255.0, 1.25, 284.0, 280.0, 37.0])
    got = [STAT_GET(f"model.{n}") for n in (
        "loss_first_half", "loss_second_half", "tokens_per_step", "held_assignments_per_step",
        "expert_load_max_over_mean", "unrouted_tokens_per_step", "block_rows_per_step",
        "masked_positions_per_step")]
    assert got == [1.5, 2.5, 128.0, 255.0, 1.25, 284.0, 280.0, 37.0]
    with pytest.raises(ValueError, match="softmax over the chosen"):
        program_config(norm_topk_prob=False)
    with pytest.raises(ValueError, match="clean tokens and their noised copy"):
        program_config(data_len=L // 2)
    assert (program_config().group, program_config().data_len) == (3, L)


# ---- (b) the visible set ---------------------------------------------------------

def _brute_table(L: int, n: int) -> np.ndarray:
    """[2 L, 2 L] bool from the rule as the issue states it, index by index."""
    seen = np.zeros((2 * L, 2 * L), bool)
    for u in range(2 * L):
        for w in range(2 * L):
            bu, bw = u % L // n, w % L // n
            if u < L:
                seen[u, w] = w < L and bw <= bu
            else:
                seen[u, w] = (w < L and bw < bu) or (w >= L and bw == bu)
    return seen


@pytest.mark.parametrize("L,n", [(32, 4), (24, 8), (8, 1)])
def test_the_visible_set_is_the_rules_brute_force_table(L, n):
    u, w = np.arange(2 * L)[:, None], np.arange(2 * L)[None, :]
    want = _brute_table(L, n)
    assert np.array_equal(diffusion_visible(u, w, L, n), want)
    assert np.array_equal(np.asarray(ref.visible(jnp.asarray(u), jnp.asarray(w),
                                                 {"data_len": L, "block_length": n})), want)
    # a clean query sees n (blk + 1) keys, a noisy one n blk + n: no row is empty
    blk = np.arange(L) // n
    assert np.array_equal(want.sum(1), np.tile(n * (blk + 1), 2))
    assert want.sum() == L * L + n * L  # the pairs a head computes
    # the planted fault shows a noisy query the clean copy of its own block too
    leak = np.asarray(ref.visible(jnp.asarray(u), jnp.asarray(w),
                                  {"data_len": L, "block_length": n, "leak": True}))
    assert np.array_equal(leak[:L], want[:L]) and (leak & ~want).sum() == n * L
    assert not (want & ~leak).any()


def test_the_blocked_form_computes_the_table(seeded):
    """``_attend_block`` over all query blocks against one dense softmax under
    the brute-force table: group 3, a query block of two blocks."""
    ks = jax.random.split(jax.random.PRNGKey(39), 3)
    q = jax.random.normal(ks[0], (1, T, 6, 16))
    k, v = (jax.random.normal(a, (1, T, 2, 16)) for a in ks[1:])
    got = jnp.concatenate([attention._attend_block(q, k, v, i, 8, 0.25, 3, None, N)
                           for i in range(0, T, 8)], 1)
    kk, vv = (jnp.repeat(a, 3, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 0.25
    p = jax.nn.softmax(jnp.where(_brute_table(L, N), s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
    assert _rel(got, want) < 5e-3  # bfloat16 operands against float32


def test_a_positions_logits_read_its_own_noisy_block_and_the_clean_past_alone(seeded):
    params, emb, ids = seeded
    model = Sdar(program_config())
    run = jax.jit(lambda e: model.forward(params, e, ids)["token_logits"])
    base = np.asarray(run(emb))
    i = 13  # a noisy position of block 3: clean blocks 0 .. 2 and the noisy positions 12 .. 15
    blk = i // N

    def changed(at: int) -> np.ndarray:
        """Per noisy position: whether a change to the row at index ``at`` moved its logits."""
        moved = np.asarray(run(emb.at[0, at].add(1.0)))
        return np.any(moved[:, 0] != base[:, 0], axis=0)

    assert not changed(N * (blk + 1))[i]  # a later clean block: bit-equal
    assert not changed(N * blk + 1)[i]  # the clean copy of its own block: bit-equal too
    assert not changed(L + N * (blk - 1))[i] and not changed(L + N * (blk + 1))[i]  # other noisy blocks
    assert changed(L + N * blk)[i] and changed(L + i)[i]  # inside its own noisy block: both directions
    assert changed(N * blk - 1)[i]  # the clean past
    # and the whole pattern of one change: a noisy row moves its own block's positions alone
    assert np.array_equal(np.flatnonzero(changed(L + i)), np.arange(N * blk, N * (blk + 1)))
    # a clean row moves every position of the later blocks, none of its own or the earlier
    assert np.array_equal(np.flatnonzero(changed(N * blk + 1)), np.arange(N * (blk + 1), L))
    assert not np.any(np.asarray(run(emb.at[0, L + i].add(1.0)))[:, 1] != base[:, 1])  # the other record


# ---- (c) the loss's weight ---------------------------------------------------------

def test_the_loss_weighs_a_masked_position_by_the_blocks_count_and_no_other():
    c = SdarConfig(hidden_size=8, vocab_size=16, mask_id=15, seq_len=16, block_length=4,
                   loss_block=8, num_hidden_layers=1)
    # a record of 8 tokens twice: block 0 has one position masked, block 1 three
    clean = np.array([3, 1, 4, 1, 5, 9, 2, 6])
    noisy = np.array([3, 15, 4, 1, 15, 15, 2, 15])
    ids = jnp.asarray(np.concatenate([clean, noisy])[None])
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (1, 16, 8))
    params = {"final_norm": jnp.ones((8,)), "head": jax.random.normal(ks[1], (8, 16))}
    out = sdar.diffusion_loss(params, x, ids, c)
    tl, lse = np.asarray(out["token_logits"], np.float64)[:, 0]
    ce = lse - tl
    assert float(out["masked"]) == 4
    # 4 / 1 on the one of block 0, 4 / 3 on each of block 1's three, over L = 8
    want = (4.0 * ce[1] + 4.0 / 3.0 * (ce[4] + ce[5] + ce[7])) / 8.0
    assert float(out["loss"]) == pytest.approx(want, rel=1e-6)
    assert np.asarray(out["parts"]) == pytest.approx(
        [4.0 * ce[1] / 4.0, 4.0 / 3.0 * (ce[4] + ce[5] + ce[7]) / 4.0], rel=1e-6)
    # the target is the clean token at the same position (no shift), read from the noisy half's row
    h = np.asarray(lm_layers.rms_norm(x[:, 8:], params["final_norm"], c.rms_norm_eps))[0]
    logits = np.asarray(lm_layers._mm(jnp.asarray(h), params["head"]), np.float64)
    assert tl == pytest.approx(logits[np.arange(8), clean], rel=1e-5)
    # an unmasked position carries no loss: its clean id may be anything
    other = sdar.diffusion_loss(params, x, ids.at[0, 0].set(7), c)
    assert float(other["loss"]) == float(out["loss"])
    # the generator's records obey the rule the weight counts on
    recs = gen_diffusion.make_pass(None, CELL["mix"], 5)[1]
    m = (recs[:, L:] == MASK).reshape(len(recs), L // N, N).sum(-1)
    assert m.min() >= 1 and m.max() <= N and set(np.unique(m)) == {1, 2, 3, 4}
    assert np.array_equal(recs[:, L:][recs[:, L:] != MASK], recs[:, :L][recs[:, L:] != MASK])
    assert not (recs[:, :L] == MASK).any()


# ---- (d) the shares add up ---------------------------------------------------------

def test_eight_shares_feed_forward_parts_add_up_to_the_uncut_layer(seeded):
    params, emb, _ = seeded
    whole_cfg = {**TINY, "num_experts": 8, "experts_offset": 0}
    layer = ref._layer_init(jax.random.PRNGKey(9), whole_cfg)  # all 8 experts
    x = emb.reshape(B * T, H)
    m = ref._Math(jnp.float32, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        chosen, w = ref.route(layer, x, whole_cfg, m)
        whole = ref.experts_part(layer, x, chosen, w, whole_cfg, m)
    total, loads, served = jnp.zeros_like(whole), [], np.zeros(B * T, int)
    for off in range(8):  # eight chips of one expert each
        c = program_config(experts_offset=off, num_experts=1)
        experts = jax.tree.map(lambda a: a[off:off + 1], layer["experts"])
        idx, g = moe.route(layer["router"], x, c.num_experts_per_tok, form="softmax_of_chosen")
        assert np.array_equal(np.sort(idx, -1), np.sort(chosen, -1))  # every chip routes alike
        routed, counts = moe.routed_experts(experts, x, idx, g, c.experts_held, c.experts_offset,
                                            c.expert_block, "model")
        here = np.any(np.asarray(idx) == off, axis=1)
        assert 0 < (~here).sum() < B * T  # no shared expert: those rows add exactly zero here
        assert not np.any(np.asarray(routed)[~here])
        total, loads, served = total + routed, loads + [np.asarray(counts)], served + here
    assert _rel(total, whole) < 1e-5
    assert np.all(served == K) and np.concatenate(loads).sum() == B * T * K
    assert np.array_equal(np.concatenate(loads), np.bincount(np.asarray(chosen).ravel(), minlength=8))


# ---- (e) through the normal path, one record a batch ------------------------------

ONE = {**TINY, "batch_size": 1}


def test_diffusion_pass_through_dataset_and_trainer_against_the_reference(tmp_path):
    ids = gen_diffusion.make_pass(None, {**CELL["mix"], "train_records": 8}, 3)[1]
    masked = (ids[:, L:] == MASK).sum(1)
    assert ids.shape == (8, T) and masked.min() > T // 5  # one key is a third of the slot
    path = tmp_path / "tokens-000.txt"
    path.write_text(gen_tokens.encode_lines(ids))  # the benchmark's own record lines
    box = BoxWrapper(embedx_dim=H, sparse_opt=SparseOptimizerConfig(**ONE["sparse_opt"]), seed=7)
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1),
         SlotInfo("ids", type="float", dense=True, dim=T), SlotInfo("tokens")], label_slot="label")
    ds = box.make_dataset(schema, batch_size=1)
    ds.set_date("20260930")
    ds.set_filelist([str(path)])
    ds.load_into_memory()
    ds.begin_pass()
    assert ds.store is not None and ds.ws.n_keys == len(np.unique(ids))
    ad = {**ONE["dense_opt"], "lr": 3e-4, "warmup_steps": 4}  # a warm-up short enough to end
    one = {**ONE, "dense_opt": ad}
    tr = CTRTrainer(
        build.build(one, box.layout.pull_width),
        TrainStepConfig(num_slots=1, batch_size=1, layout=box.layout, sparse_opt=box.sparse_opt,
                        auc_buckets=1000),
        dense_opt=optax.adam(lambda n: ad["lr"] * jnp.minimum(1.0, (n + 1) / ad["warmup_steps"]),
                             b1=ad["b1"], b2=ad["b2"], eps=ad["eps"]),
        dense_slot="ids", dense_dim=T)
    assert tr.cfg.sequence_len == T  # the model object said so: a record's 2 L keys
    params = ref.init(jax.random.PRNGKey(1), one, 3 + H)
    tr.hand_over_dense(jax.tree.map(jnp.copy, params))
    assert tr._use_resident(ds, False, False)
    seen = []
    out = tr.train_pass(ds, n_batches=8, on_batch=lambda i, m: seen.append(m))
    assert out["batches"] == 8 and out["nan_batches"] == 0
    assert out["tokens"] == T and out["masked_positions"] == pytest.approx(masked.mean())
    assert out["loss_first_half"] > 0 and out["loss_second_half"] > 0
    assert 0 < out["unrouted_tokens"] < 4 * T and out["block_rows"] >= out["held_assignments"] > 0
    assert STAT_GET("model.masked_positions_per_step") == pytest.approx(out["masked_positions"])
    assert STAT_GET("model.attn.blocked_scores") > 0  # the CPU: the blocked form

    keys = np.unique(ids + token_step.KEY_BASE).astype(np.uint64)
    with jax.default_matmul_precision("highest"):
        want = token_step.run_steps(ref.forward, params, one, 7, ids.reshape(8, 1, T), keys)
    losses = np.asarray([float(m["loss"]) for m in seen])
    assert losses == pytest.approx(want["losses"], rel=2e-4)
    parts = np.stack([np.asarray(m["counters"][:2]) for m in seen])
    assert parts == pytest.approx(want["parts"], rel=2e-4)
    width = ds.table.layout.width
    rows = ds.ws.row_of_sorted[np.searchsorted(ds.ws.sorted_keys, keys)]
    open_rows = np.asarray(ds.device_table).reshape(-1, width)[rows]
    got = np.asarray(tr.trained_table_device().reshape(-1, width))[rows]
    assert np.array_equal(got[:, 0], want["rows"][:, 0])  # show counts the occurrences
    at = np.searchsorted(keys, np.uint64(token_step.KEY_BASE + MASK))
    assert got[at, 0] - open_rows[at, 0] == masked.sum()  # the MASK row gathered every masked position
    moved = np.linalg.norm(want["rows"][:, 3:3 + H] - open_rows[:, 3:3 + H])
    assert moved > 0 and np.linalg.norm(got[:, 3:3 + H] - want["rows"][:, 3:3 + H]) < 0.02 * moved
    for (path, a), b, o in zip(jax.tree_util.tree_flatten_with_path(tr.params)[0],
                               jax.tree.leaves(want["params"]), jax.tree.leaves(want["open_params"])):
        step = np.linalg.norm(b - o)
        assert step > 0, jax.tree_util.keystr(path)  # no buffer among the leaves: every one trains
        assert np.linalg.norm(np.asarray(a) - b) < 0.25 * step, jax.tree_util.keystr(path)
