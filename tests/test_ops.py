"""Op-level numeric tests (OpTest-style parity harness, SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlebox_tpu.ops import (
    cvm_transform,
    fused_seqpool_cvm,
    pull_sparse_rows,
    push_sparse_rows,
)
from paddlebox_tpu.table import SparseOptimizerConfig, ValueLayout
from paddlebox_tpu.table.value_layout import FeatureType


LAY = ValueLayout(embedx_dim=4)


def _table(rows=8, show=None, seed=0):
    rng = np.random.default_rng(seed)
    t = rng.normal(size=(rows, LAY.width)).astype(np.float32)
    t[:, LAY.SHOW] = show if show is not None else 20.0
    t[:, LAY.CLK] = 1.0
    t[:, LAY.embed_g2_col] = 0.0
    t[:, LAY.embedx_g2_col] = 0.0
    return jnp.asarray(t)


def test_pull_layout_and_gating():
    t = _table()
    t = t.at[1, LAY.SHOW].set(0.0)  # below threshold -> embedx masked
    pulled = pull_sparse_rows(t, jnp.array([0, 1]), LAY, embedx_threshold=10.0, scale=2.0)
    assert pulled.shape == (2, LAY.pull_width)
    np.testing.assert_allclose(pulled[0, :3], t[0, :3])
    np.testing.assert_allclose(pulled[0, 3:], t[0, 3:7] * 2.0, rtol=1e-6)
    np.testing.assert_allclose(pulled[1, 3:], 0.0)


def test_cvm_transform():
    pooled = jnp.array([[3.0, 1.0, 0.7, 0.2]])
    out = cvm_transform(pooled, use_cvm=True)
    np.testing.assert_allclose(out[0, 0], np.log(4.0), rtol=1e-6)
    np.testing.assert_allclose(out[0, 1], np.log(2.0) - np.log(4.0), rtol=1e-6)
    np.testing.assert_allclose(out[0, 2:], [0.7, 0.2])
    out2 = cvm_transform(pooled, use_cvm=False)
    np.testing.assert_allclose(out2[0], [0.7, 0.2])


def test_fused_seqpool_cvm_matches_numpy():
    S, B, W = 2, 3, LAY.pull_width
    rng = np.random.default_rng(1)
    # ragged: lengths per (slot, ins)
    lens = np.array([[2, 1, 3], [1, 2, 1]])
    L = lens.sum()
    recs = np.abs(rng.normal(size=(L, W))).astype(np.float32)
    segs = np.repeat(np.arange(S * B), lens.reshape(-1)).astype(np.int32)

    out = fused_seqpool_cvm(jnp.asarray(recs), jnp.asarray(segs), S, B, use_cvm=True)
    assert out.shape == (B, S, W)

    # numpy reference
    pooled = np.zeros((S * B, W), dtype=np.float32)
    np.add.at(pooled, segs, recs)
    pooled = pooled.reshape(S, B, W)
    expect = pooled.copy()
    expect[..., 0] = np.log(pooled[..., 0] + 1)
    expect[..., 1] = np.log(pooled[..., 1] + 1) - np.log(pooled[..., 0] + 1)
    np.testing.assert_allclose(out, np.transpose(expect, (1, 0, 2)), rtol=1e-3, atol=1e-4)


def test_fused_seqpool_padding_goes_to_trash_segment():
    S, B, W = 1, 2, LAY.pull_width
    recs = jnp.ones((4, W))
    segs = jnp.array([0, 1, S * B, S * B], dtype=jnp.int32)  # 2 pads
    out = fused_seqpool_cvm(recs, segs, S, B, use_cvm=False)
    np.testing.assert_allclose(out[:, 0, :], 1.0)  # each ins pooled exactly 1 record


def test_push_updates_counters_and_weights():
    opt = SparseOptimizerConfig(embed_lr=0.1, embedx_lr=0.1, embedx_threshold=10.0)
    t = _table()
    rows = jnp.array([2, 5])
    g = jnp.ones((2, LAY.pull_width), jnp.float32) * 0.5
    show_c = jnp.array([3.0, 1.0])
    clk_c = jnp.array([1.0, 0.0])
    t2 = push_sparse_rows(t, rows, g, show_c, clk_c, LAY, opt)

    np.testing.assert_allclose(t2[2, LAY.SHOW], t[2, LAY.SHOW] + 3.0)
    np.testing.assert_allclose(t2[2, LAY.CLK], t[2, LAY.CLK] + 1.0)
    # embed_w moved against the gradient
    assert float(t2[2, LAY.embed_w_col]) < float(t[2, LAY.embed_w_col])
    # g2 accumulated
    assert float(t2[2, LAY.embed_g2_col]) > 0.0
    # untouched rows unchanged
    np.testing.assert_array_equal(t2[0], t[0])


def test_push_embedx_gated_below_threshold():
    opt = SparseOptimizerConfig(embedx_threshold=10.0)
    t = _table(show=1.0)  # below threshold
    rows = jnp.array([0])
    g = jnp.ones((1, LAY.pull_width), jnp.float32)
    t2 = push_sparse_rows(t, rows, g, jnp.ones(1), jnp.zeros(1), LAY, opt)
    # embedx unchanged, embed_w still updates
    np.testing.assert_array_equal(t2[0, LAY.embedx_col : LAY.embedx_col + 4],
                                  t[0, LAY.embedx_col : LAY.embedx_col + 4])
    assert float(t2[0, LAY.embed_w_col]) != float(t[0, LAY.embed_w_col])


def test_adagrad_step_decays_with_g2():
    opt = SparseOptimizerConfig(embed_lr=0.1, initial_g2sum=1.0)
    t = _table()
    rows = jnp.array([0])
    g = jnp.zeros((1, LAY.pull_width), jnp.float32).at[0, 2].set(1.0)
    w0 = float(t[0, LAY.embed_w_col])
    t1 = push_sparse_rows(t, rows, g, jnp.ones(1), jnp.zeros(1), LAY, opt)
    d1 = w0 - float(t1[0, LAY.embed_w_col])
    t2 = push_sparse_rows(t1, rows, g, jnp.ones(1), jnp.zeros(1), LAY, opt)
    d2 = float(t1[0, LAY.embed_w_col]) - float(t2[0, LAY.embed_w_col])
    assert 0 < d2 < d1  # adagrad: later identical grads take smaller steps


def test_variable_feature_type_graded_dims():
    """B3 VARIABLE: effective embedx dim unlocks in quarters as show crosses
    doubling thresholds (cvm_offset stays 3, same row width)."""
    import jax.numpy as jnp

    from paddlebox_tpu.ops.pull_push import pull_sparse_rows
    from paddlebox_tpu.table.value_layout import FeatureType, ValueLayout

    lay = ValueLayout(embedx_dim=8, feature_type=FeatureType.VARIABLE)
    assert lay.cvm_offset == 3
    assert lay.width == ValueLayout(embedx_dim=8).width

    T = 10.0
    table = np.ones((5, lay.width), np.float32)
    # shows: cold, >=T, >=2T, >=4T, >=8T
    table[:, lay.SHOW] = [1.0, 10.0, 20.0, 40.0, 80.0]
    rows = jnp.arange(5, dtype=jnp.int32)
    out = np.asarray(pull_sparse_rows(jnp.asarray(table), rows, lay, T, 1.0))
    emb = out[:, lay.cvm_offset :]
    active_dims = (emb != 0).sum(axis=1)
    assert list(active_dims) == [0, 2, 4, 6, 8]
    # threshold 0 == full dims everywhere (plain behavior)
    out0 = np.asarray(pull_sparse_rows(jnp.asarray(table), rows, lay, 0.0, 1.0))
    assert ((out0[:, lay.cvm_offset :] != 0).sum(axis=1) == 8).all()


def test_variable_feature_type_trains():
    """Masked dims receive no gradient; training stays finite and learns."""
    import jax
    import jax.numpy as jnp
    import optax

    from paddlebox_tpu.models import LogisticRegression
    from paddlebox_tpu.table import (
        HostSparseTable,
        PassWorkingSet,
        SparseOptimizerConfig,
    )
    from paddlebox_tpu.table.value_layout import FeatureType, ValueLayout
    from paddlebox_tpu.data.slot_record import SlotRecord, build_batch
    from paddlebox_tpu.data.slot_schema import SlotInfo, SlotSchema
    from paddlebox_tpu.data.device_pack import pack_batch
    from paddlebox_tpu.train import TrainStepConfig
    from paddlebox_tpu.train.train_step import (
        init_train_state,
        jit_train_step,
        make_train_step,
    )

    lay = ValueLayout(embedx_dim=8, feature_type=FeatureType.VARIABLE)
    opt = SparseOptimizerConfig(embed_lr=0.3, embedx_threshold=4.0, initial_range=0.01)
    rng = np.random.default_rng(0)
    NS, B = 3, 16
    recs = []
    for _ in range(4 * B):
        keys = rng.integers(1, 40, NS).astype(np.uint64)  # hot: shows accumulate
        recs.append(SlotRecord(
            u64_values=keys,
            u64_offsets=np.arange(NS + 1, dtype=np.uint32),
            f_values=np.array([float(keys[0] % 2)], np.float32),
            f_offsets=np.array([0, 1], np.uint32),
        ))
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(NS)],
        label_slot="label",
    )
    table = HostSparseTable(lay, opt, n_shards=2, seed=0)
    ws = PassWorkingSet()
    for r in recs:
        ws.add_keys(r.u64_values)
    dev = ws.finalize(table, round_to=32)
    model = LogisticRegression(num_slots=NS, feat_width=lay.pull_width)
    cfg = TrainStepConfig(num_slots=NS, batch_size=B, layout=lay,
                          sparse_opt=opt, auc_buckets=500)
    step = jit_train_step(make_train_step(model.apply, optax.adam(1e-2), cfg))
    state = init_train_state(
        jnp.asarray(dev.reshape(-1, lay.width)),
        model.init(jax.random.PRNGKey(0)), optax.adam(1e-2), 500,
    )
    losses = []
    for ep in range(6):
        for bi in range(4):
            batch = build_batch(recs[bi * B : (bi + 1) * B], schema)
            db = pack_batch(batch, ws, schema, bucket=64)
            state, m = step(state, {k: jnp.asarray(v) for k, v in db.as_dict().items()})
            losses.append(float(m["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    tbl = np.asarray(state.table)
    assert np.isfinite(tbl).all()


def test_variable_locked_dims_never_trained():
    """Push applies the same graded mask as pull: a locked quarter-dim
    receives no update and no g2 energy even when the model's gradient
    w.r.t. the (zeroed) pulled value is nonzero."""
    import jax.numpy as jnp

    from paddlebox_tpu.ops.pull_push import sparse_update_rows
    from paddlebox_tpu.table import SparseOptimizerConfig
    from paddlebox_tpu.table.value_layout import FeatureType, ValueLayout

    lay = ValueLayout(embedx_dim=8, feature_type=FeatureType.VARIABLE)
    opt = SparseOptimizerConfig(embedx_threshold=10.0, embedx_lr=0.5)
    old = np.ones((2, lay.width), np.float32)
    old[0, lay.SHOW] = 20.0  # half the dims unlocked (>=T, >=2T)
    old[1, lay.SHOW] = 160.0  # all unlocked
    grads = np.full((2, lay.pull_width), 1.0, np.float32)  # phantom grads too
    new = np.asarray(
        sparse_update_rows(
            jnp.asarray(old), jnp.asarray(grads),
            jnp.zeros(2), jnp.zeros(2), lay, opt,
        )
    )
    co = lay.cvm_offset
    emb_old, emb_new = old[:, co : co + 8], new[:, co : co + 8]
    # row 0: first 4 dims trained, locked upper 4 bit-identical
    assert (emb_new[0, :4] != emb_old[0, :4]).all()
    np.testing.assert_array_equal(emb_new[0, 4:], emb_old[0, 4:])
    # row 1: everything trained
    assert (emb_new[1] != emb_old[1]).all()
    # g2 energy reflects only unlocked dims: row 0 accumulated half of row 1
    g2 = new[:, lay.embedx_g2_col] - old[:, lay.embedx_g2_col]
    assert abs(g2[0] - 0.5 * g2[1]) < 1e-6


# ---- every feature type's pull and push against numpy ------------------------------

THRESHOLD, SCALE = 10.0, 0.5
ALL_OPT = SparseOptimizerConfig(embed_lr=0.3, embedx_lr=0.2, initial_g2sum=2.0,
                                embedx_threshold=THRESHOLD, weight_bounds=0.8)


def _np_active(lay: ValueLayout, show: np.ndarray) -> np.ndarray:
    """[U, D] bool: a VARIABLE row unlocks its columns by quarters at doubling
    thresholds, every other type the whole vector at the threshold."""
    D = lay.embedx_dim
    if lay.feature_type is FeatureType.VARIABLE:
        need = THRESHOLD * 2.0 ** (np.arange(D) * 4 // D)
        return show[:, None] >= need[None, :]
    return np.repeat((show >= THRESHOLD)[:, None], D, axis=1)


def _np_pull(lay, table, rows):
    picked, co = table[rows], lay.cvm_offset
    embedx = np.where(_np_active(lay, picked[:, lay.SHOW]), picked[:, co:co + lay.embedx_dim] * SCALE, 0.0)
    return np.concatenate([picked[:, :co], embedx], axis=1)


def _np_push(lay, table, rows, grads, show_c, clk_c, lr_scale, opt):
    """Rows are distinct, so the scatter-add of the delta is a set."""
    out, old = table.astype(np.float64), table[rows].astype(np.float64)
    co, D, g = lay.cvm_offset, lay.embedx_dim, grads.astype(np.float64)
    new = old.copy()
    new[:, lay.SHOW] += show_c
    new[:, lay.CLK] += clk_c
    # one adagrad scalar over columns 2..cvm_offset: CONV's and PCOC's extras and embed_w
    g2_e = old[:, lay.embed_g2_col] + (g[:, 2:co] ** 2).sum(axis=1)
    step = opt.embed_lr * lr_scale * np.sqrt(opt.initial_g2sum / (opt.initial_g2sum + g2_e))
    new[:, 2:co] = np.clip(old[:, 2:co] - step[:, None] * g[:, 2:co], -opt.weight_bounds, opt.weight_bounds)
    # the embedx vector with its one shared g2 (mean energy), behind the pull's mask
    gx = np.where(_np_active(lay, old[:, lay.SHOW]), g[:, co:co + D], 0.0)
    g2_x = old[:, lay.embedx_g2_col] + (gx ** 2).mean(axis=1)
    step = opt.embedx_lr * lr_scale * np.sqrt(opt.initial_g2sum / (opt.initial_g2sum + g2_x))
    new[:, co:co + D] = np.clip(old[:, co:co + D] - step[:, None] * gx, -opt.weight_bounds, opt.weight_bounds)
    new[:, lay.embed_g2_col], new[:, lay.embedx_g2_col] = g2_e, g2_x
    out[rows] = new
    return out


@pytest.mark.parametrize("op", ["pull", "push"])
@pytest.mark.parametrize("ft", list(FeatureType), ids=lambda ft: ft.value)
def test_pull_and_push_match_numpy_for_every_feature_type(ft, op):
    lay = ValueLayout(embedx_dim=8, feature_type=ft,
                      expand_embed_dim=8 if ft is FeatureType.SHARE_EMBEDDING else 0)
    assert lay.cvm_offset == {"conv": 4, "pcoc": 8, "share_embedding": 10}.get(ft.value, 3)
    assert lay.width == lay.cvm_offset + 8 + 2 and lay.embedx_g2_col == lay.width - 1
    rng = np.random.default_rng(31)
    table = rng.uniform(-0.7, 0.7, (12, lay.width)).astype(np.float32)
    # shows on both sides of the threshold and of each of VARIABLE's quarters
    table[:, lay.SHOW] = [0, 9, 10, 19, 20, 39, 40, 79, 80, 200, 5, 50]
    table[:, lay.CLK] = rng.integers(0, 5, 12)
    table[:, lay.embed_g2_col:] = rng.uniform(0, 2, (12, 2))
    rows = np.array([9, 0, 3, 4, 7, 8, 2, 11], np.int32)  # distinct; 1, 5, 6, 10 stay
    if op == "pull":
        got = pull_sparse_rows(jnp.asarray(table), jnp.asarray(rows), lay, THRESHOLD, SCALE)
        assert got.shape == (len(rows), lay.pull_width)
        np.testing.assert_allclose(got, _np_pull(lay, table, rows), rtol=1e-6)
        return
    grads = rng.normal(0, 1.5, (len(rows), lay.pull_width)).astype(np.float32)  # some steps reach the clip
    show_c = rng.integers(1, 4, len(rows)).astype(np.float32)
    clk_c = rng.integers(0, 2, len(rows)).astype(np.float32)
    lr_scale = rng.uniform(0.5, 2.0, len(rows)).astype(np.float32)
    grads[3], show_c[3], clk_c[3] = 0.0, 0.0, 0.0  # an all-zero record
    got = np.asarray(push_sparse_rows(
        jnp.asarray(table), jnp.asarray(rows), jnp.asarray(grads), jnp.asarray(show_c),
        jnp.asarray(clk_c), lay, ALL_OPT, jnp.asarray(lr_scale)))
    want = _np_push(lay, table, rows, grads, show_c, clk_c, lr_scale, ALL_OPT)
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(got[[1, 5, 6, 10]], table[[1, 5, 6, 10]])  # rows not pushed
    np.testing.assert_allclose(got[rows[3]], table[rows[3]], rtol=0, atol=1e-7)  # the zero record: identity
    assert (np.abs(want[rows, 2:lay.cvm_offset + 8]) == ALL_OPT.weight_bounds).any()  # the clip was reached
    moved = np.abs(got[rows] - table[rows])[np.arange(len(rows)) != 3]
    assert (moved[:, 2:lay.cvm_offset] > 0).all()  # every cvm extra and embed_w is trained


# ---- the table's gather and scatter are XLA's, under their scopes, at any width -----

def _primitives(jaxpr, prefix=""):
    """(primitive, named scope) of every equation, through nested jits."""
    for eqn in jaxpr.eqns:
        scope = "/".join(s for s in (prefix, str(eqn.source_info.name_stack)) if s)
        yield eqn.primitive.name, scope
        for sub in eqn.params.values():
            if hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):  # a ClosedJaxpr
                yield from _primitives(sub.jaxpr, scope)


@pytest.mark.parametrize("embedx,uniq", [
    (16, 4096), (64, 4100),  # the CTR cells' rows: 21 and 69 columns
    (123, 4096),             # 128 columns and U % 8 == 0: a lane-aligned row
    (2048, 3000),            # the token cell's row
])
def test_table_ops_lower_to_xla_gather_and_scatter_at_every_width(embedx, uniq):
    lay, opt = ValueLayout(embedx_dim=embedx), SparseOptimizerConfig()

    def step(table, rows, grads, show, clk):
        with jax.named_scope("pull"):
            pulled = pull_sparse_rows(table, rows, lay, opt.embedx_threshold)
        with jax.named_scope("push"):
            return pulled, push_sparse_rows(table, rows, grads, show, clk, lay, opt)

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    args = (f32(8192, lay.width), jax.ShapeDtypeStruct((uniq,), jnp.int32),
            f32(uniq, lay.pull_width), f32(uniq), f32(uniq))
    text = jax.jit(step).lower(*args).as_text()
    assert "custom_call" not in text
    assert text.count('"stablehlo.scatter"') == 1
    ops = list(_primitives(jax.make_jaxpr(step)(*args).jaxpr))
    assert [o for o in ops if o[0].startswith(("gather", "scatter"))] == [
        ("gather", "pull/table_gather"), ("gather", "push/table_gather"),
        ("scatter-add", "push/table_scatter")]
    assert not [o for o in ops if "call" in o[0]], ops  # no kernel, no callback
