"""How a block's rows join the token sum (``models/moe.py``:
``group_layout``, ``grouped_experts``, ``_add_rows``), every token model's
expert layer.

(a) ``routed_experts`` against a dense per-token sum, value and gradients,
over block heights below, at and above ``COMBINE_ROWS``; (b) value and ``dx``
bit for bit what one scatter-add of a whole block gave (the form PR 34
deleted, kept here as the oracle); (c) the order of a block's rows, which the
cut relies on; (d) the trace-time counters say how a call site was lowered;
(e) the height of a piece, by the rows of the token sum (PR 43); (f) the
token sum cut by columns into parts that fit fast memory, bit for bit the
one-part result (PR 44).
"""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddlebox_tpu.models import lm_layers, moe  # noqa: E402
from paddlebox_tpu.models import GlmMoeLiteConfig  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402

H, I = 16, 8
P = moe.COMBINE_ROWS


def _config(R: int, E: int, G: int, k: int, offset: int) -> GlmMoeLiteConfig:
    return GlmMoeLiteConfig(hidden_size=H, moe_intermediate_size=I, n_routed_experts=E,
                            num_experts_per_tok=k, experts_held=G, experts_offset=offset,
                            expert_block=R)


def _routed(p, x, idx, g, c):
    """``moe.routed_experts`` as a layer calls it, its share read off the config."""
    return moe.routed_experts(p, x, idx, g, c.experts_held, c.experts_offset, c.expert_block, "model")


def _inputs(seed: int, N: int, E: int, G: int, k: int, never=None):
    """Seeded weights of G held experts, N tokens, each routed to k distinct
    experts of E (none to ``never``) with weights g, and a cotangent."""
    rng = np.random.default_rng(seed)
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    p = {"gate": w(G, H, I), "up": w(G, H, I), "down": w(G, I, H)}
    score = rng.random((N, E))
    if never is not None:
        score[:, never] = -1.0
    idx = jnp.asarray(np.argsort(-score, axis=1)[:, :k], jnp.int32)
    g = jnp.asarray(rng.random((N, k)) + 0.1, jnp.float32)
    return p, w(N, H), idx, g, w(N, H)


def _dense(p, x, idx, g, c):
    """Every held expert over every token, weighed by what the token gave it."""
    y = jnp.zeros_like(x)
    for e in range(c.experts_held):
        weight = jnp.sum(jnp.where(idx == e + c.experts_offset, g, 0.0), axis=1, keepdims=True)
        y = y + lm_layers.swiglu(jax.tree.map(lambda a: a[e], p), x) * weight  # noqa: B023
    return y


def _rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ---- (a) against the dense sum ------------------------------------------------

CASES = {
    # name: (R, N, E, G, k, offset, never chosen)
    "block_of_8": (8, 40, 6, 3, 2, 1, None),
    "below_a_piece": (P // 2, 700, 4, 2, 2, 1, None),
    "one_piece": (P, 1400, 4, 2, 2, 0, None),
    "a_piece_and_eight_rows": (P + 8, 2 * P + 100, 2, 2, 1, 0, None),
    "three_pieces_the_last_all_padding": (3 * P, 3 * P, 2, 2, 1, 0, None),
    "an_expert_holds_nothing": (3 * P, 600, 4, 3, 2, 0, 1),
    "every_assignment_held": (8, 40, 3, 3, 2, 0, None),
    "every_assignment_held_tall": (P + P // 2, 2 * P, 2, 2, 2, 0, None),
    "none_held": (8, 40, 6, 2, 2, 6, None),
    "xing4s_block_into_4096_tokens": (896, 4096, 2, 2, 1, 0, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_routed_experts_give_the_dense_sum_and_its_gradients(case):
    R, N, E, G, k, offset, never = CASES[case]
    c = _config(R, E, G, k, offset)
    p, x, idx, g, dy = _inputs(list(CASES).index(case), N, E, G, k, never)

    def loss(fn):
        return lambda p, x, g: jnp.sum(fn(p, x, g) * dy)

    def routed(p, x, g):
        return _routed(p, x, idx, g, c)[0]

    y, counts = jax.jit(lambda p, x, g: _routed(p, x, idx, g, c))(p, x, g)
    held = np.bincount(np.asarray(idx).ravel() - offset + E, minlength=2 * E)[E:E + G]
    assert np.array_equal(np.asarray(counts), held)
    want = _dense(p, x, idx, g, c)
    got_grads = jax.jit(jax.grad(loss(routed), argnums=(0, 1, 2)))(p, x, g)
    want_grads = jax.grad(loss(lambda p, x, g: _dense(p, x, idx, g, c)), argnums=(0, 1, 2))(p, x, g)
    if case == "none_held":
        assert held.sum() == 0 and not np.any(np.asarray(y))
        assert not any(np.any(np.asarray(a)) for a in jax.tree.leaves(got_grads))
        return
    if case.startswith("every_assignment_held"):
        assert held.sum() == N * k  # the worst case the rows are sized for
    if case == "an_expert_holds_nothing":
        assert held[never] == 0 and not np.any(np.asarray(got_grads[0]["down"][never]))
    if case == "three_pieces_the_last_all_padding":
        assert P < held.max() <= 2 * P  # real rows in two thirds of a block, none in the last
    if case == "xing4s_block_into_4096_tokens":
        assert moe.combine_piece_rows(N) == 512 < R < held.min()  # cut blocks, full and part full
    assert _rel(y, want) < 1e-5
    for name, a, b in [("x", got_grads[1], want_grads[1]), ("g", got_grads[2], want_grads[2])] + [
            (n, got_grads[0][n], want_grads[0][n]) for n in ("gate", "up", "down")]:
        assert _rel(a, b) < 2e-5, name


# ---- (b) bit for bit the whole block's scatter-add ------------------------------

def _whole_block(acc, tb, rows):
    """The combine PR 34 deleted: one scatter-add of all the block's rows."""
    return acc.at[tb].add(rows, mode="drop")


@pytest.mark.parametrize("R", [8, 3 * P, 896])
def test_y_and_dx_are_bit_for_bit_what_one_scatter_add_a_block_gave(R, monkeypatch):
    """896: Xing4's block into 4,096 tokens, pieces of 512 + 384 under ``COMBINE_ROWS``."""
    N, E, G, k = {8: (40, 6, 3, 2), 3 * P: (3 * P, 2, 2, 1), 896: (4096, 2, 2, 1)}[R]
    assert moe.combine_piece_rows(N) < R or R == 8
    c = _config(R, E, G, k, 0)
    p, x, idx, g, dy = _inputs(R, N, E, G, k)

    def y_and_dx():
        y, vjp = jax.vjp(lambda x: _routed(p, x, idx, g, c)[0], x)
        return np.asarray(y), np.asarray(vjp(dy)[0])

    got = y_and_dx()
    monkeypatch.setattr(moe, "_add_rows", _whole_block)
    want = y_and_dx()
    assert np.any(want[0]) and np.any(want[1])
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])


# ---- (c) the order of a block's rows ----------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_a_blocks_real_rows_come_first_their_tokens_ascending_and_distinct(seed):
    rng = np.random.default_rng(340 + seed)
    N, E = int(rng.integers(4, 41)), int(rng.integers(2, 13))
    k, R = int(rng.integers(1, min(E, 4) + 1)), int(rng.integers(2, 17))
    G = int(rng.integers(1, E + 1))
    offset = int(rng.integers(0, E - G + 1))
    idx = np.argsort(rng.random((N, E)), axis=1)[:, :k]  # top_k: a token an expert once
    local = idx.reshape(-1) - offset
    expert_of = np.where((local >= 0) & (local < G), local, G)
    A = N * k
    src, blk, n_blocks, counts = (np.asarray(a) for a in moe.group_layout(jnp.asarray(expert_of), G, R))
    n_blocks = int(n_blocks)
    assert src.shape[0] == (-(-A // R) + G) * R  # rows for the worst case
    assert np.array_equal(counts, np.bincount(expert_of, minlength=G + 1)[:G])
    assert n_blocks == int(np.sum(-(-counts // R)))
    seen = []
    for j in range(n_blocks):
        rows = src[j * R:(j + 1) * R]
        n_real = int(np.sum(rows < A))
        assert n_real > 0 and np.all(rows[:n_real] < A) and np.all(rows[n_real:] == A)  # padding after
        assert np.all(expert_of[rows[:n_real]] == blk[j])  # one expert a block
        assert np.all(np.diff(rows[:n_real] // k) > 0)  # tokens strictly ascending: none twice
        seen.append(rows[:n_real])
    assert np.all(src[n_blocks * R:] == A)  # past the blocks in use: padding alone
    seen = np.concatenate(seen) if seen else np.zeros((0,), np.int64)
    assert np.array_equal(np.sort(seen), np.flatnonzero(expert_of < G))  # every held one, once


# ---- (d) the counters -----------------------------------------------------------

@pytest.mark.parametrize("R,N,pieces,piece_rows", [
    (8, 8 * P, 1, 8), (P, 8 * P, 1, P), (P + 8, 8 * P, 2, P), (3 * P, 8 * P, 3, P),  # an eighth of N is P
    (896, 4096, 2, 512), (P, 4096, 2, 512), (256, 4096, 1, 256),  # Xing4's tokens: pieces of 512
    (4 * P, 16 * P, 4, P), (8, 24, 1, 8)])  # never over P, never under 8
def test_the_counters_say_how_many_pieces_a_call_sites_block_took(R, N, pieces, piece_rows):
    c = _config(R, 4, 2, 2, 0)
    p, x, idx, g, dy = _inputs(1, N, 4, 2, 2)
    stats = lambda: np.array([STAT_GET("model.moe.combine_calls"),  # noqa: E731
                              STAT_GET("model.moe.combine_pieces")])
    piece = lambda: [STAT_GET("model.moe.combine_piece_rows"),  # noqa: E731
                     STAT_GET("model.moe.combine_piece_bytes")]
    before = stats()
    text = str(jax.make_jaxpr(lambda x: _routed(p, x, idx, g, c)[0])(x))
    assert (stats() - before).tolist() == [1, pieces]  # the forward's y
    assert text.count("scatter-add") == pieces
    assert piece() == [piece_rows, piece_rows * H * 4]  # float32 rows of H columns
    before = stats()
    jax.make_jaxpr(jax.grad(lambda x: jnp.sum(_routed(p, x, idx, g, c)[0] * dy)))(x)
    assert (stats() - before).tolist() == [2, 2 * pieces]  # the forward's y and the backward's dx
    assert piece() == [piece_rows, piece_rows * H * 4]


# ---- (e) the height of a piece -------------------------------------------------------

CELLS = {
    # cell: (expert_block, tokens a step, columns, the pieces a block takes)
    "glm47_flash": (512, 8192, 2048, [512]),
    "trinity_mini": (1536, 8192, 2048, [1024, 512]),
    "smallthinker_21b": (4096, 16384, 2560, [1024] * 4),
    "sdar_30b_a3b": (7168, 16384, 2048, [1024] * 7),
    "xing4_29b_a4b": (896, 4096, 3584, [512, 384]),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_a_cells_block_is_cut_an_eighth_of_its_tokens_tall_and_never_over_1024(cell):
    """The five token cells' blocks (``benchmark/configs/*.json``: ``expert_block``,
    ``batch_size`` x ``seq_len``, ``hidden_size``): four are cut as PR 34 cut
    them, Xing4's 896 rows into 4,096 tokens in two. The columns take no part."""
    R, N, C, want = CELLS[cell]
    acc = jax.ShapeDtypeStruct((N, C), jnp.float32)
    tb, rows = jax.ShapeDtypeStruct((R,), jnp.int32), jax.ShapeDtypeStruct((R, C), jnp.float32)
    eqns = jax.make_jaxpr(moe._add_rows)(acc, tb, rows).jaxpr.eqns
    got = [e.invars[2].aval.shape[0] for e in eqns if e.primitive.name == "scatter-add"]
    assert got == want and all(8 * h <= N for h in got)
    assert STAT_GET("model.moe.combine_piece_rows") == want[0]
    assert STAT_GET("model.moe.combine_piece_bytes") == want[0] * C * 4


@pytest.mark.parametrize("acc_rows", [1, 8, 24, 63, 64, 72, 1000, 4095, 4096, 4104, 8191, 8192, 8200,
                                      16384, 1 << 20])
def test_a_piece_is_whole_sublanes_never_none_never_over_1024_never_over_an_eighth(acc_rows):
    h = moe.combine_piece_rows(acc_rows)
    assert h % 8 == 0 and 8 <= h <= P
    assert 8 * h <= acc_rows or h == 8  # the as-written form's side of the line, where 8 rows can be
    assert h == P or 8 * (h + 8) > acc_rows  # and the tallest such


# ---- (f) the token sum cut by columns (PR 44) -------------------------------------

WIDE = 3 * 128  # three lane tiles: a sum of it can be cut in one, two or three parts


@pytest.mark.parametrize("parts,widths", [(2, (256, 128)), (3, (128, 128, 128))])
def test_a_sum_cut_by_columns_gives_the_one_part_result_bit_for_bit(parts, widths, monkeypatch):
    """Output and the five gradients (x, the gate, up and down products, the
    routing weights) of ``routed_experts`` with its token sum cut into 2 and 3
    parts by a smaller budget, against one part: several blocks an expert,
    each expert's last block real rows then padding, one held expert chosen by
    no token."""
    N, E, G, k, R = 60, 4, 3, 2, 8
    c = GlmMoeLiteConfig(hidden_size=WIDE, moe_intermediate_size=I, n_routed_experts=E,
                         num_experts_per_tok=k, experts_held=G, experts_offset=0, expert_block=R)
    rng = np.random.default_rng(44)
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    p = {"gate": w(G, WIDE, I), "up": w(G, WIDE, I), "down": w(G, I, WIDE)}
    score = rng.random((N, E))
    score[:, 1] = -1.0
    idx = jnp.asarray(np.argsort(-score, axis=1)[:, :k], jnp.int32)
    g = jnp.asarray(rng.random((N, k)) + 0.1, jnp.float32)
    x, dy = w(N, WIDE), w(N, WIDE)
    held = np.bincount(np.asarray(idx).ravel(), minlength=E)[:G]
    assert held[1] == 0 and (held[[0, 2]] > 2 * R).all() and (held[[0, 2]] % R).all()

    def run():
        def loss(p, x, g):
            return jnp.sum(_routed(p, x, idx, g, c)[0] * dy)
        y = jax.jit(lambda p, x, g: _routed(p, x, idx, g, c)[0])(p, x, g)
        return [np.asarray(a) for a in [y] + jax.tree.leaves(jax.jit(jax.grad(loss, (0, 1, 2)))(p, x, g))]

    assert moe.combine_parts(N, WIDE) == (WIDE,)
    want = run()
    monkeypatch.setattr(moe, "COMBINE_BYTES", N * WIDE * 4 // parts)
    assert moe.combine_parts(N, WIDE) == widths
    got = run()
    assert STAT_GET("model.moe.combine_parts") == parts
    assert STAT_GET("model.moe.combine_part_bytes") == N * widths[0] * 4
    assert all(np.any(a) for a in want)
    for a, b in zip(got, want):
        assert a.shape == b.shape and np.array_equal(a, b)


@pytest.mark.parametrize("rows,cols,widths", [
    (16384, 2048, (1024, 1024)),  # SDAR's cell: 128 MiB
    (16384, 2560, (1280, 1280)),  # SmallThinker's: 160 MiB
    (8192, 2048, (2048,)),  # GLM's and Trinity's: 64 MiB
    (8192, 3584, (1792, 1792)),  # 112 MiB: over the budget, though no cell has it
    (4096, 3584, (3584,)),  # Xing4's: 56 MiB
    (12288, 2048, (2048,)),  # 96 MiB: the budget itself
    (12296, 2048, (1024, 1024)),
    (16384, 3584, (1280, 1280, 1024)),
    (65536, 2560, (384,) * 6 + (256,)),
])
def test_a_token_sum_is_cut_in_parts_of_whole_lane_tiles_under_the_budget(rows, cols, widths):
    got = moe.combine_parts(rows, cols)
    assert got == widths and sum(got) == cols
    assert all(w % 128 == 0 for w in got[:-1]) and 0 < got[-1] <= got[0]
    assert len(got) <= -(-rows * cols * 4 // moe.COMBINE_BYTES)  # never more parts than the bytes ask
