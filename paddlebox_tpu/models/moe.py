"""The expert layer every token model runs: the router, the held experts'
grouped products with a hand-written backward, and the combine that joins
their rows to the token sum. A layer computes one chip's share of an
expert-parallel group: every token routed over all experts, the part of the
``held`` ones from ``offset`` on computed, the absent ones' left out.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from paddlebox_tpu.models.lm_layers import BF16, F32, _mm
from paddlebox_tpu.ops.pallas_kernels import LANE, SCORES_LSE, SCORES_OUT
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_SET

# the counters of a share with no shared expert, after a model's first five
SHARE_COUNTERS = ("unrouted_tokens", "block_rows")

# The grouped product's output, by name, and the one policy of every expert layer's checkpoint:
# ``KEEP_SCORES``' two names and this one. Where the layer's backward reads the output (a norm
# or a mixing map after the expert sum) it is kept and the recomputation's blocks' pass is dead
# code; where only an add reads it, no backward does, and nothing is stored.
EXPERTS_OUT = "grouped_experts_out"
KEEP_EXPERTS = jax.checkpoint_policies.save_only_these_names(SCORES_OUT, SCORES_LSE, EXPERTS_OUT)


def route(p, x, top_k: int, *, scale: float = 1.0, form: str = "sigmoid_bias_norm"):
    """x [N, H] -> (chosen experts [N, top_k] int32, their weights [N, top_k]).
    ``form`` (static) is the router's: ``sigmoid_bias_norm`` chooses by
    sigmoid + bias and weighs by the chosen sigmoids over their sum, times
    ``scale``; ``softmax_of_chosen`` chooses by the logits and weighs by a
    softmax over the chosen ones (a softmax over all of them renormalised over
    the chosen is the same numbers) and reads neither a bias nor a scale."""
    s = jnp.dot(x.astype(F32), p["w"], precision=lax.Precision.HIGHEST)
    if form == "softmax_of_chosen":
        chosen, idx = lax.top_k(s, top_k)
        return idx.astype(jnp.int32), jax.nn.softmax(chosen, axis=1)
    if form != "sigmoid_bias_norm":
        raise ValueError(f"router form {form!r}")
    s = jax.nn.sigmoid(s)
    _, idx = lax.top_k(s + lax.stop_gradient(p["bias"]), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=1)
    g = chosen / jnp.sum(chosen, axis=1, keepdims=True) * scale
    return idx.astype(jnp.int32), g


def group_layout(expert_of, G: int, R: int):
    """Assignments sorted by held expert into row blocks of R, every block one
    expert's. expert_of [A] in [0, G], G = not held. Returns the assignment at
    every row (A = none), each block's expert, the number of blocks in use
    and the held experts' loads.
    The rows are enough for the worst case (every assignment held), the work
    is by the blocks in use.
    The order of a block's rows: its expert's assignments first, ascending
    (the sort is stable), the padding (A) after them; the rows past the blocks
    in use are all padding. Assignments are numbered token by token and
    ``top_k`` gives a token an expert once, so a block's real rows are
    distinct tokens, ascending: ``_add_rows`` cuts a block on that
    (``tests/test_moe_combine.py`` holds it)."""
    A = expert_of.shape[0]
    M = (-(-A // R) + G) * R
    counts = jnp.sum(expert_of[:, None] == jnp.arange(G)[None, :], axis=0, dtype=jnp.int32)
    padded = -(-counts // R) * R
    ends = jnp.cumsum(padded)
    starts, cstart = ends - padded, jnp.cumsum(counts) - counts
    order = jnp.argsort(expert_of, stable=True).astype(jnp.int32)  # held first, by expert
    blk_expert = jnp.minimum(
        jnp.sum(jnp.arange(M // R)[:, None] * R >= ends[None, :], axis=1), G - 1).astype(jnp.int32)
    row = jnp.arange(M, dtype=jnp.int32)
    e_row = blk_expert[row // R]
    off = row - starts[e_row]
    src = jnp.where((off < counts[e_row]) & (row < ends[-1]),
                    order[jnp.clip(cstart[e_row] + off, 0, A - 1)], A)
    return src, blk_expert, ends[-1] // R, counts


def _gate_up(xb, wg, wu, act: str):
    hg, hu = _mm(xb, wg), _mm(xb, wu)
    return hg, hu, (jax.nn.silu(hg) if act == "silu" else jax.nn.relu(hg)) * hu


def _expert_block(xb, wg, wu, wd, act: str):
    hg, hu, h = _gate_up(xb, wg, wu, act)
    return hg, hu, h, _mm(h, wd)


COMBINE_ROWS = 1024  # the most rows one scatter-add joins to the token sum
COMBINE_BYTES = 96 << 20  # the most bytes of float32 token sum one block loop adds into


def combine_parts(acc_rows: int, cols: int) -> Tuple[int, ...]:
    """The column widths a float32 token sum of ``acc_rows`` x ``cols`` is cut
    into, each part joined by a block loop of its own: ceil(bytes /
    ``COMBINE_BYTES``) parts of whole lane tiles, the last taking what is
    left. The bytes decide where the TPU compiler keeps a scatter's
    accumulator, a ``fori_loop``'s carry: up to 96 MiB in fast memory
    (``S(1)`` in the optimised HLO), from 128 MiB in HBM, where a piece costs
    twice a KB (PERF.md section 6). Two parts carried by one loop
    are one accumulator of their sum's bytes."""
    p = -(-acc_rows * cols * 4 // COMBINE_BYTES)
    w = -(-cols // (p * LANE)) * LANE
    return tuple(min(w, cols - c) for c in range(0, cols, w))


def combine_piece_rows(acc_rows: int) -> int:
    """The rows of one piece of a block's scatter-add into a token sum of
    ``acc_rows`` rows: an eighth of the sum's rows, in whole sublanes of 8, at
    most ``COMBINE_ROWS`` and at least 8. The rows decide the scatter's form
    (the bytes where its sum lives: ``combine_parts``): up to an eighth the
    TPU compiler runs it as written; above it the compiler sorts the indices
    and reads the updates through the permutation (a ``sort`` and a
    ``gather`` beside the scatter), at any width and dtype, twice to twelve
    times the time on the chip (PERF.md section 6)."""
    return max(8, min(COMBINE_ROWS, acc_rows // 8) // 8 * 8)


def _add_rows(acc, tb, rows):
    """acc[tb[r]] += rows[r] over one block's rows (tb == N: padding,
    dropped), ``combine_piece_rows`` rows a scatter-add, cut at static offsets
    whatever the rows hold. A block's tokens are distinct (``group_layout``),
    so no two of its rows meet and a token receives the one addition that a
    whole block's scatter-add gave it, bit for bit. The pieces a block took
    are counted at trace time (``model.moe.combine_pieces`` over
    ``model.moe.combine_calls``), the last call site's piece beside them
    (``model.moe.combine_piece_rows``, ``model.moe.combine_piece_bytes``)."""
    R = tb.shape[0]
    P = min(R, combine_piece_rows(acc.shape[0]))
    STAT_ADD("model.moe.combine_calls")
    STAT_ADD("model.moe.combine_pieces", -(-R // P))
    STAT_SET("model.moe.combine_piece_rows", P)
    STAT_SET("model.moe.combine_piece_bytes", P * rows.shape[1] * rows.dtype.itemsize)
    for r in range(0, R, P):
        acc = acc.at[tb[r:r + P]].add(rows[r:r + P], mode="drop")
    return acc


ACTS = ("silu", "relu")  # the gate's activation: down((silu | relu)(x gate) * (x up))


@partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def grouped_experts(x, wg, wu, wd, gate, tok, blk_expert, n_blocks, R: int, scope: str,
                    act: str = "silu"):
    """y[t] = sum over the rows r of token t of gate[r] * expert(x[t]), the
    expert of row r being its block's, its gate's activation ``act`` (static,
    forward and the hand-written backward). x [N, H]; wg, wu [G, H, I], wd
    [G, I, H]; gate [M] float32; tok [M] the row's token (N = no token), in
    ``group_layout``'s order: a block's real rows first, their tokens
    distinct and ascending, its padding after. One pass over the ``n_blocks``
    blocks in use: gather the block's tokens, the expert's three products,
    add the weighted rows to their tokens (``_add_rows``, which depends on
    that order: a token at most once a block; the backward's ``dx`` likewise).
    Where the float32 sum is cut by columns (``combine_parts``), that pass
    keeps what the last product reads (the forward's ``h``, the backward's
    ``dhg`` and ``dhu``: bfloat16 ``[M, I]``, as the products read them) and
    adds nothing; one more pass over the blocks a part then runs that
    product for the part's columns and joins them (``_join_parts``): every
    element receives the additions it did in one pass, in the same order,
    bit for bit."""
    return _grouped_fwd(x, wg, wu, wd, gate, tok, blk_expert, n_blocks, R, scope, act)[0]


def _column_parts(N: int, H: int):
    """``combine_parts`` as (first, end) column bounds, counted at trace time
    (``model.moe.combine_parts``, ``model.moe.combine_part_bytes``: the last
    call site's)."""
    widths = combine_parts(N, H)
    STAT_SET("model.moe.combine_parts", len(widths))
    STAT_SET("model.moe.combine_part_bytes", N * widths[0] * 4)
    ends = np.cumsum(widths).tolist()
    return [(b - w, b) for w, b in zip(widths, ends)]


def _join_parts(N, cols, stash, n_blocks, R, tok, blk_expert, scope, block_rows):
    """The token sum [N, H] joined one column part a loop over the blocks,
    from what the blocks' pass kept (``stash``, rows j R .. j R + R a block):
    ``block_rows(j, e, kept, a, b)`` gives block j's rows of columns a .. b.
    Each loop carries its part alone, behind a barrier, so that the compiler
    keeps it in fast memory (``combine_parts``)."""
    parts = []
    for a, b in cols:
        def body(j, acc, a=a, b=b):
            e = blk_expert[j]
            tb = lax.dynamic_slice_in_dim(tok, j * R, R)
            with jax.named_scope(f"{scope}/moe/combine"):
                kept = tuple(lax.dynamic_slice_in_dim(s, j * R, R) for s in stash)
            with jax.named_scope(f"{scope}/moe/experts"):
                rows = block_rows(j, e, kept, a, b)
            with jax.named_scope(f"{scope}/moe/combine"):
                return _add_rows(acc, tb, rows)

        parts, stash = lax.optimization_barrier((parts, stash))
        parts.append(lax.fori_loop(0, n_blocks, body, jnp.zeros((N, b - a), F32)))
    with jax.named_scope(f"{scope}/moe/combine"):
        return jnp.concatenate(parts, axis=1)


def _grouped_fwd(x, wg, wu, wd, gate, tok, blk_expert, n_blocks, R, scope, act):
    if act not in ACTS:
        raise ValueError(f"gate activation {act!r}")
    N, H = x.shape
    xe = jnp.concatenate([x.astype(BF16), jnp.zeros((1, H), BF16)])
    res = (x, wg, wu, wd, gate, tok, blk_expert, n_blocks)
    wg, wu, wd = (w.astype(BF16) for w in (wg, wu, wd))  # once, not once a block
    cols = _column_parts(N, H)
    cut = len(cols) > 1

    def body(j, y):
        e = blk_expert[j]
        tb = lax.dynamic_slice_in_dim(tok, j * R, R)
        gb = lax.dynamic_slice_in_dim(gate, j * R, R)
        with jax.named_scope(f"{scope}/moe/dispatch"):
            xb = xe[tb]
        if cut:  # y is the kept h
            with jax.named_scope(f"{scope}/moe/experts"):
                h = _gate_up(xb, wg[e], wu[e], act)[2]
            with jax.named_scope(f"{scope}/moe/combine"):
                return lax.dynamic_update_slice_in_dim(y, h.astype(BF16), j * R, 0)
        with jax.named_scope(f"{scope}/moe/experts"):
            yb = _expert_block(xb, wg[e], wu[e], wd[e], act)[3]
        with jax.named_scope(f"{scope}/moe/combine"):
            return _add_rows(y, tb, yb * gb[:, None])

    if not cut:
        return lax.fori_loop(0, n_blocks, body, jnp.zeros((N, H), F32)), res
    hs = lax.fori_loop(0, n_blocks, body, jnp.zeros((tok.shape[0], wg.shape[2]), BF16))

    def down(j, e, kept, a, b):
        return _mm(kept[0], wd[e][:, a:b]) * lax.dynamic_slice_in_dim(gate, j * R, R)[:, None]

    return _join_parts(N, cols, (hs,), n_blocks, R, tok, blk_expert, scope, down), res


def _grouped_bwd(R, scope, act, res, dy):
    x, wg, wu, wd, gate, tok, blk_expert, n_blocks = res
    N, H = x.shape
    xe = jnp.concatenate([x.astype(BF16), jnp.zeros((1, H), BF16)])
    dye = jnp.concatenate([dy.astype(F32), jnp.zeros((1, H), F32)])
    shapes = (x, wg, wu, wd, gate)
    wg, wu, wd = (w.astype(BF16) for w in (wg, wu, wd))
    wgT, wuT, wdT = (jnp.swapaxes(w, 1, 2) for w in (wg, wu, wd))
    cols = _column_parts(N, H)
    cut = len(cols) > 1

    def add_at(acc, e, upd):
        return lax.dynamic_update_index_in_dim(acc, acc[e] + upd, e, 0)

    def body(j, carry):
        dx, dwg, dwu, dwd, dgate = carry  # where the sum is cut, dx is the kept (dhg, dhu)
        e = blk_expert[j]
        tb = lax.dynamic_slice_in_dim(tok, j * R, R)
        gb = lax.dynamic_slice_in_dim(gate, j * R, R)
        with jax.named_scope(f"{scope}/moe/dispatch"):
            xb, dyb = xe[tb], dye[tb]
        with jax.named_scope(f"{scope}/moe/experts"):
            hg, hu, h, yb = _expert_block(xb, wg[e], wu[e], wd[e], act)
            dgb = jnp.sum(yb * dyb, axis=1)
            dyb = (dyb * gb[:, None]).astype(BF16)
            dh = jnp.dot(dyb, wdT[e], preferred_element_type=F32)
            if act == "silu":
                sg = jax.nn.sigmoid(hg)
                dhu = (dh * hg * sg).astype(BF16)
                dhg = (dh * hu * sg * (1.0 + hg * (1.0 - sg))).astype(BF16)
            else:  # relu: the gate passes where it is positive
                dhu = (dh * jax.nn.relu(hg)).astype(BF16)
                dhg = jnp.where(hg > 0, dh * hu, 0.0).astype(BF16)
            dwd = add_at(dwd, e, jnp.dot(h.astype(BF16).T, dyb, preferred_element_type=F32))
            dwg = add_at(dwg, e, jnp.dot(xb.T, dhg, preferred_element_type=F32))
            dwu = add_at(dwu, e, jnp.dot(xb.T, dhu, preferred_element_type=F32))
            if not cut:
                dxb = (jnp.dot(dhg, wgT[e], preferred_element_type=F32)
                       + jnp.dot(dhu, wuT[e], preferred_element_type=F32))
        with jax.named_scope(f"{scope}/moe/combine"):
            if cut:
                dx = tuple(lax.dynamic_update_slice_in_dim(s, d, j * R, 0)
                           for s, d in zip(dx, (dhg, dhu)))
            else:
                dx = _add_rows(dx, tb, dxb)
            dgate = lax.dynamic_update_slice_in_dim(dgate, dgb, j * R, 0)
        return dx, dwg, dwu, dwd, dgate

    dx = (tuple(jnp.zeros((tok.shape[0], wg.shape[2]), BF16) for _ in range(2)) if cut
          else jnp.zeros(x.shape, F32))
    grads = lax.fori_loop(0, n_blocks, body, (dx,) + tuple(jnp.zeros(a.shape, F32) for a in shapes[1:]))
    if cut:
        def dx_rows(j, e, kept, a, b):
            return (jnp.dot(kept[0], wgT[e][:, a:b], preferred_element_type=F32)
                    + jnp.dot(kept[1], wuT[e][:, a:b], preferred_element_type=F32))

        grads = (_join_parts(N, cols, grads[0], n_blocks, R, tok, blk_expert, scope, dx_rows),) + grads[1:]
    return tuple(g.astype(a.dtype) for g, a in zip(grads, shapes)) + (None, None, None)


grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def routed_experts(p, x, idx, g, held: int, offset: int, block: int, scope: str,
                   act: str = "silu"):
    """The part of the layer of the ``held`` experts from ``offset`` on, for x
    [N, H] routed as (idx, g), in blocks of ``block`` rows, the gate's
    activation ``act``. Returns it, named ``EXPERTS_OUT``, with the held
    experts' loads [held]."""
    N, k = idx.shape
    with jax.named_scope(f"{scope}/moe/dispatch"):
        local = idx.reshape(-1) - offset
        expert_of = jnp.where((local >= 0) & (local < held), local, held)
        src, blk_expert, n_blocks, counts = group_layout(expert_of, held, block)
        tok = jnp.where(src < N * k, src // k, N)
        gate = jnp.concatenate([g.reshape(-1), jnp.zeros((1,), F32)])[src]
    y = grouped_experts(x, p["gate"], p["up"], p["down"], gate, tok, blk_expert, n_blocks,
                        block, scope, act)
    return checkpoint_name(y, EXPERTS_OUT), counts


def share_counters(choices, loads, held: int, offset: int, block: int) -> list:
    """``SHARE_COUNTERS`` of one batch, from the chosen experts [..., k] and
    the held ones' loads of every layer: the (token, layer) pairs none of
    whose chosen experts is held, and the rows of the grouped product's blocks
    in use (padding included), all layers."""
    local = choices - offset
    is_held = jnp.any((local >= 0) & (local < held), axis=-1)
    return [jnp.sum(~is_held).astype(F32), jnp.sum(-(-loads // block) * block).astype(F32)]


def record_share_counters(unrouted, block_rows) -> None:
    """A pass's mean ``SHARE_COUNTERS`` into the monitor registry (literal names)."""
    STAT_SET("model.unrouted_tokens_per_step", float(unrouted))
    STAT_SET("model.block_rows_per_step", float(block_rows))
