"""Pallas TPU kernels on a cell's path: the fused causal attention of
``models/glm_moe_lite.py`` (the token cell, since PR 29).

A kernel lives here when a call site chooses it from what it can observe
(backend and shapes: ``models/glm_moe_lite.py::fused_scores``), its XLA form
stays as every other backend's path and as its oracle
(``tests/test_fused_attention.py``), a counter says which form was lowered,
and a benchmark cell runs it. The table gather and scatter of
``ops/pull_push.py`` are XLA's: per-row DMA kernels lost to them by 3.3x at
the one lane-aligned shape ever measured (docs/SCATTER_NOTES.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128  # Mosaic lane width

# ---- fused causal attention ---------------------------------------------------
#
# softmax(q k^T * scale, causal) v for one head at a time with every score tile
# in VMEM: matrix-product operands bfloat16, accumulation, running maximum, sum
# and probabilities float32, the probabilities cast to bfloat16 only as the
# operand of their products. The forward keeps the output in float32 and each
# row's logsumexp; the backward is one kernel that recomputes the tiles from q,
# k, v and those statistics, key blocks outermost, dq resident in VMEM for the
# whole head. Tiles are square; those above the diagonal are skipped (their grid
# steps run empty and fetch nothing new), only those on it are masked. Layout
# [B, T, H * D]: a head is a column block, so nothing is transposed on the way
# in or out. Measured (v5e, PR 29, 2 x 4,096 x 20 heads of 256): forward 3.3 ms,
# backward 6.2 ms, against 11.3 and 14.7 ms of XLA's blocked form.

_MASKED = -1e30  # what a score above the diagonal is set to (exp gives 0.0)
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
# grid (record, head, outer tile, inner tile); dq alone is 2 x 4 MB at T 4,096
_ATTENTION_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 << 20)


def _lanes(x, width: int):
    """x [rows, LANE], every lane alike -> [rows, width]."""
    return x if width == LANE else pltpu.repeat(x, width // LANE, axis=1)


def _above_diagonal(blk: int, keys_first: bool):
    """[blk, blk] bool of a diagonal tile: the key comes after the query."""
    key = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0 if keys_first else 1)
    query = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1 if keys_first else 0)
    return key > query


def _attention_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *, scale):
    i, j = pl.program_id(2), pl.program_id(3)  # query tile, key tile
    blk, width = acc_sc.shape

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(diagonal: bool):
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32) * scale
        if diagonal:
            s = jnp.where(_above_diagonal(blk, keys_first=False), _MASKED, s)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, blk))
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_next
        acc_sc[...] = acc_sc[...] * _lanes(alpha, width) + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)

    pl.when(j < i)(lambda: step(False))
    pl.when(j == i)(lambda: step(True))

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / _lanes(l, width)).astype(o_ref.dtype)
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1]  # the rows' statistic as one lane-dense row


def _attention_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dq_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale):
    """Scores transposed, [keys, queries]: the rows' statistics are then rows
    of lanes, and four of the five products need no transposed operand."""
    j, i = pl.program_id(2), pl.program_id(3)  # key tile, query tile
    blk = q_ref.shape[0]

    def step(diagonal: bool):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale
        if diagonal:
            st = jnp.where(_above_diagonal(blk, keys_first=True), _MASKED, st)
        pt = jnp.exp(st - lse_ref[...])
        dv = jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
        dst = (pt * (dpt - di_ref[...]) * scale).astype(q.dtype)
        dk = jnp.dot(dst, q, preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(dst, k, _TN, preferred_element_type=jnp.float32)
        rows = pl.ds(pl.multiple_of(i * blk, blk), blk)
        if diagonal:  # the first query tile that sees this key tile
            dk_sc[...], dv_sc[...] = dk, dv
        else:
            dk_sc[...] += dk
            dv_sc[...] += dv

        @pl.when(j == 0)  # every query tile meets key tile 0 first
        def _():
            dq_ref[rows, :] = dq

        @pl.when(j != 0)
        def _():
            dq_ref[rows, :] += dq

    pl.when(i > j)(lambda: step(False))
    pl.when(i == j)(lambda: step(True))

    @pl.when(i == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _flat(a):
    return a.reshape(*a.shape[:2], -1)  # [B, T, H, D] -> [B, T, H * D]


def _attention_fwd(q, k, v, scale, blk, interpret):
    B, T, H, D = q.shape
    q_spec = pl.BlockSpec((None, blk, D), lambda b, h, i, j: (b, i, h))
    # a skipped step (j > i) names the tile it already holds
    kv_spec = pl.BlockSpec((None, blk, D), lambda b, h, i, j: (b, jnp.minimum(j, i), h))
    o, lse = pl.pallas_call(
        functools.partial(_attention_fwd_kernel, scale=scale),
        grid=(B, H, T // blk, T // blk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, pl.BlockSpec((None, None, 1, blk), lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * D), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, LANE), jnp.float32), pltpu.VMEM((blk, LANE), jnp.float32),
                        pltpu.VMEM((blk, D), jnp.float32)],
        compiler_params=_ATTENTION_PARAMS, interpret=interpret, name="causal_attention_fwd",
    )(_flat(q), _flat(k), _flat(v))
    return o.reshape(B, T, H, D), lse


def _attention_bwd(q, k, v, o, lse, do, scale, blk, interpret):
    B, T, H, D = q.shape
    # the row term of the softmax's transpose, sum(dp * p) = sum(do * o), from the float32 output
    di = jnp.sum(do.astype(jnp.float32) * o, axis=-1).transpose(0, 2, 1).reshape(B, H, 1, T)
    # a skipped step (i < j) names the tiles the diagonal step is about to need
    q_spec = pl.BlockSpec((None, blk, D), lambda b, h, j, i: (b, jnp.maximum(i, j), h))
    row_spec = pl.BlockSpec((None, None, 1, blk), lambda b, h, j, i: (b, h, 0, jnp.maximum(i, j)))
    kv_spec = pl.BlockSpec((None, blk, D), lambda b, h, j, i: (b, j, h))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_attention_bwd_kernel, scale=scale),
        grid=(B, H, T // blk, T // blk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((None, T, D), lambda b, h, j, i: (b, 0, h)), kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * D), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, H * D), k.dtype),
                   jax.ShapeDtypeStruct((B, T, H * D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, D), jnp.float32), pltpu.VMEM((blk, D), jnp.float32)],
        compiler_params=_ATTENTION_PARAMS, interpret=interpret, name="causal_attention_bwd",
    )(_flat(q), _flat(k), _flat(v), _flat(do.astype(q.dtype)), lse, di)
    return dq.astype(q.dtype).reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def causal_attention(q, k, v, scale: float, block: int, interpret: bool = False):
    """q, k, v [B, T, H, D] bfloat16 -> softmax(q k^T * scale, causal) v
    [B, T, H, D] float32, in tiles of ``block`` queries by ``block`` keys. T a
    multiple of ``block``, ``block`` and D multiples of 128. The cotangents of
    q, k and v come back in their dtype."""
    return _attention_fwd(q, k, v, scale, block, interpret)[0]


def _causal_attention_fwd(q, k, v, scale, block, interpret):
    o, lse = _attention_fwd(q, k, v, scale, block, interpret)
    return o, (q, k, v, o, lse)


def _causal_attention_bwd(scale, block, interpret, res, do):
    return _attention_bwd(*res, do, scale, block, interpret)


causal_attention.defvjp(_causal_attention_fwd, _causal_attention_bwd)
