"""Pallas TPU kernels on a cell's path: the fused causal attention of
``models/glm_moe_lite.py`` (the token cell, since PR 29) and of
``models/afmoe.py`` (grouped-query heads, window and full layers, since PR 33),
under the block-diffusion training mask of ``models/sdar.py`` (PR 39), and at
query/key heads of another width than the value heads (192 and 128,
``models/xing4.py``, PR 42); the chunk-to-chunk recurrence of the gated delta
rule of ``models/olmo_hybrid.py``'s linear layers.

A kernel lives here when a call site chooses it from what it can observe
(backend and shapes: ``models/attention.py::fused``,
``models/linear_attention.py::fused_recurrence``), its XLA form stays as
every other backend's path and as its oracle (``tests/test_fused_attention.py``,
``tests/test_delta_rule_kernel.py``), a counter says which form was lowered,
and a benchmark cell runs it. The table gather and scatter of
``ops/pull_push.py`` are XLA's: per-row DMA kernels lost to them by 3.3x at
the one lane-aligned shape ever measured (docs/SCATTER_NOTES.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128  # Mosaic lane width
SUBLANE = 8  # float32 rows of a Mosaic tile

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
#
# Two static generalisations (PR 33), both absent from the call that names
# neither. ``group``: query head h reads key-value head h // group; k and v are
# never expanded, and dk, dv are summed over a group's query heads in a float32
# block that stays in VMEM for the whole key-value head. ``window``: key j is
# visible to query i iff 0 <= i - j < window (a multiple of the tile); the
# inner grid axis then runs over the ``band`` + 1 = window / tile + 1 tiles a
# query tile can see and no further (key tile i - band .. i forward, query tile
# j .. j + band backward): the tile on the band's edge is masked (it shows what
# the diagonal tile hides), those past an end of the sequence run empty.
#
# A third static form of the mask (PR 39), absent from every call that does not
# name it. ``diffusion_block``: the sequence is two halves of L = T / 2, the
# clean tokens and their noised copy, both at positions 0 .. L - 1 in blocks of
# ``diffusion_block``; a clean query sees the clean keys of its own block and
# of the earlier ones, a noisy query the clean keys of the earlier blocks and
# the noisy keys of its own block (``diffusion_visible``). With n tiles a half:
# forward, query tile I walks clean key tiles 0 .. I, and in the noisy half
# then its own tile (an inner axis of n + 1; three masks inside a tile, by
# block, where the causal form has one); backward, clean key tile J gathers
# from the query tiles J .. n - 1 of both halves, and the last step of its
# inner axis (2 n + 1) is noisy key tile n + J against the one query tile that
# sees it. At n = 16: 288 tiles a head, against 528 of a causal 2 L.
#
# Two widths (PR 42), absent from every call whose q, k and v have one. q and k
# of Dqk, v and the output of Dv: the score products contract Dqk, the output
# block, its accumulator, do and dv are Dv wide, dq and dk Dqk. A Dqk that is not
# whole lane rows (192) goes in behind zero columns up to the next (``_widths``).

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


def diffusion_visible(u, w, L: int, block: int):
    """Whether key index w is visible to query index u under the
    block-diffusion training mask over [clean | noisy], two halves of L
    positions in blocks of ``block`` (arrays that broadcast, or numbers)."""
    bu, bw = u % L // block, w % L // block
    return (w < L) & (bw <= bu - (u >= L)) | (u >= L) & (w >= L) & (bw == bu)


# what a tile on a half's diagonal hides, by block: of a clean query tile the later blocks, of a
# noisy one against its clean twin its own block too, against itself every block but its own
_HIDDEN = {"later": lambda key, query: key > query, "own_and_later": lambda key, query: key >= query,
           "others": lambda key, query: key != query}


def _hidden_blocks(blk: int, keys_first: bool, block: int, rule: str):
    """[blk, blk] bool of a tile whose queries and keys start at the same position of their halves."""
    key = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 0 if keys_first else 1)
    query = jax.lax.broadcasted_iota(jnp.int32, (blk, blk), 1 if keys_first else 0)
    return _HIDDEN[rule](jax.lax.div(key, block), jax.lax.div(query, block))


def _attention_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *, scale, band,
                          diffusion=None):
    i, j = pl.program_id(2), pl.program_id(3)  # query tile, key tile
    blk, width = acc_sc.shape

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def step(diagonal: bool, edge: bool = False, hidden=None):
        s = jax.lax.dot_general(q_ref[...], k_ref[...], _NT,
                                preferred_element_type=jnp.float32) * scale
        if diagonal:
            s = jnp.where(_above_diagonal(blk, keys_first=False), _MASKED, s)
        if edge:  # a whole band behind: the keys still inside it are those the diagonal hides
            s = jnp.where(_above_diagonal(blk, keys_first=False), s, _MASKED)
        if hidden:
            s = jnp.where(_hidden_blocks(blk, False, diffusion[0], hidden), _MASKED, s)
        m_prev = m_sc[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - _lanes(m_next, blk))
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_next
        acc_sc[...] = acc_sc[...] * _lanes(alpha, width) + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)

    if diffusion is not None:  # clean key tiles 0 .. i mod n, then a noisy query tile's own
        n = diffusion[1]
        noisy = i >= n
        twin = i - jnp.where(noisy, n, 0)
        pl.when(j < twin)(lambda: step(False))
        pl.when((j == twin) & jnp.logical_not(noisy))(lambda: step(False, hidden="later"))
        # rows of the first block see no clean key: what they gather here the last step wipes
        pl.when((j == twin) & noisy)(lambda: step(False, hidden="own_and_later"))
        pl.when((j == n) & noisy)(lambda: step(False, hidden="others"))
    elif band is None:
        pl.when(j < i)(lambda: step(False))
        pl.when(j == i)(lambda: step(True))
    else:  # the inner axis is the band's: key tile i - band .. i, those before the sequence empty
        key = i - band + j
        pl.when((j > 0) & (j < band) & (key >= 0))(lambda: step(False))
        pl.when(j == band)(lambda: step(True))
        pl.when((j == 0) & (key >= 0))(lambda: step(False, edge=True))

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / _lanes(l, width)).astype(o_ref.dtype)
        lse_ref[...] = (m_sc[...] + jnp.log(l)).T[:1]  # the rows' statistic as one lane-dense row


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, scale, hide):
    """One tile of the backward, scores transposed: (dq [queries, D], dk, dv
    [keys, D]) float32; ``hide`` masks the scaled scores [keys, queries]."""
    q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
    pt = jnp.exp(hide(jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32) * scale)
                 - lse_ref[...])
    dv = jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    dst = (pt * (dpt - di_ref[...]) * scale).astype(q.dtype)
    dk = jnp.dot(dst, q, preferred_element_type=jnp.float32)
    return jax.lax.dot_general(dst, k, _TN, preferred_element_type=jnp.float32), dk, dv


def _attention_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dq_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale, band, group):
    """Scores transposed, [keys, queries]: the rows' statistics are then rows
    of lanes, and four of the five products need no transposed operand."""
    j, i = pl.program_id(2), pl.program_id(3)  # key tile, query tile
    blk = q_ref.shape[0]
    inner = i  # the inner axis' own index: at its end a key tile's dk, dv are complete
    if group != 1:
        first_of_group = pl.program_id(1) % group == 0
    if band is not None:  # the inner axis is the band's: query tile j .. j + band
        i = j + i

    def step(diagonal: bool, edge: bool = False):
        def hide(st):
            if diagonal:
                st = jnp.where(_above_diagonal(blk, keys_first=True), _MASKED, st)
            if edge:
                st = jnp.where(_above_diagonal(blk, keys_first=True), st, _MASKED)
            return st

        dq, dk, dv = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, scale, hide)
        rows = pl.ds(pl.multiple_of(i * blk, blk), blk)
        if diagonal:  # the first query tile that sees this key tile
            dk_sc[...], dv_sc[...] = dk, dv
        else:
            dk_sc[...] += dk
            dv_sc[...] += dv

        if edge:  # a query tile a whole band on meets the tile on the band's edge first
            dq_ref[rows, :] = dq
            return

        @pl.when(j == 0)  # every other query tile meets key tile 0 first
        def _():
            dq_ref[rows, :] = dq

        @pl.when(j != 0)
        def _():
            dq_ref[rows, :] += dq

    if band is None:
        pl.when(i > j)(lambda: step(False))
        pl.when(i == j)(lambda: step(True))
    else:
        n_q = dq_ref.shape[0] // blk
        pl.when((i > j) & (i < j + band) & (i < n_q))(lambda: step(False))
        pl.when(i == j)(lambda: step(True))
        pl.when((i == j + band) & (i < n_q))(lambda: step(False, edge=True))

    @pl.when(inner == pl.num_programs(3) - 1)
    def _():
        if group == 1:
            dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)
            return
        # the key-value head's float32 dk, dv stay in VMEM while its query heads pass
        _add_keys(dk_ref, dv_ref, j, blk, lambda: dk_sc[...], lambda: dv_sc[...], first_of_group)


def _add_keys(dk_ref, dv_ref, tile, blk: int, dk, dv, first_of_group):
    """Key tile ``tile``'s dk(), dv() [blk, D] into the key-value head's whole-T
    blocks: set by the group's first query head, added by the others."""
    keys = pl.ds(pl.multiple_of(tile * blk, blk), blk)

    @pl.when(first_of_group)
    def _():
        dk_ref[keys, :], dv_ref[keys, :] = dk(), dv()

    @pl.when(jnp.logical_not(first_of_group))
    def _():
        dk_ref[keys, :] += dk()
        dv_ref[keys, :] += dv()


def _diffusion_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dq_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale, group, block, n):
    """The backward under the block-diffusion mask: clean key tile j of n
    against query tile i of the 2 n that see it (i mod n >= j), then, the
    inner axis' last step, noisy key tile n + j against query tile n + j. dq
    and the key-value head's dk, dv are whole-T float32 blocks in VMEM."""
    j, i = pl.program_id(2), pl.program_id(3)
    blk = q_ref.shape[0]
    first_of_group = pl.program_id(1) % group == 0

    def tile(rule, query_tile):
        hide = lambda st: st if rule is None else jnp.where(  # noqa: E731
            _hidden_blocks(blk, True, block, rule), _MASKED, st)
        dq, dk, dv = _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, scale, hide)
        return dq, dk, dv, pl.ds(pl.multiple_of(query_tile * blk, blk), blk)

    def step(rule):
        dq, dk, dv, rows = tile(rule, i)
        if rule == "later":  # the first query tile that sees this key tile
            dk_sc[...], dv_sc[...] = dk, dv
        else:
            dk_sc[...] += dk
            dv_sc[...] += dv

        @pl.when(j == 0)  # every query tile meets clean key tile 0 first
        def _():
            dq_ref[rows, :] = dq

        @pl.when(j != 0)
        def _():
            dq_ref[rows, :] += dq

    pl.when((i < 2 * n) & (i % n > j))(lambda: step(None))
    pl.when(i == j)(lambda: step("later"))
    pl.when(i == n + j)(lambda: step("own_and_later"))

    @pl.when(i == 2 * n - 1)  # the clean key tile is complete
    def _():
        _add_keys(dk_ref, dv_ref, j, blk, lambda: dk_sc[...], lambda: dv_sc[...], first_of_group)

    @pl.when(i == 2 * n)  # the noisy key tile, seen by its own query tile alone
    def _():
        dq, dk, dv, rows = tile("others", n + j)
        dq_ref[rows, :] += dq
        _add_keys(dk_ref, dv_ref, n + j, blk, lambda: dk, lambda: dv, first_of_group)


def _flat(a):
    return a.reshape(*a.shape[:2], -1)  # [B, T, H, D] -> [B, T, H * D]


def _band(window, T: int, blk: int):
    """Tiles between a query tile and the tile on its band's edge; None where
    every earlier key is visible."""
    if window is None or window >= T:
        return None
    if window % blk:
        raise ValueError(f"window {window} is not a multiple of the tile {blk}")
    return window // blk


def _kv_head(group: int):
    """Query head -> its key-value head (the identity, and no division traced, at group 1)."""
    return (lambda h: h) if group == 1 else (lambda h: h // group)


def _grouped_params(group: int):
    if group == 1:
        return _ATTENTION_PARAMS
    # a key-value head's dk, dv gather over its query heads: the head axis is a sequence too
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_ATTENTION_PARAMS.vmem_limit_bytes)


def _halves(diffusion_block, T: int, blk: int, window):
    """Tiles a half under the block-diffusion mask."""
    if window is not None or T % (2 * blk) or blk % diffusion_block:
        raise ValueError(f"diffusion blocks of {diffusion_block} in tiles of {blk} over {T}, "
                         f"window {window}")
    return T // blk // 2


def _widths(q, k, v):
    """(q and k with zero columns up to whole lane rows, their width then, v's).
    A head is a column block of [B, T, H * D], so its width has to be whole lane
    rows; zero columns add nothing to a score. The MXU contracts 128 at a time, so
    192 columns cost it the two passes 256 do: what the zeros cost is their bytes
    (a third more of q and k read, of dq and dk written, and the pad and the cut
    themselves). Nothing is traced where the width is whole lane rows already."""
    pad = -q.shape[-1] % LANE
    if pad:
        q, k = (jnp.pad(a, ((0, 0), (0, 0), (0, 0), (0, pad))) for a in (q, k))
    if v.shape[-1] % LANE:
        raise ValueError(f"value heads of {v.shape[-1]} are not whole lane rows of {LANE}")
    return q, k, q.shape[-1], v.shape[-1]


def _attention_fwd(q, k, v, scale, blk, interpret, group, window, diffusion_block=None):
    B, T, H, _ = q.shape
    q, k, D, Dv = _widths(q, k, v)
    band, n, kv_head = _band(window, T, blk), T // blk, _kv_head(group)
    q_spec = pl.BlockSpec((None, blk, D), lambda b, h, i, j: (b, i, h))
    o_spec = pl.BlockSpec((None, blk, Dv), lambda b, h, i, j: (b, i, h))
    kernel = functools.partial(_attention_fwd_kernel, scale=scale, band=band)
    if diffusion_block is not None:
        half = _halves(diffusion_block, T, blk, window)
        kernel = functools.partial(kernel, diffusion=(diffusion_block, half))
        # a skipped step names the tile it holds; a noisy query tile's last step its own tile
        k_tile = lambda i, j: jnp.where(  # noqa: E731
            (i >= half) & (j == half), i, jnp.minimum(j, i % half))
    elif band is None:
        # a skipped step (j > i) names the tile it already holds
        k_tile = lambda i, j: jnp.minimum(j, i)  # noqa: E731
    else:  # a step before the sequence names the first tile the row needs
        k_tile = lambda i, j: jnp.maximum(i - band + j, 0)  # noqa: E731
    kv_spec = pl.BlockSpec((None, blk, D), lambda b, h, i, j: (b, k_tile(i, j), kv_head(h)))
    v_spec = pl.BlockSpec((None, blk, Dv), lambda b, h, i, j: (b, k_tile(i, j), kv_head(h)))
    inner = half + 1 if diffusion_block is not None else n if band is None else band + 1
    o, lse = pl.pallas_call(
        kernel, grid=(B, H, n, inner),
        in_specs=[q_spec, kv_spec, v_spec],
        out_specs=[o_spec, pl.BlockSpec((None, None, 1, blk), lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * Dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, LANE), jnp.float32), pltpu.VMEM((blk, LANE), jnp.float32),
                        pltpu.VMEM((blk, Dv), jnp.float32)],
        compiler_params=_ATTENTION_PARAMS, interpret=interpret, name="causal_attention_fwd",
    )(_flat(q), _flat(k), _flat(v))
    return o, lse  # o as the kernel writes it: [B, T, H * Dv]


def _attention_bwd(q, k, v, o, lse, do, scale, blk, interpret, group, window, diffusion_block=None):
    B, T, H, Dqk = q.shape
    q, k, D, Dv = _widths(q, k, v)
    band, n, kv_head = _band(window, T, blk), T // blk, _kv_head(group)
    # the row term of the softmax's transpose, sum(dp * p) = sum(do * o), from the float32 output
    di = jnp.sum(do.astype(jnp.float32) * o, axis=-1).transpose(0, 2, 1).reshape(B, H, 1, T)
    kernel = functools.partial(_attention_bwd_kernel, scale=scale, band=band, group=group)
    k_tile = lambda j, i: j  # noqa: E731
    if diffusion_block is not None:
        half = _halves(diffusion_block, T, blk, window)
        kernel = functools.partial(_diffusion_bwd_kernel, scale=scale, group=group,
                                   block=diffusion_block, n=half)
        n, last = half, 2 * half  # key tiles of a half; the step of the noisy key tile n + j
        # a skipped step names the tiles the next step of its half is about to need
        q_tile = lambda j, i: jnp.where(  # noqa: E731
            i == last, half + j, jnp.maximum(i, j + jnp.where(i >= half, half, 0)))
        k_tile = lambda j, i: j + jnp.where(i == last, half, 0)  # noqa: E731
    elif band is None:
        # a skipped step (i < j) names the tiles the diagonal step is about to need
        q_tile = lambda j, i: jnp.maximum(i, j)  # noqa: E731
    else:  # a step past the sequence names the tiles it already holds
        q_tile = lambda j, i: jnp.minimum(j + i, n - 1)  # noqa: E731
    q_spec = pl.BlockSpec((None, blk, D), lambda b, h, j, i: (b, q_tile(j, i), h))
    row_spec = pl.BlockSpec((None, None, 1, blk), lambda b, h, j, i: (b, h, 0, q_tile(j, i)))
    kv_spec = pl.BlockSpec((None, blk, D), lambda b, h, j, i: (b, k_tile(j, i), kv_head(h)))
    do_spec = pl.BlockSpec((None, blk, Dv), lambda b, h, j, i: (b, q_tile(j, i), h))
    v_spec = pl.BlockSpec((None, blk, Dv), lambda b, h, j, i: (b, k_tile(j, i), kv_head(h)))
    if group == 1 and diffusion_block is None:
        dk_spec, dv_spec, dkv_dtypes = kv_spec, v_spec, (k.dtype, v.dtype)
    else:  # float32 and whole, resident for the key-value head's ``group`` query heads
        dk_spec = pl.BlockSpec((None, T, D), lambda b, h, j, i: (b, 0, kv_head(h)))
        dv_spec = pl.BlockSpec((None, T, Dv), lambda b, h, j, i: (b, 0, kv_head(h)))
        dkv_dtypes = (jnp.float32, jnp.float32)
    inner = 2 * n + 1 if diffusion_block is not None else n if band is None else band + 1
    dq, dk, dv = pl.pallas_call(
        kernel, grid=(B, H, n, inner),
        in_specs=[q_spec, kv_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((None, T, D), lambda b, h, j, i: (b, 0, h)), dk_spec, dv_spec],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * D), jnp.float32),
                   jax.ShapeDtypeStruct((B, T, H // group * D), dkv_dtypes[0]),
                   jax.ShapeDtypeStruct((B, T, H // group * Dv), dkv_dtypes[1])],
        scratch_shapes=[pltpu.VMEM((blk, D), jnp.float32), pltpu.VMEM((blk, Dv), jnp.float32)],
        compiler_params=_grouped_params(group), interpret=interpret, name="causal_attention_bwd",
    )(_flat(q), _flat(k), _flat(v), _flat(do.astype(q.dtype)), lse, di)
    dq, dk = dq.astype(q.dtype).reshape(q.shape), dk.astype(k.dtype).reshape(k.shape)
    if D != Dqk:  # the zero columns' cotangents go nowhere
        dq, dk = dq[..., :Dqk], dk[..., :Dqk]
    return dq, dk, dv.astype(v.dtype).reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def causal_attention(q, k, v, scale: float, block: int, interpret: bool = False,
                     group: int = 1, window=None, diffusion_block=None):
    """q [B, T, H, Dqk], k [B, T, H / group, Dqk], v [B, T, H / group, Dv]
    bfloat16 -> softmax(q k^T * scale, causal) v [B, T, H, Dv] float32, in
    tiles of ``block`` queries by ``block`` keys; query head h attends
    key-value head h // ``group``, and with a ``window`` only to the keys less
    than ``window`` behind it. T and ``window`` multiples of ``block``,
    ``block`` and Dv multiples of 128; Dqk any width, whole lane rows of 128
    as it stands and any other (192: DeepSeek-V3's own head, ``models/xing4.py``)
    behind zero columns up to the next (``_widths`` says what they cost). With
    a ``diffusion_block`` the visible keys are not the causal prefix but
    ``diffusion_visible``'s: T is two halves of whole tiles, in blocks of
    ``diffusion_block`` that divide the tile; no window then. The cotangents
    of q, k and v come back in their dtype."""
    return _attention_fwd(q, k, v, scale, block, interpret, group, window,
                          diffusion_block)[0].reshape(*q.shape[:3], v.shape[-1])


# The two of the backward's residuals that only the forward kernel can give (q, k, v are a
# layer's recomputation anyway). Under a checkpoint that takes ``KEEP_SCORES`` they are kept
# and the recomputation's kernel call is dead code; under any other they are the identity.
SCORES_OUT, SCORES_LSE = "causal_attention_out", "causal_attention_lse"
KEEP_SCORES = jax.checkpoint_policies.save_only_these_names(SCORES_OUT, SCORES_LSE)


def _causal_attention_fwd(q, k, v, scale, block, interpret, group, window, diffusion_block):
    o, lse = _attention_fwd(q, k, v, scale, block, interpret, group, window, diffusion_block)
    o = checkpoint_name(o, SCORES_OUT).reshape(*q.shape[:3], v.shape[-1])
    lse = checkpoint_name(lse, SCORES_LSE)
    return o, (q, k, v, o, lse)


def _causal_attention_bwd(scale, block, interpret, group, window, diffusion_block, res, do):
    return _attention_bwd(*res, do, scale, block, interpret, group, window, diffusion_block)


causal_attention.defvjp(_causal_attention_fwd, _causal_attention_bwd)


# ---- the gated delta rule's chunk-to-chunk recurrence ---------------------------
#
# What passes from chunk to chunk in ``models/linear_attention.py::delta_rule``:
# the state M = S^T [H, dk, dv] of a record's heads, held in a VMEM scratch
# across a grid that walks the chunks in order (backward: in reverse, the
# state's cotangent dM held instead). A grid step is one chunk of all heads of
# a record, its products batched over the heads, float32 operands at
# ``highest`` with float32 accumulation, as the rule's XLA form has them:
#
#   forward    U = U0 - W M,   o = Qd M + P U,   M <- last M + Kd^T U
#   backward   (M the state that entered the chunk, U recomputed from it)
#              dU = P^T do + Kd dM,   dP = do U^T,   dQd = do M^T,   dKd = U dM^T,
#              dlast = sum(M * dM),   dU0 = dU,   dW = -dU M^T,
#              dM <- last dM + Qd^T do - W^T dU
#
# The forward rule writes the state that enters each chunk (``Ms``, the
# backward's residual); the plain call does not. Blocks are whole chunks and
# whole widths, so any dk, dv and chunk is a legal block (dk 96 rides in lanes
# of 128 in VMEM); ``models/linear_attention.py::fused_recurrence`` says which
# shapes take the kernel.

_HI = jax.lax.Precision.HIGHEST
_NN_H = (((2,), (1,)), ((0,), (0,)))  # [H, a, b] x [H, b, c] -> [H, a, c]
_TN_H = (((1,), (1,)), ((0,), (0,)))  # [H, b, a] x [H, b, c] -> [H, a, c]
_NT_H = (((2,), (2,)), ((0,), (0,)))  # [H, a, b] x [H, c, b] -> [H, a, c]
RECURRENCE_VMEM_BYTES = 64 << 20
# grid (record, chunk): the state passes along the chunk axis
_RECURRENCE_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=RECURRENCE_VMEM_BYTES)


def recurrence_vmem_bytes(heads: int, chunk: int, dk: int, dv: int) -> int:
    """VMEM of the backward's grid step (the larger of the two): its 14 blocks
    twice (the pipeline's two buffers) and dM, float32 tiles of (8, 128)."""
    def tile(rows, cols):
        return -(-rows // SUBLANE) * SUBLANE * -(-cols // LANE) * LANE

    blocks = (6 * tile(chunk, dk) + 3 * tile(chunk, dv) + 2 * tile(chunk, chunk) + tile(dk, dv)
              + 2 * tile(1, 1))
    return 4 * heads * (2 * blocks + tile(dk, dv))


def _dot32(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_HI, preferred_element_type=jnp.float32)


def _recurrence_fwd_kernel(w_ref, u0_ref, p_ref, qd_ref, kd_ref, last_ref, o_ref, *rest):
    *ms_ref, m_sc = rest  # the entering states' block where the forward rule keeps them

    @pl.when(pl.program_id(1) == 0)
    def _():
        m_sc[...] = jnp.zeros(m_sc.shape, jnp.float32)

    M = m_sc[...]
    if ms_ref:
        ms_ref[0][...] = M
    U = u0_ref[...] - _dot32(w_ref[...], M, _NN_H)
    o_ref[...] = _dot32(qd_ref[...], M, _NN_H) + _dot32(p_ref[...], U, _NN_H)
    m_sc[...] = last_ref[...] * M + _dot32(kd_ref[...], U, _TN_H)


def _recurrence_bwd_kernel(w_ref, u0_ref, p_ref, qd_ref, kd_ref, last_ref, ms_ref, do_ref,
                           dw_ref, du0_ref, dp_ref, dqd_ref, dkd_ref, dlast_ref, dm_sc):
    @pl.when(pl.program_id(1) == 0)  # the last chunk: nothing flows back into its state
    def _():
        dm_sc[...] = jnp.zeros(dm_sc.shape, jnp.float32)

    W, M, dM, do = w_ref[...], ms_ref[...], dm_sc[...], do_ref[...]
    U = u0_ref[...] - _dot32(W, M, _NN_H)
    dU = _dot32(p_ref[...], do, _TN_H) + _dot32(kd_ref[...], dM, _NN_H)
    dp_ref[...] = _dot32(do, U, _NT_H)
    dqd_ref[...] = _dot32(do, M, _NT_H)
    dkd_ref[...] = _dot32(U, dM, _NT_H)
    dlast_ref[...] = jnp.sum(jnp.sum(M * dM, axis=2, keepdims=True), axis=1, keepdims=True)
    du0_ref[...] = dU
    dw_ref[...] = -_dot32(dU, M, _NT_H)
    dm_sc[...] = last_ref[...] * dM + _dot32(qd_ref[...], do, _TN_H) - _dot32(W, dU, _TN_H)


def _chunk_spec(shape, reverse: bool):
    """One chunk of one record, all heads: a block of [n, B, H, rows, cols]."""
    n = shape[0]
    at = (lambda b, i: (n - 1 - i, b, 0, 0, 0)) if reverse else (lambda b, i: (i, b, 0, 0, 0))
    return pl.BlockSpec((None, None) + tuple(shape[2:]), at)


def _over_chunks(kernel, ins, outs, reverse: bool, interpret: bool, name: str):
    """``kernel`` over the grid (record, chunk), the chunks in order or in
    reverse, a state's [H, dk, dv] in VMEM; ins[0] is W, ins[1] U0, ins[5]
    last [n, B, H], handed to the kernel as [n, B, H, 1, 1]."""
    n, B, H, _, dk = ins[0].shape
    ins = (*ins[:5], ins[5].reshape(n, B, H, 1, 1), *ins[6:])
    return pl.pallas_call(
        kernel, grid=(B, n),
        in_specs=[_chunk_spec(a.shape, reverse) for a in ins],
        out_specs=[_chunk_spec(o.shape, reverse) for o in outs],
        out_shape=outs, scratch_shapes=[pltpu.VMEM((H, dk, ins[1].shape[-1]), jnp.float32)],
        compiler_params=_RECURRENCE_PARAMS, interpret=interpret, name=name)(*ins)


def _recurrence_fwd(W, U0, P, Qd, Kd, last, interpret: bool, keep_states: bool):
    """-> [o], and the state entering each chunk where ``keep_states``."""
    n, B, H, C, dk = W.shape
    f32 = lambda *s: jax.ShapeDtypeStruct((n, B, H, *s, U0.shape[-1]), jnp.float32)  # noqa: E731
    outs = [f32(C)] + ([f32(dk)] if keep_states else [])
    return _over_chunks(_recurrence_fwd_kernel, (W, U0, P, Qd, Kd, last), outs, False, interpret,
                        "delta_rule_recurrence_fwd")


def _recurrence_bwd(W, U0, P, Qd, Kd, last, Ms, do, interpret: bool):
    ins = (W, U0, P, Qd, Kd, last, Ms, do)
    outs = [jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in ins[:5]]
    outs.append(jax.ShapeDtypeStruct(last.shape + (1, 1), jnp.float32))
    grads = _over_chunks(_recurrence_bwd_kernel, ins, outs, True, interpret,
                         "delta_rule_recurrence_bwd")
    return (*grads[:5], grads[5].reshape(last.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def delta_rule_recurrence(W, U0, P, Qd, Kd, last, interpret: bool = False):
    """The gated delta rule's chunk-to-chunk recurrence, float32: W [n, B, H,
    C, dk], U0 [n, B, H, C, dv], P [n, B, H, C, C], Qd and Kd [n, B, H, C,
    dk], last [n, B, H] -> o [n, B, H, C, dv], chunk by chunk from a zero
    state (the equations above). The cotangents of all six operands come back."""
    return _recurrence_fwd(W, U0, P, Qd, Kd, last, interpret, keep_states=False)[0]


def _delta_rule_recurrence_fwd(W, U0, P, Qd, Kd, last, interpret):
    o, Ms = _recurrence_fwd(W, U0, P, Qd, Kd, last, interpret, keep_states=True)
    return o, (W, U0, P, Qd, Kd, last, Ms)


def _delta_rule_recurrence_bwd(interpret, res, do):
    return _recurrence_bwd(*res, do, interpret)


delta_rule_recurrence.defvjp(_delta_rule_recurrence_fwd, _delta_rule_recurrence_bwd)
