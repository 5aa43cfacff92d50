"""GLM-4.7-Flash (``glm4_moe_lite``): latent attention, routed experts beside
a shared expert, one multi-token-prediction module; a language model trained
through the pass path, the first of five (``models/afmoe.py``,
``models/smallthinker.py``, ``models/sdar.py`` and ``models/xing4.py`` import
its pieces; the last takes each block's branch, ``mla_branch``,
``dense_branch`` and ``moe_branch``, without the sum that the layers here put
around it).

The model is a *sequence model that owns its loss* (``models/base.py``): the
step hands it the pulled rows of the one token slot unpooled, as
``[B, T, hidden]`` in record order, and the record's dense slot of T token
ids; it returns the loss (next-token cross-entropy over the vocabulary slice
it holds, plus the weighted MTP loss) and its counters, one stacked array. The
token embedding is the pass's sparse table: the gradient with respect to the
pulled rows goes back through the push like any feature's.

One instance is one chip's share of an expert-parallel group: it is told
``experts_held`` and ``experts_offset``, routes every token over all
``n_routed_experts``, and computes the part of the held ones; what the
absent experts would add is left out (``test_glm_moe_lite`` adds the shares
up to the uncut layer). Nothing here stands in for the other chips.

Precision: parameters, residual stream, norms, rope, router, softmax and
loss in float32; matrix-product operands cast to bfloat16 with float32
accumulation; the router's own product in float32 at ``highest``.

Memory: every layer is recomputed in the backward from its input
(``jax.checkpoint``), but for the fused scores' float32 output and logsumexp,
which each layer's checkpoint keeps by name
(``ops/pallas_kernels.py::KEEP_SCORES``; 1.01 GB over the cell's six attention
layers of two 4k records): q, k, v are the recomputation's anyway, so the
backward kernel is fed without the forward kernel's second run. The
expert layers are one stacked body under ``lax.scan``, the attention scores
never exist whole, and the head's logits exist one block of positions at a
time. The scores take one of two forms, chosen at trace time from what the
call site can see (``fused_scores``: the backend and the shapes; counted under
``model.mla.fused_scores`` / ``model.mla.blocked_scores``): on a TPU, at
shapes its tiles divide, one fused kernel a pass
(``ops/pallas_kernels.py::causal_attention``: tiles of ``attn_block``
queries by ``attn_block`` keys in VMEM, online softmax, the tiles above the
diagonal skipped, a backward that recomputes them from q, k, v and the rows'
logsumexp); everywhere else ``attn_block`` queries at a time against their
causal prefix, each block recomputed in the backward (``_attend_block``).
Both keep the precision above.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from functools import lru_cache, partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddlebox_tpu.ops.pallas_kernels import KEEP_SCORES, LANE, causal_attention
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_SET

BF16, F32 = jnp.bfloat16, jnp.float32
COUNTERS = ("loss_main", "loss_mtp", "tokens", "held_assignments", "expert_load_max_over_mean")


@dataclass(frozen=True)
class GlmMoeLiteConfig:
    """Keys as in the published ``config.json``; ``num_hidden_layers`` and
    ``vocab_size`` are what this instance holds, not the published counts."""

    hidden_size: int = 2048
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.8
    first_k_dense_replace: int = 1
    num_hidden_layers: int = 5
    num_nextn_predict_layers: int = 1
    vocab_size: int = 19360
    experts_held: int = 8
    experts_offset: int = 0
    seq_len: int = 4096
    mtp_loss_weight: float = 0.3
    initializer_range: float = 0.02
    attn_block: int = 512  # queries (and, in the fused kernel, keys) a tile of the scores
    loss_block: int = 1024  # positions whose logits exist at once
    expert_block: int = 512  # rows of one grouped product

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "GlmMoeLiteConfig":
        names = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


# ---- pieces -----------------------------------------------------------------


# spec -> (the cotangent of a from (g, b), the cotangent of b from (p, a): the
# scores [b, h, q, k] first, whichever of a and g they are, as the CPU's dot wants)
_TRANSPOSES = {
    "...k,kn->...n": ("...n,kn->...k", "...k,...n->kn", False),
    "bqhd,bkhd->bhqk": ("bhqk,bkhd->bqhd", "bhqk,bqhd->bkhd", True),
    "bhqk,bkhd->bqhd": ("bqhd,bkhd->bhqk", "bhqk,bqhd->bkhd", False),
}


@lru_cache(maxsize=None)
def _product(spec: str):
    """The einsum ``spec`` with bfloat16 operands and float32 accumulation, in
    the backward pass too: the cotangent is cast to bfloat16 before it enters
    either transpose (what the MXU does with a float32 operand at default
    precision; spelled out, so that every backend computes the same), and
    both transposes give float32."""
    to_a, to_b, g_first = _TRANSPOSES[spec]

    def einsum(sp, x, y):
        return jnp.einsum(sp, x.astype(BF16), y.astype(BF16), preferred_element_type=F32)

    @jax.custom_vjp
    def product(a, b):
        return einsum(spec, a, b)

    def fwd(a, b):
        return product(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        db = einsum(to_b, g, a) if g_first else einsum(to_b, a, g)
        return einsum(to_a, g, b).astype(a.dtype), db.astype(b.dtype)

    product.defvjp(fwd, bwd)
    return product


def _mm(x, w):
    """x @ w: bfloat16 operands, float32 accumulation."""
    return _product("...k,kn->...n")(x, w)


def rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope_tables(T: int, dim: int, theta: float):
    # the frequencies on the host in float64: a device's float32 pow is a few
    # ulps off, and position 4,095 multiplies that into the angle
    return _tables(T, float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim))


def _tables(T: int, inv_freq):
    """(cos, sin) [T, dim/2] of position x frequency, the frequencies float64 from the host."""
    ang = jnp.arange(T, dtype=F32)[:, None] * np.asarray(inv_freq, np.float32)[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def yarn_rope_tables(T: int, dim: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """``rope_tables`` under YaRN (Peng et al., arXiv:2309.00071, as DeepSeek-V3's
    ``rope_scaling`` states it): the frequencies that turn more than
    ``beta_fast`` times over the ``original`` positions stay, those that turn
    less than ``beta_slow`` times are divided by ``factor``, a linear ramp over
    the pair index between. The cos/sin factor ``mscale / mscale_all_dim`` is
    the caller's (1 where the two are equal); ``yarn_mscale`` is the softmax
    scale's."""
    f = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)  # host, float64: as above

    def turns_at(r):  # the (fractional) pair index whose frequency turns r times over ``original``
        return dim * np.log(original / (r * 2 * np.pi)) / (2 * np.log(float(theta)))

    low = max(np.floor(turns_at(beta_fast)), 0)
    high = min(np.ceil(turns_at(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return _tables(T, f / factor * ramp + f * (1.0 - ramp))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor; the softmax scale takes its square at ``mscale_all_dim``."""
    return 0.1 * mscale * float(np.log(factor)) + 1.0 if factor > 1 else 1.0


def apply_rope(x, cos, sin):
    """x [B, T, ..., dim], halves paired (x[i], x[i + dim/2]); position = axis 1."""
    half = x.shape[-1] // 2
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


@partial(jax.checkpoint, static_argnums=(3, 4, 5))
def _attend_block(q, k, v, q0: int, n_q: int, scale: float):
    """Queries q0 .. q0 + n_q against their causal prefix. q, k, v are whole
    (what the backward keeps is then one buffer for all blocks, not a slice a
    block); the block and its prefix are cut here."""
    q, k, v = q[:, q0:q0 + n_q], k[:, :q0 + n_q], v[:, :q0 + n_q]
    s = _product("bqhd,bkhd->bhqk")(q, k) * scale
    qi = q0 + jnp.arange(n_q)[:, None]
    s = jnp.where(jnp.arange(k.shape[1])[None, :] <= qi, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return _product("bhqk,bkhd->bqhd")(p, v)


def fused_scores(backend: str, T: int, qk_dim: int, v_dim: int, block: int) -> bool:
    """Whether a call site of ``mla_branch`` takes the fused kernel: on a
    TPU, at shapes the kernel tiles (value heads of whole lane rows;
    query/key heads of half lane rows, which the kernel fills up with zero
    columns). Everything else runs the blocked form."""
    return (backend == "tpu" and v_dim % LANE == 0 and qk_dim % (LANE // 2) == 0
            and block % LANE == 0 and T % block == 0)


def mla_branch(p, x, norm_w, c: GlmMoeLiteConfig, rope, scope: str, scale=None):
    """attention(norm(x)), without the residual sum: latent attention in its
    uncompressed (training) form. x [B, T, H]. ``rope`` is the caller's
    (cos, sin) tables, ``scale`` its softmax scale (default ``(nope + rope) **
    -0.5``; YaRN's is larger). ``scope`` is the block's absolute scope path:
    every leaf scope here is named in full, so that an instruction's scope
    reads the same in the forward pass, under ``checkpoint`` and inside the
    layer scan."""
    B, T, _ = x.shape
    nh, dn, dr, dv = c.num_attention_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    cos, sin = rope
    with jax.named_scope(f"{scope}/mla/q_proj"):
        xn = rms_norm(x, norm_w, c.rms_norm_eps)
        c_q = rms_norm(_mm(xn, p["q_a"]), p["q_a_norm"], c.rms_norm_eps)
        q = _mm(c_q, p["q_b"]).reshape(B, T, nh, dn + dr)
    with jax.named_scope(f"{scope}/mla/kv_proj"):
        ckv = _mm(xn, p["kv_a"])
        c_kv = rms_norm(ckv[..., : c.kv_lora_rank], p["kv_a_norm"], c.rms_norm_eps)
        kv = _mm(c_kv, p["kv_b"]).reshape(B, T, nh, dn + dv)
    with jax.named_scope(f"{scope}/mla/rope"):
        q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], cos, sin)], -1).astype(BF16)
        k_r = apply_rope(ckv[..., c.kv_lora_rank:], cos, sin)  # one rope key for all heads
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None, :], (B, T, nh, dr))], -1).astype(BF16)
        v = kv[..., dn:].astype(BF16)
    with jax.named_scope(f"{scope}/mla/scores"):
        Q = min(c.attn_block, T)
        if T % Q:
            raise ValueError(f"seq_len {T} is not a multiple of attn_block {Q}")
        scale = float(dn + dr) ** -0.5 if scale is None else float(scale)
        if fused_scores(jax.default_backend(), T, dn + dr, dv, Q):
            STAT_ADD("model.mla.fused_scores")  # call sites lowered each way, at trace time
            o = causal_attention(q, k, v, scale, Q)
        else:
            STAT_ADD("model.mla.blocked_scores")
            o = jnp.concatenate(
                [_attend_block(q, k, v, i, Q, scale) for i in range(0, T, Q)], axis=1)
    with jax.named_scope(f"{scope}/mla/out_proj"):
        return _mm(o.reshape(B, T, nh * dv), p["o"])


def mla(p, x, norm_w, c: GlmMoeLiteConfig, rope, scope: str):
    """x + attention(norm(x)): ``mla_branch`` in a pre-norm residual block."""
    y = mla_branch(p, x, norm_w, c, rope, scope)
    with jax.named_scope(f"{scope}/mla/out_proj"):
        return x + y


def swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["gate"])) * _mm(x, p["up"]), p["down"])


# ---- the routed experts a chip holds ------------------------------------------


def route(p, x, c: GlmMoeLiteConfig, form: str = "sigmoid_bias_norm"):
    """x [N, H] -> (chosen experts [N, k] int32, their weights [N, k]).
    ``form`` (static) is the router's: ``sigmoid_bias_norm`` chooses by
    sigmoid + bias and weighs by the chosen sigmoids over their sum, times
    ``routed_scaling_factor``; ``softmax_of_chosen`` chooses by the logits and
    weighs by a softmax over the chosen ones (a softmax over all of them
    renormalised over the chosen is the same numbers) and reads neither a bias
    nor a scale."""
    s = jnp.dot(x.astype(F32), p["w"], precision=lax.Precision.HIGHEST)
    if form == "softmax_of_chosen":
        chosen, idx = lax.top_k(s, c.num_experts_per_tok)
        return idx.astype(jnp.int32), jax.nn.softmax(chosen, axis=1)
    if form != "sigmoid_bias_norm":
        raise ValueError(f"router form {form!r}")
    s = jax.nn.sigmoid(s)
    _, idx = lax.top_k(s + lax.stop_gradient(p["bias"]), c.num_experts_per_tok)
    chosen = jnp.take_along_axis(s, idx, axis=1)
    g = chosen / jnp.sum(chosen, axis=1, keepdims=True) * c.routed_scaling_factor
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
    left (one part up to 96 MiB). The bytes decide where the TPU compiler
    keeps a scatter's accumulator, not its rows: in a ``fori_loop`` whose
    carry is the sum, ``[8192, 2048]``, ``[16384, 1024]``, ``[16384, 1280]``,
    ``[12288, 2048]`` (96 MiB), ``[8192, 3584]`` and ``[4096, 3584]`` stay in
    fast memory (``S(1)`` in the optimised HLO's layouts), ``[16384, 2048]``
    (128 MiB) and ``[16384, 2560]`` go to HBM, and a piece into HBM costs
    twice a KB (31-40 ns/KB against 13-18: PERF.md section 6, PRs 43-44;
    compiled for a described v5e). Two parts carried by one loop are one
    accumulator of their sum's bytes."""
    p = -(-acc_rows * cols * 4 // COMBINE_BYTES)
    w = -(-cols // (p * LANE)) * LANE
    return tuple(min(w, cols - c) for c in range(0, cols, w))


def combine_piece_rows(acc_rows: int) -> int:
    """The rows of one piece of a block's scatter-add into a token sum of
    ``acc_rows`` rows: an eighth of the sum's rows, in whole sublanes of 8, at
    most ``COMBINE_ROWS`` and at least 8. The rows decide the scatter's form,
    the bytes where its sum lives (``combine_parts``). The TPU compiler's form
    of a scatter turns on the update's rows against the rows it adds into and
    on nothing else of the shape: up to an eighth (8 R <= N) the scatter runs as written;
    above it the indices are sorted and the updates read through the
    permutation (a ``sort`` and a ``gather`` beside the scatter in the
    optimised HLO), at 2,048, 2,560 and 3,584 columns, float32 and bfloat16
    alike (compiled for a described v5e: 512 rows into 4,096 as written, 520
    sorted; 1,024 / 1,032 into 8,192; 2,048 / 2,056 into 16,384). On the chip a
    float32 piece as written costs 13-20 ns/KB at any height from 256 up into
    a sum in fast memory (4,096 or 8,192 rows in PR 43's grid), 31-40 into one
    in HBM (its 16,384 rows: 128 MiB and more, ``combine_parts``); sorted, a
    call costs 0.22-0.25 ms at 2,048 columns,
    0.84-0.87 at 3,584 and 1.61-1.65 at 2,560 whatever its rows (PERF.md
    section 6, PR 43). PR 34's 1,024 was that eighth of Trinity's 8,192
    tokens; Xing4's 896-row blocks into 4,096 tokens stood over theirs. Past
    1,024 rows a taller piece into 16,384 buys nothing (2 x 2,048 and
    4 x 1,024 rows: 1.18 ms both)."""
    return max(8, min(COMBINE_ROWS, acc_rows // 8) // 8 * 8)


def _add_rows(acc, tb, rows):
    """acc[tb[r]] += rows[r] over one block's rows (tb == N: padding,
    dropped), ``combine_piece_rows`` rows a scatter-add, cut at static offsets
    whatever the rows hold. A block's tokens are distinct (``group_layout``),
    so no two of its rows meet and a token receives the one addition a block
    that a whole block's scatter-add gave it, bit for bit; what the cut buys is
    the price of a row: a piece over an eighth of the sum's rows takes the
    compiler's sorted form, twice to twelve times the time (PERF.md section 6,
    PRs 34 and 43). The rows decide that form; the bytes of ``acc`` decide
    whether it stays in fast memory, and the caller cuts it by columns for that
    (``combine_parts``, PR 44). The pieces a block took are counted at trace time
    (``model.moe.combine_pieces`` over ``model.moe.combine_calls``), the last
    call site's piece beside them (``model.moe.combine_piece_rows``,
    ``model.moe.combine_piece_bytes``)."""
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


def routed_experts(p, x, idx, g, c: GlmMoeLiteConfig, scope: str, act: str = "silu"):
    """The held experts' part of the layer, for x [N, H] routed as (idx, g),
    the gate's activation ``act``. Returns it with the held experts' loads
    [experts_held]."""
    N, k = idx.shape
    G = c.experts_held
    with jax.named_scope(f"{scope}/moe/dispatch"):
        local = idx.reshape(-1) - c.experts_offset
        expert_of = jnp.where((local >= 0) & (local < G), local, G)
        src, blk_expert, n_blocks, counts = group_layout(expert_of, G, c.expert_block)
        tok = jnp.where(src < N * k, src // k, N)
        gate = jnp.concatenate([g.reshape(-1), jnp.zeros((1,), F32)])[src]
    y = grouped_experts(x, p["gate"], p["up"], p["down"], gate, tok, blk_expert, n_blocks,
                        c.expert_block, scope, act)
    return y, counts


# ---- layers -------------------------------------------------------------------


# A block is its branch F(norm(h)) and the sum around it; ``models/xing4.py`` puts
# hyper-connections around the same branches instead.


def dense_branch(p, h, c: GlmMoeLiteConfig, scope: str = "model"):
    """swiglu(norm(h)), the dense layer's feed-forward without the residual sum."""
    with jax.named_scope(f"{scope}/dense_mlp"):
        return swiglu(p["mlp"], rms_norm(h, p["ln2"], c.rms_norm_eps))


def moe_branch(p, h, c: GlmMoeLiteConfig, scope: str = "model"):
    """shared(norm(h)) + the held experts' part, without the residual sum
    -> (branch [B, T, H], chosen experts [B * T, k], held experts' loads)."""
    B, T, H = h.shape
    with jax.named_scope(f"{scope}/moe/router"):
        flat = rms_norm(h, p["ln2"], c.rms_norm_eps).reshape(B * T, H)
        idx, g = route(p["router"], flat, c)
    routed, counts = routed_experts(p["experts"], flat, idx, g, c, scope)
    with jax.named_scope(f"{scope}/moe/shared"):
        y = (swiglu(p["shared"], flat) + routed).reshape(B, T, H)
    return y, idx, counts


def dense_layer(p, x, c: GlmMoeLiteConfig, rope, scope: str = "model"):
    h = mla(p["attn"], x, p["ln1"], c, rope, scope)
    y = dense_branch(p, h, c, scope)
    with jax.named_scope(f"{scope}/dense_mlp"):
        return h + y


def moe_layer(p, x, c: GlmMoeLiteConfig, rope, scope: str = "model"):
    """-> (stream, chosen experts [B, T, k], held experts' loads)."""
    h = mla(p["attn"], x, p["ln1"], c, rope, scope)
    y, idx, counts = moe_branch(p, h, c, scope)
    with jax.named_scope(f"{scope}/moe/shared"):
        out = h + y
    return out, idx.reshape(*h.shape[:2], -1), counts


def head_logits(head, h, targets, block: int):
    """h [N, H], targets [N] -> (the target's logit, logsumexp of the logits),
    float32 [N] each; the logits exist one block of positions at a time."""
    N = h.shape[0]
    blk = min(block, N)
    if N % blk:
        raise ValueError(f"{N} positions are not a multiple of loss_block {blk}")

    @jax.checkpoint
    def one(hb, tb):
        logits = _mm(hb, head)
        return (jnp.take_along_axis(logits, tb[:, None], axis=1)[:, 0],
                jax.nn.logsumexp(logits, axis=1))

    tl, lse = lax.map(lambda a: one(*a), (h.reshape(N // blk, blk, -1),
                                          targets.reshape(N // blk, blk)))
    return tl.reshape(N), lse.reshape(N)


# ---- the model ----------------------------------------------------------------


class GlmMoeLite:
    """``apply(params, emb [B, T, H], ids [B, T]) -> (loss, {"counters": [5]})``;
    ``forward`` gives the logit terms and expert choices behind it."""

    sequence_feed = True  # the step feeds the slot's rows unpooled and takes the loss from here
    counter_names = COUNTERS

    def __init__(self, cfg: GlmMoeLiteConfig):
        self.cfg = cfg
        self.num_slots = 1
        self.seq_len = cfg.seq_len
        self.dense_dim = cfg.seq_len  # the record's dense slot: its T token ids
        self.feat_width = 3 + cfg.hidden_size

    # -- parameters

    def _attn_init(self, key):
        c = self.cfg
        ks = jax.random.split(key, 5)
        nh = c.num_attention_heads
        w = lambda k, *s: jax.random.normal(k, s, F32) * c.initializer_range  # noqa: E731
        return {
            "q_a": w(ks[0], c.hidden_size, c.q_lora_rank), "q_a_norm": jnp.ones((c.q_lora_rank,)),
            "q_b": w(ks[1], c.q_lora_rank, nh * c.qk_head_dim),
            "kv_a": w(ks[2], c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim),
            "kv_a_norm": jnp.ones((c.kv_lora_rank,)),
            "kv_b": w(ks[3], c.kv_lora_rank, nh * (c.qk_nope_head_dim + c.v_head_dim)),
            "o": w(ks[4], nh * c.v_head_dim, c.hidden_size),
        }

    def _mlp_init(self, key, width, lead=()):
        c = self.cfg
        ks = jax.random.split(key, 3)
        w = lambda k, *s: jax.random.normal(k, lead + s, F32) * c.initializer_range  # noqa: E731
        return {"gate": w(ks[0], c.hidden_size, width), "up": w(ks[1], c.hidden_size, width),
                "down": w(ks[2], width, c.hidden_size)}

    def _layer_init(self, key, moe: bool):
        c = self.cfg
        ks = jax.random.split(key, 5)
        p = {"attn": self._attn_init(ks[0]), "ln1": jnp.ones((c.hidden_size,)),
             "ln2": jnp.ones((c.hidden_size,))}
        if not moe:
            return {**p, "mlp": self._mlp_init(ks[1], c.intermediate_size)}
        return {
            **p,
            "router": {"w": jax.random.normal(ks[1], (c.hidden_size, c.n_routed_experts), F32)
                       * c.initializer_range,
                       "bias": jax.random.normal(ks[2], (c.n_routed_experts,), F32)
                       * c.initializer_range},
            "shared": self._mlp_init(ks[3], c.moe_intermediate_size),
            "experts": self._mlp_init(ks[4], c.moe_intermediate_size, lead=(c.experts_held,)),
        }

    def init(self, rng) -> Dict[str, Any]:
        c = self.cfg
        ks = jax.random.split(rng, c.num_hidden_layers + 4)
        dense = [self._layer_init(ks[i], False) for i in range(c.first_k_dense_replace)]
        moe = [self._layer_init(ks[i], True) for i in range(c.first_k_dense_replace,
                                                             c.num_hidden_layers)]
        return {
            "dense": dense,
            "moe": jax.tree.map(lambda *a: jnp.stack(a), *moe),
            "final_norm": jnp.ones((c.hidden_size,)),
            "head": jax.random.normal(ks[-4], (c.hidden_size, c.vocab_size), F32)
            * c.initializer_range,
            "mtp": {
                "enorm": jnp.ones((c.hidden_size,)), "hnorm": jnp.ones((c.hidden_size,)),
                "eh_proj": jax.random.normal(ks[-3], (2 * c.hidden_size, c.hidden_size), F32)
                * c.initializer_range,
                "block": self._layer_init(ks[-2], True),
                "norm": jnp.ones((c.hidden_size,)),
            },
        }

    # -- forward and loss

    def hidden_states(self, params, emb) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """emb [B, T, H] -> (main stack's last hidden state before its final
        norm, MTP's before its norm, chosen experts [layers, B, T, k], held
        loads [layers, held]); the MTP module is the last layer of both."""
        c = self.cfg
        rope = rope_tables(emb.shape[1], c.qk_rope_head_dim, c.rope_theta)
        x = emb.astype(F32)
        # checkpoints that keep the scores' output and logsumexp, at trace time
        STAT_ADD("model.mla.keep_scores_sites", len(params["dense"]) + 2)
        for p in params["dense"]:
            x = jax.checkpoint(lambda p, x: dense_layer(p, x, c, rope), policy=KEEP_SCORES)(p, x)

        @partial(jax.checkpoint, policy=KEEP_SCORES)
        def body(x, p):
            x, idx, counts = moe_layer(p, x, c, rope)
            return x, (idx, counts)

        x, (choices, loads) = lax.scan(body, x, params["moe"])

        @partial(jax.checkpoint, policy=KEEP_SCORES)
        def mtp(m, x, emb):
            with jax.named_scope("model/mtp/eh_proj"):
                nxt = jnp.concatenate([emb[:, 1:], jnp.zeros_like(emb[:, :1])], axis=1)
                both = jnp.concatenate([rms_norm(nxt, m["enorm"], c.rms_norm_eps),
                                        rms_norm(x, m["hnorm"], c.rms_norm_eps)], axis=-1)
                h = _mm(both, m["eh_proj"])
            return moe_layer(m["block"], h, c, rope, scope="model/mtp")

        xm, idx_m, counts_m = mtp(params["mtp"], x, emb)
        return (x, xm, jnp.concatenate([choices, idx_m[None]]),
                jnp.concatenate([loads, counts_m[None]]))

    def forward(self, params, emb, ids):
        """What one batch gives, before the loss is weighed: ``parts`` [2]
        (the main and the MTP cross-entropy, each a mean over the positions
        that have a target), ``token_logits`` [4, B, T] (the target's logit
        at the main head and at MTP's, then the logsumexp of all logits at
        each), ``router_choices`` [layers + 1, B, T, k] and the held experts'
        ``loads`` [layers + 1, held]. emb [B, T, H]: the token slot's pulled
        rows, CVM columns dropped; ids [B, T]: the record's token ids (whole
        numbers in float32 or int32), relative to the held slice."""
        c = self.cfg
        B, T, H = emb.shape
        if T != c.seq_len or ids.shape != (B, T):
            raise ValueError(f"sequence feed of {emb.shape} / {ids.shape}, seq_len {c.seq_len}")
        ids = ids.astype(jnp.int32)
        x, xm, choices, loads = self.hidden_states(params, emb)
        with jax.named_scope("loss/head"):
            pos = jnp.arange(T)
            shift = lambda n: jnp.concatenate(  # noqa: E731
                [ids[:, n:], jnp.zeros((B, n), jnp.int32)], axis=1)
            h = jnp.stack([rms_norm(x, params["final_norm"], c.rms_norm_eps),
                           rms_norm(xm, params["mtp"]["norm"], c.rms_norm_eps)])
            targets = jnp.stack([shift(1), shift(2)])
            tl, lse = head_logits(params["head"], h.reshape(2 * B * T, H),
                                  targets.reshape(-1), c.loss_block)
            tl, lse = tl.reshape(2, B, T), lse.reshape(2, B, T)
            mask = jnp.stack([pos < T - 1, pos < T - 2]).astype(F32)[:, None, :]
            parts = jnp.sum((lse - tl) * mask, axis=(1, 2)) / (B * jnp.sum(mask, axis=(1, 2)))
        return {"parts": parts, "token_logits": jnp.concatenate([tl, lse]),
                "router_choices": choices, "loads": loads}

    def apply(self, params, emb, ids):
        """The training loss of one batch (``forward``'s arguments) and the
        one array the step carries out beside it: ``counters``, named by
        ``counter_names``."""
        out = self.forward(params, emb, ids)
        with jax.named_scope("loss/head"):
            parts, loads = out["parts"], out["loads"].astype(F32)
            loss = parts[0] + self.cfg.mtp_loss_weight * parts[1]
            counters = jnp.stack([
                parts[0], parts[1], jnp.asarray(float(emb.shape[0] * emb.shape[1])),
                jnp.sum(loads), jnp.max(loads) / jnp.maximum(jnp.mean(loads), 1e-9)])
        return loss, {"counters": lax.stop_gradient(counters)}

    @staticmethod
    def record_counters(means) -> None:
        """A pass's mean counters into the monitor registry (literal names)."""
        from paddlebox_tpu.utils.monitor import STAT_SET

        STAT_SET("model.loss_main", float(means[0]))
        STAT_SET("model.loss_mtp", float(means[1]))
        STAT_SET("model.tokens_per_step", float(means[2]))
        STAT_SET("model.held_assignments_per_step", float(means[3]))
        STAT_SET("model.expert_load_max_over_mean", float(means[4]))
