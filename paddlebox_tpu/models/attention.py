"""The scores of every token model's attention: one path into the fused
kernel (``ops/pallas_kernels.py::causal_attention``) and one blocked XLA form,
chosen at trace time by one rule (``fused``: on a TPU at shapes the kernel
tiles) and counted by the call site's kind (``model.mla.fused_scores`` /
``.blocked_scores``, ``model.attn.fused_window_scores`` / ``.fused_full_scores``
/ ``.fused_diffusion_scores`` / ``.blocked_scores``). The blocked form,
``block`` queries at a time against the keys they may see, each block
recomputed in the backward, is every other backend's path and the kernel's
oracle. Both take bfloat16 operands with float32 accumulation and a float32
softmax. A layer's checkpoint keeps the kernel's float32 output and
logsumexp by name (``ops/pallas_kernels.py::KEEP_SCORES``): q, k, v are the
recomputation's anyway, so the backward kernel is fed without the forward
kernel's second run.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.models.lm_layers import BF16, _product, apply_rope
from paddlebox_tpu.ops.pallas_kernels import LANE, causal_attention, diffusion_visible
from paddlebox_tpu.utils.monitor import STAT_ADD


@partial(jax.checkpoint, static_argnums=(3, 4, 5, 6, 7, 8))
def _attend_block(q, k, v, q0: int, n_q: int, scale: float, group: int,
                  window: Optional[int], diffusion_block: Optional[int]):
    """Queries q0 .. q0 + n_q against the keys they may see. q [B, T, H, D],
    k and v [B, T, H / group, D], whole (what the backward keeps is then one
    buffer for all blocks, not a slice a block); the block and its keys are
    cut here: the causal prefix, under a ``window`` from its last ``window``
    keys on (k0), under a ``diffusion_block`` the clean keys through the
    query's block and, in the noisy half, its own noisy positions. Where
    ``group`` > 1 a group's query heads are folded into the query axis: the
    two products are then those of equal head counts."""
    B, T, nh, d = q.shape
    if diffusion_block is None:
        k0 = 0 if window is None else max(0, q0 - window + 1)
        q, k, v = q[:, q0:q0 + n_q], k[:, k0:q0 + n_q], v[:, k0:q0 + n_q]
    else:
        L = T // 2
        spans = [(0, q0 % L + n_q)] + ([(q0, q0 + n_q)] if q0 >= L else [])
        k, v = (jnp.concatenate([a[:, lo:hi] for lo, hi in spans], axis=1) for a in (k, v))
        q = q[:, q0:q0 + n_q]
    if group > 1:
        q = q.reshape(B, n_q, nh // group, group, d).transpose(0, 3, 1, 2, 4).reshape(
            B, group * n_q, nh // group, d)
    s = _product("bqhd,bkhd->bhqk")(q, k) * scale
    qi = q0 + (jnp.tile(jnp.arange(n_q), group) if group > 1 else jnp.arange(n_q))[:, None]
    if diffusion_block is not None:
        kj = jnp.concatenate([jnp.arange(lo, hi) for lo, hi in spans])[None, :]
        seen = diffusion_visible(qi, kj, L, diffusion_block)
    else:
        kj = jnp.arange(k.shape[1])[None, :]
        if k0 or group > 1:  # k0 + kj at k0 = 0 as well under a group: the equations it traced
            kj = k0 + kj
        seen = kj <= qi if window is None else (kj <= qi) & (qi - kj < window)
    o = _product("bhqk,bkhd->bqhd")(jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1), v)
    if group == 1:
        return o
    return o.reshape(B, group, n_q, nh // group, d).transpose(0, 2, 3, 1, 4).reshape(B, n_q, nh, d)


def fused(backend: str, T: int, qk_dim: int, v_dim: int, block: int,
          window: Optional[int] = None, diffusion_block: Optional[int] = None) -> bool:
    """Whether a call site takes the fused kernel: on a TPU, at shapes the
    kernel tiles. Value heads of whole lane rows; query/key heads of half
    lane rows at least, which the kernel fills up with zero columns; query
    tiles of whole lane rows that divide the record, a window they divide,
    under the diffusion mask two halves of whole tiles in blocks the tile
    holds whole. Everything else runs the blocked form."""
    if backend != "tpu" or v_dim % LANE or qk_dim % (LANE // 2) or block % LANE:
        return False
    if diffusion_block is not None:
        return T % (2 * block) == 0 and block % diffusion_block == 0
    return T % block == 0 and (window is None or window >= T or window % block == 0)


# (a call site's kind, fused) -> the counter it adds to at trace time (literal names)
_COUNT = {
    ("mla", True): lambda: STAT_ADD("model.mla.fused_scores"),
    ("mla", False): lambda: STAT_ADD("model.mla.blocked_scores"),
    ("window", True): lambda: STAT_ADD("model.attn.fused_window_scores"),
    ("full", True): lambda: STAT_ADD("model.attn.fused_full_scores"),
    ("diffusion", True): lambda: STAT_ADD("model.attn.fused_diffusion_scores"),
    **{(kind, False): lambda: STAT_ADD("model.attn.blocked_scores")
       for kind in ("window", "full", "diffusion")},
}


def scores(q, k, v, *, scale: float, block: int, kind: str, group: int = 1,
           window: Optional[int] = None, diffusion_block: Optional[int] = None):
    """softmax(q k^T * scale over the visible keys) v: q [B, T, H, Dqk], k
    [B, T, H / group, Dqk], v [B, T, H / group, Dv] bfloat16 -> [B, T, H, Dv]
    float32, ``block`` queries a tile (at most the record, or a half of it
    under the diffusion mask), by the fused kernel or the blocked form
    (``fused``), counted by the call site's ``kind``: ``mla``, ``window``,
    ``full`` or ``diffusion``."""
    if (kind, True) not in _COUNT:
        raise ValueError(f"call site kind {kind!r}")
    T = q.shape[1]
    if diffusion_block is None:
        Q = min(block, T)
        if T % Q:
            raise ValueError(f"seq_len {T} is not a multiple of attn_block {Q}")
    else:
        Q = min(block, T // 2)
        if (T // 2) % Q or Q % diffusion_block:
            raise ValueError(f"a half of {T // 2} in query blocks of {Q}, blocks of {diffusion_block}")
    if fused(jax.default_backend(), T, q.shape[-1], v.shape[-1], Q, window, diffusion_block):
        _COUNT[kind, True]()
        return causal_attention(q, k, v, scale, Q, False, group, window, diffusion_block)
    _COUNT[kind, False]()
    return jnp.concatenate([_attend_block(q, k, v, i, Q, scale, group, window, diffusion_block)
                            for i in range(0, T, Q)], axis=1)


def window_or_full(q, k, v, rope, sliding, *, sliding_window: int, block: int, group: int,
                   scope: str):
    """The scores of a stack of window layers with rope beside full layers
    without positions. q [B, T, H, D], k, v [B, T, H / group, D] float32;
    ``sliding`` is the layer's kind: a bool, or a traced flag where a scan's
    step is told it (``lax.cond`` then holds both kinds' call sites)."""
    kinds = [partial(_one_kind, rope=rope, sliding=s, sliding_window=sliding_window, block=block,
                     group=group, scope=scope) for s in (True, False)]
    if isinstance(sliding, bool):
        return kinds[0 if sliding else 1](q, k, v)
    return lax.cond(sliding, *kinds, q, k, v)


def _one_kind(q, k, v, rope, sliding: bool, sliding_window: int, block: int, group: int,
              scope: str):
    """One kind's part of the layer: rope (sliding layers alone), the casts, the scores."""
    T = q.shape[1]
    with jax.named_scope(f"{scope}/attn/qk_norm_rope"):
        if sliding:
            q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        q, k, v = q.astype(BF16), k.astype(BF16), v.astype(BF16)
    window = sliding_window if sliding and sliding_window < T else None
    with jax.named_scope(f"{scope}/attn/scores_window" if sliding else f"{scope}/attn/scores_full"):
        return scores(q, k, v, scale=float(q.shape[-1]) ** -0.5, block=block, group=group,
                      window=window, kind="window" if sliding else "full")
