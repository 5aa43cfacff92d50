"""What every token model (``models/base.py::SequenceLossModel``) is built of
beside its attention (``models/attention.py``) and its expert layer
(``models/moe.py``): the matrix product in bfloat16 with float32 accumulation,
RMSNorm, the rope tables (YaRN's too), SwiGLU, the head's logits a block of
positions at a time, the next-token loss and the counters the step carries,
and the one base of the models and of their configs.

Precision: float32 but for the bfloat16 operands of the matrix products,
which accumulate in float32, in the backward pass too (``_product``).
"""

from __future__ import annotations

from dataclasses import fields
from functools import lru_cache
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddlebox_tpu.utils.monitor import STAT_SET

BF16, F32 = jnp.bfloat16, jnp.float32
# the counters of a model whose loss has two parts, a window's and what lies past it
WINDOW_COUNTERS = ("loss_in_window", "loss_past_window", "tokens", "held_assignments",
                   "expert_load_max_over_mean")


class TokenConfig:
    """The base of a token model's frozen dataclass config."""

    @classmethod
    def from_dict(cls, d: Dict[str, Any]):
        """The keys the config names; the others are the benchmark's."""
        names = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


class GroupedQueryConfig(TokenConfig):
    """A config of ``num_attention_heads`` query heads over ``num_key_value_heads``."""

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not a multiple of the key-value heads")

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads


class TokenModel:
    """What the step reads off a token model: one slot of ``seq_len`` keys a
    record, fed unpooled, and the record's dense slot of ``seq_len`` ids."""

    sequence_feed = True  # the step feeds the slot's rows unpooled and takes the loss from here

    def __init__(self, cfg):
        self.cfg = cfg
        self.num_slots = 1
        self.seq_len = cfg.seq_len
        self.dense_dim = cfg.seq_len  # the record's dense slot: its ids
        self.feat_width = 3 + cfg.hidden_size

    def _mlp_init(self, key, width, lead=()):
        """A SwiGLU's ``gate``, ``up`` and ``down`` of ``width``, with the leading axes ``lead``."""
        c = self.cfg
        ks = jax.random.split(key, 3)
        w = lambda k, *s: jax.random.normal(k, lead + s, F32) * c.initializer_range  # noqa: E731
        return {"gate": w(ks[0], c.hidden_size, width), "up": w(ks[1], c.hidden_size, width),
                "down": w(ks[2], width, c.hidden_size)}

    def _stack_init(self, rng, n: int) -> Dict[str, Any]:
        """``n`` layers of ``_layer_init`` stacked for a scan, the final norm and the head."""
        c = self.cfg
        ks = jax.random.split(rng, n + 1)
        return {
            "layers": jax.tree.map(lambda *a: jnp.stack(a), *[self._layer_init(k) for k in ks[:n]]),
            "final_norm": jnp.ones((c.hidden_size,)),
            "head": jax.random.normal(ks[n], (c.hidden_size, c.vocab_size), F32)
            * c.initializer_range,
        }


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


def swiglu(p, x):
    return _mm(jax.nn.silu(_mm(x, p["gate"])) * _mm(x, p["up"]), p["down"])


# ---- the head and the loss ------------------------------------------------------


def feed_ids(emb, ids, seq_len: int):
    """The record's token ids as int32, once the feed fits the model."""
    B, T, _ = emb.shape
    if T != seq_len or ids.shape != (B, T):
        raise ValueError(f"sequence feed of {emb.shape} / {ids.shape}, seq_len {seq_len}")
    return ids.astype(jnp.int32)


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


def next_token_logits(params, x, ids, eps: float, block: int):
    """The last hidden state x [B, T, H] through the final norm and the head
    (``params["final_norm"]``, ``params["head"]``) against the next token ->
    (the target's logit, the logsumexp of all logits), [1, B, T] each; the
    last position's target is token 0, which no loss reads."""
    B, T, H = x.shape
    targets = jnp.concatenate([ids[:, 1:], jnp.zeros((B, 1), jnp.int32)], axis=1)
    h = rms_norm(x, params["final_norm"], eps)
    tl, lse = head_logits(params["head"], h.reshape(B * T, H), targets.reshape(-1), block)
    return tl.reshape(1, B, T), lse.reshape(1, B, T)


def window_loss(params, x, ids, window: int, eps: float, block: int) -> Dict[str, Any]:
    """``next_token_logits`` and the loss over them: ``parts`` [2] (the mean
    cross-entropy of the target positions t < ``window`` and of those past
    it, 0 where there are none), ``token_logits`` [2, B, T] and ``loss``, the
    plain mean over the T - 1 positions that have a target."""
    B, T, _ = x.shape
    with jax.named_scope("loss/head"):
        pos = jnp.arange(T)
        tl, lse = next_token_logits(params, x, ids, eps, block)
        has = pos < T - 1
        mask = jnp.stack([has & (pos < window), has & (pos >= window)]).astype(F32)[:, None, :]
        sums = jnp.sum((lse - tl) * mask, axis=(1, 2))
        parts = sums / jnp.maximum(B * jnp.sum(mask, axis=(1, 2)), 1.0)
        loss = jnp.sum(sums) / (B * (T - 1))
    return {"parts": parts, "token_logits": jnp.concatenate([tl, lse]), "loss": loss}


# ---- the counters ---------------------------------------------------------------


def load_counters(loads) -> list:
    """The held experts' loads (float32) -> [their sum, max over mean]."""
    return [jnp.sum(loads), jnp.max(loads) / jnp.maximum(jnp.mean(loads), 1e-9)]


def step_counters(parts, loads, tokens: int) -> list:
    """The five counters a step leads with: the loss's two ``parts``, the
    batch's ``tokens`` and ``load_counters`` of the float32 ``loads``."""
    return [parts[0], parts[1], jnp.asarray(float(tokens))] + load_counters(loads)


def record_load_counters(tokens, held, ratio) -> None:
    """A pass's mean tokens and load counters into the monitor registry (literal names)."""
    STAT_SET("model.tokens_per_step", float(tokens))
    STAT_SET("model.held_assignments_per_step", float(held))
    STAT_SET("model.expert_load_max_over_mean", float(ratio))


def record_window_counters(means) -> None:
    """A pass's mean ``WINDOW_COUNTERS`` into the monitor registry (literal names)."""
    STAT_SET("model.loss_in_window", float(means[0]))
    STAT_SET("model.loss_past_window", float(means[1]))
    record_load_counters(*means[2:5])
