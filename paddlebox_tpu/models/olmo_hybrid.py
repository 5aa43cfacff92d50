"""Olmo-Hybrid (``olmo_hybrid``): Gated DeltaNet linear attention in three of
every four layers beside a full softmax layer without positions, a dense
SwiGLU in every layer, OLMo 2's block (arXiv:2501.00656: no input norm, an
RMSNorm on each sublayer's output before the residual sum, QK-norm over the
whole q and k projections); a language model trained through the pass path
(``models/base.py::SequenceLossModel``). Its linear attention is
``models/linear_attention.py``'s, its full layer's scores
``models/attention.py``'s, its norms, SwiGLU, head, loss
``models/lm_layers.py``'s.

A linear layer, per held head (``linear_num_value_heads`` of them, keys of
``linear_key_head_dim``, values of ``linear_value_head_dim``): q, k, v =
silu(conv4(x W)), a causal depthwise convolution of kernel
``linear_conv_kernel_dim`` each; q and k divided by their norms, q times
dk^-1/2; beta = 2 sigmoid(x w_b) (``linear_allow_neg_eigval``: the factor 2,
negative eigenvalues of I - beta k k^T); the log-decay g = -exp(A_log)
softplus(x w_a + dt_bias); the gated delta rule; ``W_o [RMSNorm(o) w_n *
silu(x W_g)]``. A full layer: q = RMSNorm(x W_q), k = RMSNorm(x W_k), each
over the held projection's columns (``qk_ms`` hands a layer the whole
projection's mean square instead), v = x W_v, heads of ``head_dim``, causal
softmax, no rope, ``W_o``. Either layer: ``h = x + norm(y)``, then
``h + norm(SwiGLU(h))``.

One instance is one chip's share of a head-parallel group: the held heads
of every layer with their convolution channels, gates, decays and output
rows, the feed-forward whole (data parallel over the group's tokens), a
slice of the vocabulary (``test_olmo_hybrid`` adds three shares up to the
uncut layer).

The stack is one ``lax.scan`` over periods (``layer_types`` in whole
periods of linear layers then a full one) whose body scans the period's
linear layers and runs the full one: depth never multiplies the compile.
The linear layers' leaves are stacked flat (``[periods x linear layers,
...]``) and cut into periods inside the program, and beta's and the decay's
projections are one leaf ``ba`` (``[w_b | w_a]``, Qwen3-Next's
``in_proj_ba``): where the table's layout is the compiler's choice
(``train/table_format.py``) the TPU compiler lays a donated leaf with a
leading axis of one period, or a ``[layers, H, 10]`` stack, out one way on
the way in and another of another size on the way out, and the alias fails.
Precision: float32 but for the bfloat16 operands of the projections, the
SwiGLU, the scores and the head; the delta rule float32 at ``highest``.
Memory: every layer recomputed in the backward from its input, the full
layer's checkpoint keeping the fused scores' output and logsumexp
(``KEEP_SCORES``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddlebox_tpu.models.attention import window_or_full
from paddlebox_tpu.models.linear_attention import (
    delta_rule, gated_rms_norm, l2_normalize, short_conv)
from paddlebox_tpu.models.lm_layers import (
    F32, TokenConfig, TokenModel, _mm, feed_ids, rms_norm, swiglu, window_loss)
from paddlebox_tpu.ops.pallas_kernels import KEEP_SCORES
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_SET

LINEAR, FULL = "linear_attention", "full_attention"
COUNTERS = ("loss_first_chunk", "loss_past_first_chunk", "tokens", "beta_over_one_share",
            "decay_mean")


@dataclass(frozen=True)
class OlmoHybridConfig(TokenConfig):
    """Keys as in the published ``config.json``; ``layer_types``, the head
    counts and ``vocab_size`` are what this instance holds."""

    hidden_size: int = 3840
    num_attention_heads: int = 10
    num_key_value_heads: int = 10
    head_dim: int = 128
    intermediate_size: int = 11008
    rms_norm_eps: float = 1e-6
    layer_types: Tuple[str, ...] = (LINEAR, LINEAR, LINEAR, FULL)
    linear_num_key_heads: int = 10
    linear_num_value_heads: int = 10
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    vocab_size: int = 33451
    seq_len: int = 4096
    initializer_range: float = 0.02
    attn_block: int = 512  # queries (and, in the fused kernel, keys) a tile of the scores
    loss_block: int = 1024  # positions whose logits exist at once
    chunk: int = 64  # tokens a chunk of the delta rule

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        n = self.layer_types.index(FULL) + 1 if FULL in self.layer_types else 0
        period = (LINEAR,) * (n - 1) + (FULL,)
        if not n or self.layer_types != period * (len(self.layer_types) // n):
            raise ValueError(f"layer_types {self.layer_types} are not whole periods of linear "
                             "layers then a full one")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("the full layers' key-value heads are their query heads")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("the linear layers' key heads are their value heads")

    @property
    def linear_per_period(self) -> int:
        return self.layer_types.index(FULL)

    @property
    def periods(self) -> int:
        return len(self.layer_types) // (self.linear_per_period + 1)


# ---- the two kinds of attention ---------------------------------------------------


def linear_attention(p, x, c: OlmoHybridConfig, scope: str = "model"):
    """x [B, T, H] -> (y = W_o [gated norm of the delta rule's output], before
    the post-norm; [the share of (token, head) steps with beta > 1, mean alpha])."""
    B, T, _ = x.shape
    nh, dk, dv = c.linear_num_value_heads, c.linear_key_head_dim, c.linear_value_head_dim
    with jax.named_scope(f"{scope}/linear_attn/qkv_proj"):
        q, k, v = _mm(x, p["q"]), _mm(x, p["k"]), _mm(x, p["v"])
    with jax.named_scope(f"{scope}/linear_attn/short_conv"):
        q = l2_normalize(short_conv(q, p["conv_q"]).reshape(B, T, nh, dk)) * float(dk) ** -0.5
        k = l2_normalize(short_conv(k, p["conv_k"]).reshape(B, T, nh, dk))
        v = short_conv(v, p["conv_v"]).reshape(B, T, nh, dv)
    with jax.named_scope(f"{scope}/linear_attn/gates"):
        b, a = jnp.split(_mm(x, p["ba"]), 2, axis=-1)  # [w_b | w_a], a column a head each
        beta = jax.nn.sigmoid(b)
        if c.linear_allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])  # log alpha
    with jax.named_scope(f"{scope}/linear_attn/delta_rule"):
        o = delta_rule(q, k, v, beta, g, c.chunk, f"{scope}/linear_attn/delta_rule")
    with jax.named_scope(f"{scope}/linear_attn/out_norm_gate"):
        o = gated_rms_norm(o, _mm(x, p["g"]).reshape(B, T, nh, dv), p["out_norm"], c.rms_norm_eps)
    with jax.named_scope(f"{scope}/linear_attn/out_proj"):
        y = _mm(o.reshape(B, T, nh * dv), p["o"])
    readings = jnp.stack([jnp.mean((beta > 1.0).astype(F32)), jnp.mean(jnp.exp(g))])
    return y, lax.stop_gradient(readings)


def mean_square(a):
    """The QK-norm's statistic: a's mean square over its last axis, [..., 1]."""
    return jnp.mean(a * a, axis=-1, keepdims=True)


def full_attention(p, x, c: OlmoHybridConfig, qk_ms: Optional[tuple] = None,
                   scope: str = "model"):
    """x [B, T, H] -> y = W_o softmax(q k^T / sqrt(d)) v, before the
    post-norm. ``qk_ms`` (the mean squares of q's and k's projection, [B, T,
    1] each) stands in for the held columns' own: where a group shares a
    layer it is the whole projection's, one all-reduce away."""
    B, T, _ = x.shape
    nh, d, eps = c.num_attention_heads, c.head_dim, c.rms_norm_eps
    with jax.named_scope(f"{scope}/attn/qkv_proj"):
        q, k, v = _mm(x, p["q"]), _mm(x, p["k"]), _mm(x, p["v"])
    with jax.named_scope(f"{scope}/attn/qk_norm_rope"):
        q_ms, k_ms = qk_ms if qk_ms is not None else (mean_square(q), mean_square(k))
        q = q * lax.rsqrt(q_ms + eps) * p["q_norm"]
        k = k * lax.rsqrt(k_ms + eps) * p["k_norm"]
    heads = lambda a: a.reshape(B, T, nh, d)  # noqa: E731
    o = window_or_full(heads(q), heads(k), heads(v), None, False, sliding_window=T,
                       block=c.attn_block, group=1, scope=scope)
    with jax.named_scope(f"{scope}/attn/out_proj"):
        return _mm(o.reshape(B, T, nh * d), p["o"])


# ---- layers ---------------------------------------------------------------------


def _mlp(p, h, c: OlmoHybridConfig, scope: str):
    with jax.named_scope(f"{scope}/mlp"):
        return h + rms_norm(swiglu(p["mlp"], h), p["ln_post_mlp"], c.rms_norm_eps)


def linear_layer(p, x, c: OlmoHybridConfig, scope: str = "model"):
    """-> (x', the layer's readings [2])."""
    y, readings = linear_attention(p, x, c, scope)
    with jax.named_scope(f"{scope}/linear_attn/out_proj"):
        h = x + rms_norm(y, p["ln_post_attn"], c.rms_norm_eps)
    return _mlp(p, h, c, scope), readings


def full_layer(p, x, c: OlmoHybridConfig, qk_ms: Optional[tuple] = None, scope: str = "model"):
    y = full_attention(p, x, c, qk_ms, scope)
    with jax.named_scope(f"{scope}/attn/out_proj"):
        h = x + rms_norm(y, p["ln_post_attn"], c.rms_norm_eps)
    return _mlp(p, h, c, scope)


# ---- the model ------------------------------------------------------------------


class OlmoHybrid(TokenModel):
    """``apply(params, emb [B, T, H], ids [B, T]) -> (loss, {"counters": [5]})``;
    ``forward`` gives the logit terms behind it."""

    counter_names = COUNTERS

    # -- parameters

    def _linear_init(self, key):
        c = self.cfg
        H, nh, dk, dv = (c.hidden_size, c.linear_num_value_heads, c.linear_key_head_dim,
                         c.linear_value_head_dim)
        ks = jax.random.split(key, 12)
        w = lambda k, *s: jax.random.normal(k, s, F32) * c.initializer_range  # noqa: E731
        bound = float(c.linear_conv_kernel_dim) ** -0.5  # a depthwise Conv1d's default
        conv = lambda k, n: jax.random.uniform(  # noqa: E731
            k, (c.linear_conv_kernel_dim, n), F32, -bound, bound)
        dt = jnp.exp(jax.random.uniform(ks[10], (nh,), F32, np.log(1e-3), np.log(0.1)))
        return {"q": w(ks[0], H, nh * dk), "k": w(ks[1], H, nh * dk), "v": w(ks[2], H, nh * dv),
                "ba": w(ks[3], H, 2 * nh),
                "A_log": jnp.log(jax.random.uniform(ks[5], (nh,), F32, 1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "conv_q": conv(ks[6], nh * dk), "conv_k": conv(ks[7], nh * dk),
                "conv_v": conv(ks[8], nh * dv), "g": w(ks[9], H, nh * dv),
                "out_norm": jnp.ones((dv,)), "o": w(ks[11], nh * dv, H)}

    def _full_init(self, key):
        c = self.cfg
        H, n = c.hidden_size, c.num_attention_heads * c.head_dim
        ks = jax.random.split(key, 4)
        w = lambda k, *s: jax.random.normal(k, s, F32) * c.initializer_range  # noqa: E731
        return {"q": w(ks[0], H, n), "k": w(ks[1], H, n), "v": w(ks[2], H, n), "o": w(ks[3], n, H),
                "q_norm": jnp.ones((n,)), "k_norm": jnp.ones((n,))}

    def _layer_init(self, key, linear: bool):
        c = self.cfg
        ks = jax.random.split(key)
        ones = lambda: jnp.ones((c.hidden_size,))  # noqa: E731
        attn = self._linear_init(ks[0]) if linear else self._full_init(ks[0])
        return {**attn, "ln_post_attn": ones(), "ln_post_mlp": ones(),
                "mlp": self._mlp_init(ks[1], c.intermediate_size)}

    def init(self, rng) -> Dict[str, Any]:
        c = self.cfg
        P, L = c.periods, c.linear_per_period
        ks = jax.random.split(rng, P + 1)
        stack = lambda trees: jax.tree.map(lambda *a: jnp.stack(a), *trees)  # noqa: E731

        kl = [jax.random.split(k, L + 1) for k in ks[:P]]
        return {"linear": stack([self._layer_init(k[j], True) for k in kl for j in range(L)]),
                "full": stack([self._layer_init(k[L], False) for k in kl]),
                "final_norm": jnp.ones((c.hidden_size,)),
                "head": jax.random.normal(ks[P], (c.hidden_size, c.vocab_size), F32)
                * c.initializer_range}

    # -- forward and loss

    def hidden_states(self, params, emb):
        """emb [B, T, H] -> (the last hidden state before the final norm, the
        linear layers' readings [linear layers, 2])."""
        c = self.cfg
        linear = jax.checkpoint(lambda p, x: linear_layer(p, x, c))
        full = jax.checkpoint(lambda p, x: full_layer(p, x, c), policy=KEEP_SCORES)
        # checkpoints that keep the scores' output and logsumexp, at trace time
        STAT_ADD("model.attn.keep_scores_sites")

        def period(x, p):  # one compiled body: the period's linear layers, then its full one
            pl, pf = p
            x, readings = lax.scan(lambda x, pl: linear(pl, x), x, pl)
            return full(pf, x), readings

        by_period = jax.tree.map(lambda a: a.reshape(c.periods, -1, *a.shape[1:]), params["linear"])
        x, readings = lax.scan(period, emb.astype(F32), (by_period, params["full"]))
        return x, readings.reshape(-1, 2)

    def forward(self, params, emb, ids):
        """What one batch gives: ``parts`` [2] (the mean cross-entropy of the
        target positions inside the first chunk, t < ``chunk``, which no
        carried state reaches, and of those past it), ``token_logits`` [2, B,
        T] (the target's logit, then the logsumexp of all logits),
        ``router_choices`` one [1, B, T, 1] zero array (no router: the
        benchmark's comparison reads every model's), ``linear`` [2] (the linear
        layers' mean readings) and the ``loss``, the plain mean over the T -
        1 positions that have a target. emb [B, T, H]: the token slot's
        pulled rows, CVM columns dropped; ids [B, T]: the record's token ids
        (whole numbers in float32 or int32), relative to the held slice."""
        c = self.cfg
        ids = feed_ids(emb, ids, c.seq_len)
        x, readings = self.hidden_states(params, emb)
        return {**window_loss(params, x, ids, c.chunk, c.rms_norm_eps, c.loss_block),
                "router_choices": jnp.zeros((1,) + ids.shape + (1,), jnp.int32),
                "linear": jnp.mean(readings, axis=0)}

    def apply(self, params, emb, ids):
        """The training loss of one batch (``forward``'s arguments) and the
        one array the step carries out beside it: ``counters``, named by
        ``counter_names``."""
        out = self.forward(params, emb, ids)
        with jax.named_scope("loss/head"):
            tokens = jnp.asarray([float(emb.shape[0] * emb.shape[1])])
            counters = jnp.concatenate([out["parts"], tokens, out["linear"]])
        return out["loss"], {"counters": lax.stop_gradient(counters)}

    @staticmethod
    def record_counters(means) -> None:
        """A pass's mean counters into the monitor registry (literal names)."""
        STAT_SET("model.loss_first_chunk", float(means[0]))
        STAT_SET("model.loss_past_first_chunk", float(means[1]))
        STAT_SET("model.tokens_per_step", float(means[2]))
        STAT_SET("model.beta_over_one_share", float(means[3]))
        STAT_SET("model.decay_mean", float(means[4]))
