"""Trinity (``afmoe``): grouped-query attention with QK-norm and an output
gate, window layers with rope beside full layers without positions in one
stack, routed experts beside a shared one; a language model trained through
the pass path (``models/base.py::SequenceLossModel``). Its scores are
``models/attention.py``'s, its expert layer ``models/moe.py``'s, its norms,
rope, head, loss and counters ``models/lm_layers.py``'s.

The step hands it the pulled rows of the one token slot unpooled, as
``[B, T, hidden]`` in record order, and the record's dense slot of T token
ids; it returns the next-token cross-entropy over the vocabulary slice it
holds and its counters. One instance is one chip's share of an
expert-parallel group: ``experts_held`` of ``num_experts`` from
``experts_offset`` on, every token routed over all of them, the held ones'
part computed (``test_afmoe`` adds the shares up to the uncut layer).

A layer (``layer_types``): ``a = norm(x)``; q, k, v and a gate from ``a``;
RMSNorm over each head's q and k; rope on q and k *on a sliding layer only*;
query head h attends key-value head h // group, causally, on a sliding layer
only ``sliding_window`` keys back; ``x += norm((o * sigmoid(gate)) W_o)``;
``x += norm(F(norm(x)))``, F a SwiGLU or the experts. The input is scaled by
sqrt(hidden) (``mup_enabled``).

Precision: float32 but for the bfloat16 operands of the matrix products.
Memory: every layer recomputed in the backward from its input, but for the
fused scores' float32 output and logsumexp (``models/attention.py``; 0.67 GB
over the cell's five layers of one 8k record) and, in the expert layers, the
grouped product's output, which the norm after the experts reads
(``models/moe.py::KEEP_EXPERTS``: the backward runs the blocks' pass once;
0.27 GB over the cell's four). The expert layers are one stacked body under
``lax.scan`` **whose step is told its kind** (a traced flag: ``lax.cond``
picks rope and window or neither, so both kinds compile once whatever their
order); the scores take the fused kernel with ``group`` and ``window`` on a
TPU at shapes it tiles and query blocks elsewhere, counted at trace time
under ``model.attn.fused_window_scores`` / ``model.attn.fused_full_scores`` /
``model.attn.blocked_scores``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.models.attention import window_or_full
from paddlebox_tpu.models.lm_layers import (
    F32, WINDOW_COUNTERS, GroupedQueryConfig, TokenModel, _mm, feed_ids, record_window_counters,
    rms_norm, rope_tables, step_counters, swiglu, window_loss)
from paddlebox_tpu.models.moe import KEEP_EXPERTS, route, routed_experts
from paddlebox_tpu.ops.pallas_kernels import KEEP_SCORES
from paddlebox_tpu.utils.monitor import STAT_ADD

SLIDING, FULL = "sliding_attention", "full_attention"
COUNTERS = WINDOW_COUNTERS


@dataclass(frozen=True)
class AfmoeConfig(GroupedQueryConfig):
    """Keys as in the published ``config.json``; ``layer_types`` (with
    ``num_dense_layers`` of them dense, leading) and ``vocab_size`` are what
    this instance holds, ``num_experts`` what the router scores."""

    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    sliding_window: int = 2048
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
    num_dense_layers: int = 1
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    route_scale: float = 2.826
    mup_enabled: bool = True
    vocab_size: int = 25024
    experts_held: int = 16
    experts_offset: int = 0
    seq_len: int = 8192
    initializer_range: float = 0.02
    attn_block: int = 512  # queries (and, in the fused kernel, keys) a tile of the scores
    loss_block: int = 1024  # positions whose logits exist at once
    expert_block: int = 512  # rows of one grouped product

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if not set(self.layer_types) <= {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types}")
        super().__post_init__()


# ---- attention ------------------------------------------------------------------


def attention(p, x, w_in, w_post, c: AfmoeConfig, rope, sliding, scope: str = "model"):
    """x + norm(attention(norm(x))). x [B, T, H]. ``sliding`` is the layer's
    kind: a bool, or a traced flag where a scan's step is told it. Every leaf
    scope is named in full (see ``glm_moe_lite.mla_branch``)."""
    B, T, _ = x.shape
    nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope(f"{scope}/attn/qkvg_proj"):
        a = rms_norm(x, w_in, c.rms_norm_eps)
        q = _mm(a, p["q"]).reshape(B, T, nh, d)
        k = _mm(a, p["k"]).reshape(B, T, nkv, d)
        v = _mm(a, p["v"]).reshape(B, T, nkv, d)
        gate = _mm(a, p["gate"])
    with jax.named_scope(f"{scope}/attn/qk_norm_rope"):
        q = rms_norm(q, p["q_norm"], c.rms_norm_eps)
        k = rms_norm(k, p["k_norm"], c.rms_norm_eps)
    o = window_or_full(q, k, v, rope, sliding, sliding_window=c.sliding_window,
                       block=c.attn_block, group=c.group, scope=scope)
    with jax.named_scope(f"{scope}/attn/out_proj"):
        y = _mm(o.reshape(B, T, nh * d) * jax.nn.sigmoid(gate), p["o"])
        return x + rms_norm(y, w_post, c.rms_norm_eps)


# ---- layers ---------------------------------------------------------------------


def dense_layer(p, x, c: AfmoeConfig, rope, sliding, scope: str = "model"):
    h = attention(p["attn"], x, p["ln_in"], p["ln_post_attn"], c, rope, sliding, scope)
    with jax.named_scope(f"{scope}/dense_mlp"):
        y = swiglu(p["mlp"], rms_norm(h, p["ln_pre_mlp"], c.rms_norm_eps))
        return h + rms_norm(y, p["ln_post_mlp"], c.rms_norm_eps)


def moe_layer(p, x, c: AfmoeConfig, rope, sliding, scope: str = "model"):
    """-> (stream, chosen experts [B, T, k], held experts' loads)."""
    B, T, H = x.shape
    h = attention(p["attn"], x, p["ln_in"], p["ln_post_attn"], c, rope, sliding, scope)
    with jax.named_scope(f"{scope}/moe/router"):
        flat = rms_norm(h, p["ln_pre_mlp"], c.rms_norm_eps).reshape(B * T, H)
        idx, g = route(p["router"], flat, c.num_experts_per_tok, scale=c.route_scale)
    routed, counts = routed_experts(p["experts"], flat, idx, g, c.experts_held, c.experts_offset,
                                    c.expert_block, scope)
    with jax.named_scope(f"{scope}/moe/shared"):
        y = (swiglu(p["shared"], flat) + routed).reshape(B, T, H)
        out = h + rms_norm(y, p["ln_post_mlp"], c.rms_norm_eps)
    return out, idx.reshape(B, T, -1), counts


# ---- the model ------------------------------------------------------------------


class Afmoe(TokenModel):
    """``apply(params, emb [B, T, H], ids [B, T]) -> (loss, {"counters": [5]})``;
    ``forward`` gives the logit terms and expert choices behind it."""

    counter_names = COUNTERS

    # -- parameters

    def _attn_init(self, key):
        c = self.cfg
        ks = jax.random.split(key, 5)
        H, nh, nkv, d = c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim
        w = lambda k, *s: jax.random.normal(k, s, F32) * c.initializer_range  # noqa: E731
        return {"q": w(ks[0], H, nh * d), "k": w(ks[1], H, nkv * d), "v": w(ks[2], H, nkv * d),
                "gate": w(ks[3], H, nh * d), "o": w(ks[4], nh * d, H),
                "q_norm": jnp.ones((d,)), "k_norm": jnp.ones((d,))}

    def _layer_init(self, key, moe: bool):
        c = self.cfg
        ks = jax.random.split(key, 5)
        ones = lambda: jnp.ones((c.hidden_size,))  # noqa: E731
        p = {"attn": self._attn_init(ks[0]), "ln_in": ones(), "ln_post_attn": ones(),
             "ln_pre_mlp": ones(), "ln_post_mlp": ones()}
        if not moe:
            return {**p, "mlp": self._mlp_init(ks[1], c.intermediate_size)}
        normal = lambda k, *s: jax.random.normal(k, s, F32) * c.initializer_range  # noqa: E731
        return {
            **p,
            "router": {"w": normal(ks[1], c.hidden_size, c.num_experts),
                       "bias": normal(ks[2], c.num_experts)},
            "shared": self._mlp_init(ks[3], c.moe_intermediate_size),
            "experts": self._mlp_init(ks[4], c.moe_intermediate_size, lead=(c.experts_held,)),
        }

    def init(self, rng) -> Dict[str, Any]:
        c = self.cfg
        n = len(c.layer_types)
        ks = jax.random.split(rng, n + 1)
        moe = [self._layer_init(ks[i], True) for i in range(c.num_dense_layers, n)]
        return {
            "dense": [self._layer_init(ks[i], False) for i in range(c.num_dense_layers)],
            "moe": jax.tree.map(lambda *a: jnp.stack(a), *moe),
            "final_norm": jnp.ones((c.hidden_size,)),
            "head": jax.random.normal(ks[n], (c.hidden_size, c.vocab_size), F32)
            * c.initializer_range,
        }

    # -- forward and loss

    def hidden_states(self, params, emb):
        """emb [B, T, H] -> (the last hidden state before the final norm,
        chosen experts [expert layers, B, T, k], held loads [expert layers,
        held])."""
        c = self.cfg
        rope = rope_tables(emb.shape[1], c.head_dim, c.rope_theta)
        kinds = [t == SLIDING for t in c.layer_types]
        x = emb.astype(F32)
        if c.mup_enabled:
            x = x * float(c.hidden_size) ** 0.5
        for p, sliding in zip(params["dense"], kinds):
            x = jax.checkpoint(lambda p, x, s=sliding: dense_layer(p, x, c, rope, s),
                               policy=KEEP_SCORES)(p, x)
        # checkpoints that keep the scores' output and logsumexp, at trace time
        STAT_ADD("model.attn.keep_scores_sites", c.num_dense_layers + 1)
        STAT_ADD("model.moe.keep_output_sites")  # and the expert output: the norm after it reads it

        @partial(jax.checkpoint, policy=KEEP_EXPERTS)
        def body(x, layer):
            p, sliding = layer  # one compiled body: the step is told its kind
            x, idx, counts = moe_layer(p, x, c, rope, sliding)
            return x, (idx, counts)

        x, (choices, loads) = lax.scan(
            body, x, (params["moe"], jnp.asarray(kinds[c.num_dense_layers:])))
        return x, choices, loads

    def forward(self, params, emb, ids):
        """What one batch gives: ``parts`` [2] (the mean cross-entropy of the
        target positions inside the first window, t < sliding_window, and of
        those past it, where a window layer no longer sees the whole prefix;
        a sequence no longer than the window has no second part: 0),
        ``token_logits`` [2, B, T] (the target's logit, then the logsumexp of
        all logits), ``router_choices`` [expert layers, B, T, k], the held
        experts' ``loads`` and the ``loss``, the plain mean over the T - 1
        positions that have a target. emb [B, T, H]: the token slot's pulled
        rows, CVM columns dropped; ids [B, T]: the record's token ids (whole
        numbers in float32 or int32), relative to the held slice."""
        c = self.cfg
        ids = feed_ids(emb, ids, c.seq_len)
        x, choices, loads = self.hidden_states(params, emb)
        return {**window_loss(params, x, ids, c.sliding_window, c.rms_norm_eps, c.loss_block),
                "router_choices": choices, "loads": loads}

    def apply(self, params, emb, ids):
        """The training loss of one batch (``forward``'s arguments) and the
        one array the step carries out beside it: ``counters``, named by
        ``counter_names``."""
        out = self.forward(params, emb, ids)
        with jax.named_scope("loss/head"):
            counters = jnp.stack(step_counters(out["parts"], out["loads"].astype(F32),
                                               emb.shape[0] * emb.shape[1]))
        return out["loss"], {"counters": lax.stop_gradient(counters)}

    record_counters = staticmethod(record_window_counters)
