"""SmallThinker (``smallthinker``): a router that reads the layer's input
stream *ahead of attention*, ReLU-gated routed experts with no shared expert
and no dense layer, a full layer without positions to three window layers
with rope, grouped-query heads; a language model trained through the pass
path (``models/base.py::SequenceLossModel``). Its scores are
``models/attention.py``'s, its expert layer ``models/moe.py``'s, its norms,
rope, head, loss and counters ``models/lm_layers.py``'s.

The step hands it the pulled rows of the one token slot unpooled, as
``[B, T, hidden]`` in record order, and the record's dense slot of T token
ids; it returns the next-token cross-entropy over the vocabulary slice it
holds and its counters. One instance is one chip's share of an
expert-parallel group: ``experts_held`` of ``num_experts`` from
``experts_offset`` on, every token routed over all of them, the held ones'
part computed. There is no shared expert: a token none of whose chosen
experts is held gets no feed-forward output here at all
(``test_smallthinker`` adds the shares up to the uncut layer, and counts
those tokens).

A layer (``layer_kinds``, 1 = sliding): the router's choice *first*, from the
layer's input itself (before any norm): ``r = x W_r``, the top k logits, a
softmax over the chosen; ``a = norm(x)``; q, k, v from ``a``; rope on q and k
*on a sliding layer only*; query head h attends key-value head h // group,
causally, on a sliding layer only ``sliding_window`` keys back;
``x += o W_o``; ``x += sum_k w_k E_k(norm(x))`` over the chosen experts held,
``E(m) = (m W_up * relu(m W_gate)) W_down``: the experts read the
post-attention stream, by the choice made before it.

Precision: float32 but for the bfloat16 operands of the matrix products.
Memory: every layer recomputed in the backward from its input, but for the
fused scores' float32 output and logsumexp (``models/attention.py``; 0.94 GB
over the cell's four layers of one 16k record). The stack is one body under ``lax.scan`` whose step is
told its kind.
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
    rms_norm, rope_tables, step_counters, window_loss)
from paddlebox_tpu.models.moe import (
    KEEP_EXPERTS, SHARE_COUNTERS, record_share_counters, route, routed_experts, share_counters)
from paddlebox_tpu.utils.monitor import STAT_ADD

COUNTERS = WINDOW_COUNTERS + SHARE_COUNTERS


@dataclass(frozen=True)
class SmallThinkerConfig(GroupedQueryConfig):
    """Sizes as in the published ``config.json`` (under the names the shared
    pieces read); ``layer_kinds`` (1 = sliding with rope, 0 = full without
    positions) and ``vocab_size`` are what this instance holds, ``num_experts``
    what the router scores."""

    hidden_size: int = 2560
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    sliding_window: int = 4096
    layer_kinds: Tuple[int, ...] = (0, 1, 1, 1)
    moe_intermediate_size: int = 768
    num_experts: int = 64
    num_experts_per_tok: int = 6
    vocab_size: int = 18992
    experts_held: int = 8
    experts_offset: int = 0
    seq_len: int = 16384
    initializer_range: float = 0.02
    attn_block: int = 512  # queries (and, in the fused kernel, keys) a tile of the scores
    loss_block: int = 1024  # positions whose logits exist at once
    expert_block: int = 512  # rows of one grouped product

    def __post_init__(self):
        object.__setattr__(self, "layer_kinds", tuple(int(k) for k in self.layer_kinds))
        if not set(self.layer_kinds) <= {0, 1}:
            raise ValueError(f"layer_kinds {self.layer_kinds}")
        super().__post_init__()


def attention(p, x, w_in, c: SmallThinkerConfig, rope, sliding, scope: str = "model"):
    """x + attention(norm(x)) W_o. x [B, T, H]. ``sliding`` is the layer's
    kind: a bool, or a traced flag where a scan's step is told it. No biases,
    no QK-norm, no gate: ``qk_norm_rope`` (``attention.window_or_full``'s own
    name) holds rope and the casts here."""
    B, T, _ = x.shape
    nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope(f"{scope}/attn/qkv_proj"):
        a = rms_norm(x, w_in, c.rms_norm_eps)
        q = _mm(a, p["q"]).reshape(B, T, nh, d)
        k = _mm(a, p["k"]).reshape(B, T, nkv, d)
        v = _mm(a, p["v"]).reshape(B, T, nkv, d)
    o = window_or_full(q, k, v, rope, sliding, sliding_window=c.sliding_window,
                       block=c.attn_block, group=c.group, scope=scope)
    with jax.named_scope(f"{scope}/attn/out_proj"):
        return x + _mm(o.reshape(B, T, nh * d), p["o"])


def layer(p, x, c: SmallThinkerConfig, rope, sliding, scope: str = "model"):
    """-> (stream, chosen experts [B, T, k], held experts' loads)."""
    B, T, H = x.shape
    with jax.named_scope(f"{scope}/moe/router"):  # ahead of attention, from the input itself
        idx, g = route(p["router"], x.reshape(B * T, H), c.num_experts_per_tok,
                       form="softmax_of_chosen")
    h = attention(p["attn"], x, p["ln_in"], c, rope, sliding, scope)
    with jax.named_scope(f"{scope}/moe/experts"):
        flat = rms_norm(h, p["ln_post_attn"], c.rms_norm_eps).reshape(B * T, H)
    routed, counts = routed_experts(p["experts"], flat, idx, g, c.experts_held, c.experts_offset,
                                    c.expert_block, scope, "relu")
    with jax.named_scope(f"{scope}/moe/combine"):
        return h + routed.reshape(B, T, H), idx.reshape(B, T, -1), counts


class SmallThinker(TokenModel):
    """``apply(params, emb [B, T, H], ids [B, T]) -> (loss, {"counters": [7]})``;
    ``forward`` gives the logit terms and expert choices behind it."""

    counter_names = COUNTERS

    # -- parameters

    def _layer_init(self, key):
        c = self.cfg
        ks = jax.random.split(key, 8)
        H, nh, nkv, d = c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim
        I, G = c.moe_intermediate_size, c.experts_held
        w = lambda k, *s: jax.random.normal(k, s, F32) * c.initializer_range  # noqa: E731
        return {
            "attn": {"q": w(ks[0], H, nh * d), "k": w(ks[1], H, nkv * d), "v": w(ks[2], H, nkv * d),
                     "o": w(ks[3], nh * d, H)},
            "ln_in": jnp.ones((H,)), "ln_post_attn": jnp.ones((H,)),
            "router": {"w": w(ks[4], H, c.num_experts)},
            "experts": {"gate": w(ks[5], G, H, I), "up": w(ks[6], G, H, I), "down": w(ks[7], G, I, H)},
        }

    def init(self, rng) -> Dict[str, Any]:
        return self._stack_init(rng, len(self.cfg.layer_kinds))

    # -- forward and loss

    def hidden_states(self, params, emb):
        """emb [B, T, H] -> (the last hidden state before the final norm,
        chosen experts [layers, B, T, k], held loads [layers, held])."""
        c = self.cfg
        rope = rope_tables(emb.shape[1], c.head_dim, c.rope_theta)

        # checkpoints that keep the scores' output and logsumexp, at trace time
        STAT_ADD("model.attn.keep_scores_sites")
        STAT_ADD("model.moe.keep_output_sites")  # and the expert output, where a backward reads it

        @partial(jax.checkpoint, policy=KEEP_EXPERTS)
        def body(x, step):
            p, sliding = step  # one compiled body: the step is told its kind
            x, idx, counts = layer(p, x, c, rope, sliding)
            return x, (idx, counts)

        x, (choices, loads) = lax.scan(
            body, emb.astype(F32), (params["layers"], jnp.asarray(c.layer_kinds, bool)))
        return x, choices, loads

    def forward(self, params, emb, ids):
        """What one batch gives, as ``Afmoe.forward``: ``parts`` [2] (the mean
        cross-entropy of the target positions t < sliding_window and of those
        past it), ``token_logits`` [2, B, T] (the target's logit, then the
        logsumexp of all logits), ``router_choices`` [layers, B, T, k], the
        held experts' ``loads`` [layers, held] and the ``loss``, the plain mean
        over the T - 1 positions that have a target. emb [B, T, H]: the token
        slot's pulled rows, CVM columns dropped; ids [B, T]: the record's token
        ids (whole numbers in float32 or int32), relative to the held slice."""
        c = self.cfg
        ids = feed_ids(emb, ids, c.seq_len)
        x, choices, loads = self.hidden_states(params, emb)
        return {**window_loss(params, x, ids, c.sliding_window, c.rms_norm_eps, c.loss_block),
                "router_choices": choices, "loads": loads}

    def apply(self, params, emb, ids):
        """The training loss of one batch (``forward``'s arguments) and the
        one array the step carries out beside it: ``counters``, named by
        ``counter_names`` (``moe.share_counters`` has the last two)."""
        c = self.cfg
        out = self.forward(params, emb, ids)
        with jax.named_scope("loss/head"):
            counters = jnp.stack(
                step_counters(out["parts"], out["loads"].astype(F32), emb.shape[0] * emb.shape[1])
                + share_counters(out["router_choices"], out["loads"], c.experts_held,
                                 c.experts_offset, c.expert_block))
        return out["loss"], {"counters": lax.stop_gradient(counters)}

    @staticmethod
    def record_counters(means) -> None:
        """A pass's mean counters into the monitor registry (literal names)."""
        record_window_counters(means)
        record_share_counters(*means[5:7])
