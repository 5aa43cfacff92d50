"""SmallThinker (``smallthinker``): a router that reads the layer's input
stream *ahead of attention*, ReLU-gated routed experts with no shared expert
and no dense layer, a full layer without positions to three window layers
with rope, grouped-query heads; a language model trained through the pass
path, the third ``SequenceLossModel`` (``models/base.py``) beside
``models/glm_moe_lite.py`` and ``models/afmoe.py``, whose pieces it shares
(``rms_norm``, rope, ``_mm``, ``route``, ``routed_experts``, ``head_logits``
from the first; the scores part ``_scores``, with its kernel, its blocked form
and its trace-time counters, and the loss with its two parts and five counters
from the second: an optimisation of one is measured on all three, and on
``models/sdar.py``, the fourth, which takes ``share_counters`` from here).

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

Precision as ``glm_moe_lite``: float32 but for the bfloat16 operands of the
matrix products. Memory: every layer recomputed in the backward from its
input, but for the fused scores' float32 output and logsumexp, which the
layer's checkpoint keeps by name (``ops/pallas_kernels.py::KEEP_SCORES``: 0.94
GB over the cell's four layers of one 16k record): q, k, v are the
recomputation's anyway, so the backward kernel is fed without the forward
kernel's second run. The stack is one body under ``lax.scan`` whose step is
told its kind, as ``afmoe``'s.
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.models import afmoe
from paddlebox_tpu.models.afmoe import _scores
from paddlebox_tpu.models.glm_moe_lite import F32, _mm, rms_norm, rope_tables, route, routed_experts
from paddlebox_tpu.ops.pallas_kernels import KEEP_SCORES
from paddlebox_tpu.utils.monitor import STAT_ADD

COUNTERS = afmoe.COUNTERS + ("unrouted_tokens", "block_rows")


@dataclass(frozen=True)
class SmallThinkerConfig:
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
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not a multiple of the key-value heads")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SmallThinkerConfig":
        names = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads


def attention(p, x, w_in, c: SmallThinkerConfig, rope, sliding, scope: str = "model"):
    """x + attention(norm(x)) W_o. x [B, T, H]. ``sliding`` is the layer's
    kind: a bool, or a traced flag where a scan's step is told it. No biases,
    no QK-norm, no gate: ``qk_norm_rope`` (``afmoe._scores``'s own name) holds
    rope and the casts here."""
    B, T, _ = x.shape
    nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope(f"{scope}/attn/qkv_proj"):
        a = rms_norm(x, w_in, c.rms_norm_eps)
        q = _mm(a, p["q"]).reshape(B, T, nh, d)
        k = _mm(a, p["k"]).reshape(B, T, nkv, d)
        v = _mm(a, p["v"]).reshape(B, T, nkv, d)
    window, full = (partial(_scores, c=c, rope=rope, sliding=s, scope=scope) for s in (True, False))
    if isinstance(sliding, bool):
        o = (window if sliding else full)(q, k, v)
    else:
        o = lax.cond(sliding, window, full, q, k, v)
    with jax.named_scope(f"{scope}/attn/out_proj"):
        return x + _mm(o.reshape(B, T, nh * d), p["o"])


def layer(p, x, c: SmallThinkerConfig, rope, sliding, scope: str = "model"):
    """-> (stream, chosen experts [B, T, k], held experts' loads)."""
    B, T, H = x.shape
    with jax.named_scope(f"{scope}/moe/router"):  # ahead of attention, from the input itself
        idx, g = route(p["router"], x.reshape(B * T, H), c, "softmax_of_chosen")
    h = attention(p["attn"], x, p["ln_in"], c, rope, sliding, scope)
    with jax.named_scope(f"{scope}/moe/experts"):
        flat = rms_norm(h, p["ln_post_attn"], c.rms_norm_eps).reshape(B * T, H)
    routed, counts = routed_experts(p["experts"], flat, idx, g, c, scope, "relu")
    with jax.named_scope(f"{scope}/moe/combine"):
        return h + routed.reshape(B, T, H), idx.reshape(B, T, -1), counts


def share_counters(out, c) -> list:
    """``unrouted_tokens`` and ``block_rows`` of one batch, from what
    ``forward`` gave: the (token, layer) pairs none of whose chosen experts is
    held, and the rows of the grouped product's blocks in use (padding
    included), all layers (``sdar`` shares them)."""
    local = out["router_choices"] - c.experts_offset
    held = jnp.any((local >= 0) & (local < c.experts_held), axis=-1)
    R = c.expert_block
    return [jnp.sum(~held).astype(F32), jnp.sum(-(-out["loads"] // R) * R).astype(F32)]


class SmallThinker:
    """``apply(params, emb [B, T, H], ids [B, T]) -> (loss, {"counters": [7]})``;
    ``forward`` gives the logit terms and expert choices behind it."""

    sequence_feed = True  # the step feeds the slot's rows unpooled and takes the loss from here
    counter_names = COUNTERS

    def __init__(self, cfg: SmallThinkerConfig):
        self.cfg = cfg
        self.num_slots = 1
        self.seq_len = cfg.seq_len
        self.dense_dim = cfg.seq_len  # the record's dense slot: its T token ids
        self.feat_width = 3 + cfg.hidden_size

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
        c = self.cfg
        n = len(c.layer_kinds)
        ks = jax.random.split(rng, n + 1)
        return {
            "layers": jax.tree.map(lambda *a: jnp.stack(a), *[self._layer_init(k) for k in ks[:n]]),
            "final_norm": jnp.ones((c.hidden_size,)),
            "head": jax.random.normal(ks[n], (c.hidden_size, c.vocab_size), F32)
            * c.initializer_range,
        }

    # -- forward and loss

    def hidden_states(self, params, emb):
        """emb [B, T, H] -> (the last hidden state before the final norm,
        chosen experts [layers, B, T, k], held loads [layers, held])."""
        c = self.cfg
        rope = rope_tables(emb.shape[1], c.head_dim, c.rope_theta)

        # checkpoints that keep the scores' output and logsumexp, at trace time
        STAT_ADD("model.attn.keep_scores_sites")

        @partial(jax.checkpoint, policy=KEEP_SCORES)
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
        ids = afmoe.feed_ids(emb, ids, self.cfg)
        x, choices, loads = self.hidden_states(params, emb)
        return {**afmoe.window_loss(params, x, ids, self.cfg),
                "router_choices": choices, "loads": loads}

    def apply(self, params, emb, ids):
        """The training loss of one batch (``forward``'s arguments) and the
        one array the step carries out beside it: ``counters``, named by
        ``counter_names`` (``share_counters`` has the last two)."""
        out = self.forward(params, emb, ids)
        with jax.named_scope("loss/head"):
            counters = jnp.stack(afmoe.window_counters(out, emb) + share_counters(out, self.cfg))
        return out["loss"], {"counters": lax.stop_gradient(counters)}

    @staticmethod
    def record_counters(means) -> None:
        """A pass's mean counters into the monitor registry (literal names)."""
        from paddlebox_tpu.utils.monitor import STAT_SET

        afmoe.record_window_counters(means)
        STAT_SET("model.unrouted_tokens_per_step", float(means[5]))
        STAT_SET("model.block_rows_per_step", float(means[6]))
