"""Trinity (``afmoe``): grouped-query attention with QK-norm and an output
gate, window layers with rope beside full layers without positions in one
stack, routed experts beside a shared one; a language model trained through
the pass path, the second ``SequenceLossModel`` (``models/base.py``) beside
``models/glm_moe_lite.py``, whose pieces it shares (``rms_norm``, rope,
``_mm``, ``swiglu``, ``route``, ``routed_experts``, ``head_logits``: an
optimisation of one is measured on both, and on ``models/smallthinker.py``
and ``models/sdar.py``, the third and fourth, which share pieces of this one).

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

Precision as ``glm_moe_lite``: float32 but for the bfloat16 operands of the
matrix products. Memory: every layer recomputed in the backward from its
input, but for the fused scores' float32 output and logsumexp, which each
layer's checkpoint keeps by name (``ops/pallas_kernels.py::KEEP_SCORES``; 0.67
GB over the cell's five layers of one 8k record): q, k, v are the
recomputation's anyway, so the backward kernel is fed without the forward
kernel's second run. The expert layers are one stacked body under
``lax.scan`` **whose step is told its kind** (a traced flag: ``lax.cond``
picks rope and window or neither, so both kinds compile once whatever their
order); the scores take the fused kernel
(``ops/pallas_kernels.py::causal_attention`` with ``group`` and ``window``) on
a TPU at shapes it tiles and query blocks against their visible keys
(``_attend_block``) everywhere else, chosen and counted at trace time
(``fused_scores``; ``model.attn.fused_window_scores`` /
``model.attn.fused_full_scores`` / ``model.attn.blocked_scores``).
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.models.glm_moe_lite import (
    BF16, F32, _mm, _product, apply_rope, head_logits, rms_norm, rope_tables, route,
    routed_experts, swiglu)
from paddlebox_tpu.ops.pallas_kernels import KEEP_SCORES, LANE, causal_attention
from paddlebox_tpu.utils.monitor import STAT_ADD

SLIDING, FULL = "sliding_attention", "full_attention"
COUNTERS = ("loss_in_window", "loss_past_window", "tokens", "held_assignments",
            "expert_load_max_over_mean")


@dataclass(frozen=True)
class AfmoeConfig:
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
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads are not a multiple of the key-value heads")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "AfmoeConfig":
        names = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @property
    def routed_scaling_factor(self) -> float:  # the name ``glm_moe_lite.route`` reads
        return self.route_scale

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads


# ---- attention ------------------------------------------------------------------


@partial(jax.checkpoint, static_argnums=(3, 4, 5, 6, 7))
def _attend_block(q, k, v, q0: int, n_q: int, scale: float, group: int, window: Optional[int]):
    """Queries q0 .. q0 + n_q against the keys they may see: their causal
    prefix, with a window its last ``window`` keys. q [B, T, H, D], k and v
    [B, T, H / group, D], whole (see ``glm_moe_lite._attend_block``). A
    group's query heads are folded into the query axis: the two products are
    then those of equal head counts."""
    B, _, nh, d = q.shape
    k0 = 0 if window is None else max(0, q0 - window + 1)
    q, k, v = q[:, q0:q0 + n_q], k[:, k0:q0 + n_q], v[:, k0:q0 + n_q]
    q = q.reshape(B, n_q, nh // group, group, d).transpose(0, 3, 1, 2, 4).reshape(
        B, group * n_q, nh // group, d)
    s = _product("bqhd,bkhd->bhqk")(q, k) * scale
    qi = q0 + jnp.tile(jnp.arange(n_q), group)[:, None]
    kj = k0 + jnp.arange(k.shape[1])[None, :]
    seen = kj <= qi if window is None else (kj <= qi) & (qi - kj < window)
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    o = _product("bhqk,bkhd->bqhd")(p, v)
    return o.reshape(B, group, n_q, nh // group, d).transpose(0, 2, 3, 1, 4).reshape(B, n_q, nh, d)


def fused_scores(backend: str, T: int, head_dim: int, block: int, window: Optional[int]) -> bool:
    """Whether a call site of ``attention`` takes the fused kernel: on a TPU,
    at shapes the kernel tiles. Everything else runs the blocked form."""
    return (backend == "tpu" and head_dim % LANE == 0 and block % LANE == 0 and T % block == 0
            and (window is None or window >= T or window % block == 0))


def _scores(q, k, v, c: AfmoeConfig, rope, sliding: bool, scope: str):
    """One kind's part of the block: rope (sliding layers alone), the casts,
    the scores. q [B, T, H, D], k, v [B, T, H / group, D] float32."""
    T = q.shape[1]
    with jax.named_scope(f"{scope}/attn/qk_norm_rope"):
        if sliding:
            q, k = apply_rope(q, *rope), apply_rope(k, *rope)
        q, k, v = q.astype(BF16), k.astype(BF16), v.astype(BF16)
    window = c.sliding_window if sliding and c.sliding_window < T else None
    with jax.named_scope(f"{scope}/attn/scores_window" if sliding else f"{scope}/attn/scores_full"):
        Q = min(c.attn_block, T)
        if T % Q:
            raise ValueError(f"seq_len {T} is not a multiple of attn_block {Q}")
        scale = float(c.head_dim) ** -0.5
        if fused_scores(jax.default_backend(), T, c.head_dim, Q, window):
            # call sites lowered each way, at trace time
            if sliding:
                STAT_ADD("model.attn.fused_window_scores")
            else:
                STAT_ADD("model.attn.fused_full_scores")
            return causal_attention(q, k, v, scale, Q, False, c.group, window)
        STAT_ADD("model.attn.blocked_scores")
        return jnp.concatenate(
            [_attend_block(q, k, v, i, Q, scale, c.group, window) for i in range(0, T, Q)], axis=1)


def attention(p, x, w_in, w_post, c: AfmoeConfig, rope, sliding, scope: str = "model"):
    """x + norm(attention(norm(x))). x [B, T, H]. ``sliding`` is the layer's
    kind: a bool, or a traced flag where a scan's step is told it. Every leaf
    scope is named in full (see ``glm_moe_lite.mla``)."""
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
    window, full = (partial(_scores, c=c, rope=rope, sliding=s, scope=scope) for s in (True, False))
    if isinstance(sliding, bool):
        o = (window if sliding else full)(q, k, v)
    else:
        o = lax.cond(sliding, window, full, q, k, v)
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
        idx, g = route(p["router"], flat, c)
    routed, counts = routed_experts(p["experts"], flat, idx, g, c, scope)
    with jax.named_scope(f"{scope}/moe/shared"):
        y = (swiglu(p["shared"], flat) + routed).reshape(B, T, H)
        out = h + rms_norm(y, p["ln_post_mlp"], c.rms_norm_eps)
    return out, idx.reshape(B, T, -1), counts


# ---- the loss and the counters of a window-and-full model (``smallthinker`` shares them) ----


def feed_ids(emb, ids, c):
    """The record's token ids as int32, once the feed fits the model."""
    B, T, _ = emb.shape
    if T != c.seq_len or ids.shape != (B, T):
        raise ValueError(f"sequence feed of {emb.shape} / {ids.shape}, seq_len {c.seq_len}")
    return ids.astype(jnp.int32)


def window_loss(params, x, ids, c) -> Dict[str, Any]:
    """The last hidden state x [B, T, H] through the final norm and the head
    (``params["final_norm"]``, ``params["head"]``) against the next token:
    ``parts`` [2], ``token_logits`` [2, B, T] and ``loss`` as ``Afmoe.forward``
    describes them."""
    B, T, H = x.shape
    with jax.named_scope("loss/head"):
        pos = jnp.arange(T)
        targets = jnp.concatenate([ids[:, 1:], jnp.zeros((B, 1), jnp.int32)], axis=1)
        h = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        tl, lse = head_logits(params["head"], h.reshape(B * T, H), targets.reshape(-1),
                              c.loss_block)
        tl, lse = tl.reshape(1, B, T), lse.reshape(1, B, T)
        has = pos < T - 1
        mask = jnp.stack([has & (pos < c.sliding_window),
                          has & (pos >= c.sliding_window)]).astype(F32)[:, None, :]
        sums = jnp.sum((lse - tl) * mask, axis=(1, 2))
        parts = sums / jnp.maximum(B * jnp.sum(mask, axis=(1, 2)), 1.0)
        loss = jnp.sum(sums) / (B * (T - 1))
    return {"parts": parts, "token_logits": jnp.concatenate([tl, lse]), "loss": loss}


def window_counters(out, emb) -> list:
    """``COUNTERS`` of one batch, from what ``forward`` gave."""
    parts, loads = out["parts"], out["loads"].astype(F32)
    return [parts[0], parts[1], jnp.asarray(float(emb.shape[0] * emb.shape[1])),
            jnp.sum(loads), jnp.max(loads) / jnp.maximum(jnp.mean(loads), 1e-9)]


def record_window_counters(means) -> None:
    """A pass's mean ``COUNTERS`` into the monitor registry (literal names)."""
    from paddlebox_tpu.utils.monitor import STAT_SET

    STAT_SET("model.loss_in_window", float(means[0]))
    STAT_SET("model.loss_past_window", float(means[1]))
    STAT_SET("model.tokens_per_step", float(means[2]))
    STAT_SET("model.held_assignments_per_step", float(means[3]))
    STAT_SET("model.expert_load_max_over_mean", float(means[4]))


# ---- the model ------------------------------------------------------------------


class Afmoe:
    """``apply(params, emb [B, T, H], ids [B, T]) -> (loss, {"counters": [5]})``;
    ``forward`` gives the logit terms and expert choices behind it."""

    sequence_feed = True  # the step feeds the slot's rows unpooled and takes the loss from here
    counter_names = COUNTERS

    def __init__(self, cfg: AfmoeConfig):
        self.cfg = cfg
        self.num_slots = 1
        self.seq_len = cfg.seq_len
        self.dense_dim = cfg.seq_len  # the record's dense slot: its T token ids
        self.feat_width = 3 + cfg.hidden_size

    # -- parameters

    def _attn_init(self, key):
        c = self.cfg
        ks = jax.random.split(key, 5)
        H, nh, nkv, d = c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim
        w = lambda k, *s: jax.random.normal(k, s, F32) * c.initializer_range  # noqa: E731
        return {"q": w(ks[0], H, nh * d), "k": w(ks[1], H, nkv * d), "v": w(ks[2], H, nkv * d),
                "gate": w(ks[3], H, nh * d), "o": w(ks[4], nh * d, H),
                "q_norm": jnp.ones((d,)), "k_norm": jnp.ones((d,))}

    def _mlp_init(self, key, width, lead=()):
        c = self.cfg
        ks = jax.random.split(key, 3)
        w = lambda k, *s: jax.random.normal(k, lead + s, F32) * c.initializer_range  # noqa: E731
        return {"gate": w(ks[0], c.hidden_size, width), "up": w(ks[1], c.hidden_size, width),
                "down": w(ks[2], width, c.hidden_size)}

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

        @partial(jax.checkpoint, policy=KEEP_SCORES)
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
        ids = feed_ids(emb, ids, self.cfg)
        x, choices, loads = self.hidden_states(params, emb)
        return {**window_loss(params, x, ids, self.cfg), "router_choices": choices, "loads": loads}

    def apply(self, params, emb, ids):
        """The training loss of one batch (``forward``'s arguments) and the
        one array the step carries out beside it: ``counters``, named by
        ``counter_names``."""
        out = self.forward(params, emb, ids)
        with jax.named_scope("loss/head"):
            counters = jnp.stack(window_counters(out, emb))
        return out["loss"], {"counters": lax.stop_gradient(counters)}

    record_counters = staticmethod(record_window_counters)
