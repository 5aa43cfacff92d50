"""GLM-4.7-Flash (``glm4_moe_lite``): latent attention, routed experts beside
a shared expert, one multi-token-prediction module; a language model trained
through the pass path. Its attention's scores are ``models/attention.py``'s,
its expert layer ``models/moe.py``'s, its norms, rope, head and counters
``models/lm_layers.py``'s. ``models/xing4.py`` takes each block's branch here,
``mla_branch``, ``dense_branch`` and ``moe_branch``, without the sum that the
layers here put around it.

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
(``jax.checkpoint``), but for the fused scores' float32 output and logsumexp
(``models/attention.py``; 1.01 GB over the cell's six attention layers of two
4k records). The expert layers are one stacked body under ``lax.scan``, the
attention scores never exist whole, and the head's logits exist one block of
positions at a time.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.models.attention import scores
from paddlebox_tpu.models.lm_layers import (
    BF16, F32, TokenConfig, TokenModel, _mm, apply_rope, feed_ids, head_logits,
    record_load_counters, rms_norm, rope_tables, step_counters, swiglu)
from paddlebox_tpu.models.moe import KEEP_EXPERTS, route, routed_experts
from paddlebox_tpu.ops.pallas_kernels import KEEP_SCORES
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_SET

COUNTERS = ("loss_main", "loss_mtp", "tokens", "held_assignments", "expert_load_max_over_mean")


@dataclass(frozen=True)
class GlmMoeLiteConfig(TokenConfig):
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

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


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
        scale = float(dn + dr) ** -0.5 if scale is None else float(scale)
        o = scores(q, k, v, scale=scale, block=c.attn_block, kind="mla")
    with jax.named_scope(f"{scope}/mla/out_proj"):
        return _mm(o.reshape(B, T, nh * dv), p["o"])


def mla(p, x, norm_w, c: GlmMoeLiteConfig, rope, scope: str):
    """x + attention(norm(x)): ``mla_branch`` in a pre-norm residual block."""
    y = mla_branch(p, x, norm_w, c, rope, scope)
    with jax.named_scope(f"{scope}/mla/out_proj"):
        return x + y


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
        idx, g = route(p["router"], flat, c.num_experts_per_tok, scale=c.routed_scaling_factor)
    routed, counts = routed_experts(p["experts"], flat, idx, g, c.experts_held, c.experts_offset,
                                    c.expert_block, scope)
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


# ---- the model ----------------------------------------------------------------


class GlmMoeLite(TokenModel):
    """``apply(params, emb [B, T, H], ids [B, T]) -> (loss, {"counters": [5]})``;
    ``forward`` gives the logit terms and expert choices behind it."""

    counter_names = COUNTERS

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
        STAT_ADD("model.moe.keep_output_sites", 2)  # and the expert output, where a backward reads it
        for p in params["dense"]:
            x = jax.checkpoint(lambda p, x: dense_layer(p, x, c, rope), policy=KEEP_SCORES)(p, x)

        @partial(jax.checkpoint, policy=KEEP_EXPERTS)
        def body(x, p):
            x, idx, counts = moe_layer(p, x, c, rope)
            return x, (idx, counts)

        x, (choices, loads) = lax.scan(body, x, params["moe"])

        @partial(jax.checkpoint, policy=KEEP_EXPERTS)
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
        ids = feed_ids(emb, ids, c.seq_len)
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
            counters = jnp.stack(step_counters(parts, loads, emb.shape[0] * emb.shape[1]))
        return loss, {"counters": lax.stop_gradient(counters)}

    @staticmethod
    def record_counters(means) -> None:
        """A pass's mean counters into the monitor registry (literal names)."""
        STAT_SET("model.loss_main", float(means[0]))
        STAT_SET("model.loss_mtp", float(means[1]))
        record_load_counters(*means[2:5])
