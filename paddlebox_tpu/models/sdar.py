"""SDAR (``sdar_moe``): **block diffusion** over a grouped-query expert model
with QK-norm; a language model trained through the pass path
(``models/base.py::SequenceLossModel``). Its scores are
``models/attention.py``'s, its expert layer ``models/moe.py``'s (the router
in its form ``softmax_of_chosen``, the gate's ``silu``), its norms, rope,
head and counters ``models/lm_layers.py``'s.

What is trained is not next-token prediction. A record is ``seq_len`` = 2 L
keys: the clean tokens c_0 .. c_{L-1}, then their noised copy n_0 .. n_{L-1},
n_i the MASK token where the data job masked position i and c_i elsewhere
(the MASK token's embedding is a table row, so its key is in the record; the
record's dense slot holds the same 2 L ids). Both halves carry the positions
0 .. L - 1, in blocks of ``block_length``. Inside a block the model denoises
with attention in both directions, across blocks it is causal
(``ops/pallas_kernels.py::diffusion_visible``): a clean query sees the clean
keys of its own and the earlier blocks, a noisy query the clean keys of the
earlier blocks and the noisy keys of its own. The head and the loss run over
the noisy half alone: position i predicts c_i (no shift), and the loss is
``(1 / L) sum_i [n_i = MASK] (block_length / m_blk(i)) CE_i``, m_b the masked
positions of block b: the masked-diffusion bound with a linear schedule in its
fixed-count form, the weight read off the ids.

A layer: ``a = norm(x)``; q, k, v from ``a``; RMSNorm over each head's q and
k; rope on both at the position inside the half; query head h attends
key-value head h // group under the mask above; ``x += o W_o``;
``m = norm(x)``; the router's top k logits of ``m W_r``, a softmax over the
chosen; ``x += sum_k w_k E_k(m)`` over the chosen experts held,
``E(m) = (silu(m W_gate) * (m W_up)) W_down``. No shared expert: a token none
of whose chosen experts is held gets no feed-forward output here
(``test_sdar`` adds the shares up to the uncut layer).

Precision: float32 but for the bfloat16 operands of the matrix products.
Memory: every layer recomputed in the backward from its input, but for the
fused scores' float32 output and logsumexp (``models/attention.py``). The
stack is one body under ``lax.scan`` over layers that are all alike; the
scores take the fused kernel with ``diffusion_block`` on a TPU at shapes it
tiles and query blocks elsewhere, counted at trace time under
``model.attn.fused_diffusion_scores`` / ``model.attn.blocked_scores``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.models.attention import scores
from paddlebox_tpu.models.lm_layers import (
    BF16, F32, WINDOW_COUNTERS, GroupedQueryConfig, TokenModel, _mm, apply_rope, feed_ids,
    head_logits, record_load_counters, rms_norm, rope_tables, step_counters)
from paddlebox_tpu.models.moe import (
    KEEP_EXPERTS, SHARE_COUNTERS, record_share_counters, route, routed_experts, share_counters)
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_SET

COUNTERS = (("loss_first_half", "loss_second_half") + WINDOW_COUNTERS[2:] + SHARE_COUNTERS
            + ("masked_positions",))


@dataclass(frozen=True)
class SdarConfig(GroupedQueryConfig):
    """Keys as in the published ``config.json``; ``num_hidden_layers`` and
    ``vocab_size`` are what this instance holds, ``num_experts`` what the
    router scores. ``seq_len`` counts a record's keys: twice the tokens it
    trains."""

    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    moe_intermediate_size: int = 768
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_hidden_layers: int = 4
    vocab_size: int = 18992
    experts_held: int = 16
    experts_offset: int = 0
    seq_len: int = 16384
    block_length: int = 4
    mask_id: int = 18991
    initializer_range: float = 0.02
    attn_block: int = 512  # queries (and, in the fused kernel, keys) a tile of the scores
    loss_block: int = 1024  # positions whose logits exist at once
    expert_block: int = 512  # rows of one grouped product

    def __post_init__(self):
        super().__post_init__()
        if self.seq_len % (2 * self.block_length):
            raise ValueError(f"seq_len {self.seq_len} is not two halves of blocks of {self.block_length}")

    @property
    def data_len(self) -> int:  # L: the tokens a record trains
        return self.seq_len // 2


# ---- attention ------------------------------------------------------------------


def attention(p, x, w_in, c: SdarConfig, rope, scope: str = "model"):
    """x + attention(norm(x)) W_o under the block-diffusion mask. x [B, 2 L,
    H]; ``rope`` the tables of both halves' positions. No biases, no gate.
    Every leaf scope is named in full (see ``glm_moe_lite.mla_branch``)."""
    B, T, _ = x.shape
    nh, nkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope(f"{scope}/attn/qkv_proj"):
        a = rms_norm(x, w_in, c.rms_norm_eps)
        q = _mm(a, p["q"]).reshape(B, T, nh, d)
        k = _mm(a, p["k"]).reshape(B, T, nkv, d)
        v = _mm(a, p["v"]).reshape(B, T, nkv, d)
    with jax.named_scope(f"{scope}/attn/qk_norm_rope"):
        q = apply_rope(rms_norm(q, p["q_norm"], c.rms_norm_eps), *rope).astype(BF16)
        k = apply_rope(rms_norm(k, p["k_norm"], c.rms_norm_eps), *rope).astype(BF16)
        v = v.astype(BF16)
    with jax.named_scope(f"{scope}/attn/scores_diffusion"):
        o = scores(q, k, v, scale=float(d) ** -0.5, block=c.attn_block, group=c.group,
                   diffusion_block=c.block_length, kind="diffusion")
    with jax.named_scope(f"{scope}/attn/out_proj"):
        return x + _mm(o.reshape(B, T, nh * d), p["o"])


def layer(p, x, c: SdarConfig, rope, scope: str = "model"):
    """-> (stream, chosen experts [B, T, k], held experts' loads)."""
    B, T, H = x.shape
    h = attention(p["attn"], x, p["ln_in"], c, rope, scope)
    with jax.named_scope(f"{scope}/moe/router"):
        flat = rms_norm(h, p["ln_post_attn"], c.rms_norm_eps).reshape(B * T, H)
        idx, g = route(p["router"], flat, c.num_experts_per_tok, form="softmax_of_chosen")
    routed, counts = routed_experts(p["experts"], flat, idx, g, c.experts_held, c.experts_offset,
                                    c.expert_block, scope)
    with jax.named_scope(f"{scope}/moe/combine"):
        return h + routed.reshape(B, T, H), idx.reshape(B, T, -1), counts


def diffusion_loss(params, x, ids, c: SdarConfig) -> Dict[str, Any]:
    """The last hidden state x [B, 2 L, H] and the record's ids [B, 2 L]
    (clean, then noised) -> ``parts``, ``token_logits``, ``masked`` and
    ``loss`` as ``Sdar.forward`` describes them: the noisy half through the
    final norm and the head against the clean tokens, weighted by what the
    ids say was masked."""
    B, T, H = x.shape
    L, n = T // 2, c.block_length
    with jax.named_scope("loss/head"):
        h = rms_norm(x[:, L:], params["final_norm"], c.rms_norm_eps)
        tl, lse = head_logits(params["head"], h.reshape(B * L, H), ids[:, :L].reshape(-1),
                              c.loss_block)
        tl, lse = tl.reshape(1, B, L), lse.reshape(1, B, L)
        masked = (ids[:, L:] == c.mask_id).astype(F32).reshape(B, L // n, n)
        weight = masked * (n / jnp.maximum(jnp.sum(masked, axis=-1, keepdims=True), 1.0))
        sums = jnp.sum(((lse - tl)[0] * weight.reshape(B, L)).reshape(B, 2, L // 2), axis=(0, 2))
        parts, loss = sums / (B * (L // 2)), jnp.sum(sums) / (B * L)
    return {"parts": parts, "token_logits": jnp.concatenate([tl, lse]), "loss": loss,
            "masked": jnp.sum(masked)}


# ---- the model ------------------------------------------------------------------


class Sdar(TokenModel):
    """``apply(params, emb [B, 2 L, H], ids [B, 2 L]) -> (loss, {"counters":
    [8]})``; ``forward`` gives the logit terms and expert choices behind it."""

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
                     "o": w(ks[3], nh * d, H), "q_norm": jnp.ones((d,)), "k_norm": jnp.ones((d,))},
            "ln_in": jnp.ones((H,)), "ln_post_attn": jnp.ones((H,)),
            "router": {"w": w(ks[4], H, c.num_experts)},
            "experts": {"gate": w(ks[5], G, H, I), "up": w(ks[6], G, H, I), "down": w(ks[7], G, I, H)},
        }

    def init(self, rng) -> Dict[str, Any]:
        return self._stack_init(rng, self.cfg.num_hidden_layers)

    # -- forward and loss

    def hidden_states(self, params, emb):
        """emb [B, 2 L, H] -> (the last hidden state before the final norm,
        chosen experts [layers, B, 2 L, k], held loads [layers, held])."""
        c = self.cfg
        # both halves carry the positions 0 .. L - 1
        rope = tuple(jnp.tile(t, (2, 1)) for t in rope_tables(c.data_len, c.head_dim, c.rope_theta))

        # checkpoints that keep the scores' output and logsumexp, at trace time
        STAT_ADD("model.attn.keep_scores_sites")
        STAT_ADD("model.moe.keep_output_sites")  # and the expert output, where a backward reads it

        @partial(jax.checkpoint, policy=KEEP_EXPERTS)
        def body(x, p):
            x, idx, counts = layer(p, x, c, rope)
            return x, (idx, counts)

        x, (choices, loads) = lax.scan(body, emb.astype(F32), params["layers"])
        return x, choices, loads

    def forward(self, params, emb, ids):
        """What one batch gives: ``parts`` [2] (the loss's share of the target
        positions i < L / 2 and of those from L / 2 on, each over its L / 2
        positions: their mean is the loss), ``token_logits`` [2, B, L] (the
        target's logit, then the logsumexp of all logits, of every noisy
        position, masked or not), ``router_choices`` [layers, B, 2 L, k], the
        held experts' ``loads`` [layers, held], ``masked`` (the positions that
        carry loss) and the ``loss``. emb [B, 2 L, H]: the token slot's pulled
        rows, CVM columns dropped; ids [B, 2 L]: the record's ids, clean then
        noised (whole numbers in float32 or int32), relative to the held
        slice."""
        ids = feed_ids(emb, ids, self.cfg.seq_len)
        x, choices, loads = self.hidden_states(params, emb)
        return {**diffusion_loss(params, x, ids, self.cfg), "router_choices": choices, "loads": loads}

    def apply(self, params, emb, ids):
        """The training loss of one batch (``forward``'s arguments) and the
        one array the step carries out beside it: ``counters``, named by
        ``counter_names`` (``tokens`` counts a record's 2 L rows,
        ``masked_positions`` those of them that carry loss)."""
        c = self.cfg
        out = self.forward(params, emb, ids)
        with jax.named_scope("loss/head"):
            counters = jnp.stack(
                step_counters(out["parts"], out["loads"].astype(F32), emb.shape[0] * emb.shape[1])
                + share_counters(out["router_choices"], out["loads"], c.experts_held,
                                 c.experts_offset, c.expert_block) + [out["masked"]])
        return out["loss"], {"counters": lax.stop_gradient(counters)}

    @staticmethod
    def record_counters(means) -> None:
        """A pass's mean counters into the monitor registry (literal names)."""
        STAT_SET("model.loss_first_half", float(means[0]))
        STAT_SET("model.loss_second_half", float(means[1]))
        record_load_counters(*means[2:5])
        record_share_counters(*means[5:7])
        STAT_SET("model.masked_positions_per_step", float(means[7]))
