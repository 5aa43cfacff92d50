"""Xing4.0 (``xing4_0``): manifold-constrained hyper-connections (mHC,
DeepSeek-AI, arXiv:2512.24880, over Hyper-Connections, arXiv:2409.19606)
around the DeepSeek-V3 family's latent attention (query/key heads of 192, value
heads of 128, rope under YaRN) and its sigmoid-routed experts beside a shared
one; a language model trained through the pass path
(``models/base.py::SequenceLossModel``). Every branch is
``models/glm_moe_lite.py``'s (``mla_branch``, ``dense_branch``, ``moe_branch``),
the rope tables, head and counters ``models/lm_layers.py``'s. What is this
model's own is the residual path: **no layer adds its output to its input**.

The state is ``hc_mult`` = n streams, X a tuple of n arrays [B, T, C] float32
(one buffer a stream: no stream is ever sliced out of, or stacked into, a
larger array, and the two minor dimensions are T and C, whole tiles, where
[B, T, n, C] would pad n = 4 to the 8 sublanes and double the state); every
stream starts as the token's row and the head reads their sum. A hyper-connection around a
branch F, with its own ``phi`` [n C, 2 n + n^2], ``b`` [2 n + n^2], ``alpha``
[3], twice a layer (around attention, around the feed-forward):

1. *maps*: ``u_t = vec(X[t])`` (stream-major); ``z_t = (u_t / sqrt(mean(u_t^2) + eps))
   phi`` (computed as ``(u_t phi) / sqrt(..)``: the token's scalar commutes
   with the product, and the normalised copy of the state never exists);
   ``H_pre = sigmoid(alpha_0 z_pre + b_pre)``, ``H_post = 2 sigmoid(alpha_1
   z_post + b_post)``, ``M = exp(clip(alpha_2 Z_res + B_res, min, max))``, then
   ``hc_sinkhorn_iters`` times columns, then rows, divided by their sums +
   eps: ``H_res``, doubly stochastic within the last round's residual. The
   maps live as [n, n, B T]: a token is a lane, the 4 x 4 matrix is 16 rows.
2. *pre*: ``h_t = sum_i H_pre[i, t] X[i, t]``.
3. the branch: ``y = F(h)``.
4. *post_res*: ``X'[i, t] = sum_j H_res[i, j, t] X[j, t] + H_post[i, t] y_t``.

One instance is one chip's share of an expert-parallel group, as
``glm_moe_lite``'s. Precision as there: float32 but for the bfloat16 operands
of the branches' matrix products; the maps' product is float32 at ``highest``
(as the router's), Sinkhorn float32. Memory: every layer is recomputed in the
backward from its input X (``jax.checkpoint`` with ``KEEP_SCORES``: the fused
scores' output and logsumexp are kept), the expert layers one ``lax.scan``
body whose checkpoint keeps the grouped product's output as well
(``models/moe.py::KEEP_EXPERTS``: post_res's ``H_post`` gradient reads it, so
the backward runs the blocks' pass once).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.models.glm_moe_lite import (
    GlmMoeLite, GlmMoeLiteConfig, dense_branch, mla_branch, moe_branch)
from paddlebox_tpu.models.lm_layers import (
    F32, feed_ids, load_counters, next_token_logits, record_load_counters, yarn_mscale,
    yarn_rope_tables)
from paddlebox_tpu.models.moe import KEEP_EXPERTS
from paddlebox_tpu.ops.pallas_kernels import KEEP_SCORES
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_SET

COUNTERS = ("loss_main", "tokens", "held_assignments", "expert_load_max_over_mean",
            "hc_res_offdiag", "hc_sinkhorn_residual")


@dataclass(frozen=True)
class Xing4Config(GlmMoeLiteConfig):
    """Keys as in the published ``config.json`` (``rope_scaling``'s flattened:
    ``from_dict`` takes the nested group); ``num_hidden_layers`` and
    ``vocab_size`` are what this instance holds. The latent attention's and the
    experts' keys are ``GlmMoeLiteConfig``'s, with this model's numbers."""

    hidden_size: int = 3584
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-6
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.0
    num_nextn_predict_layers: int = 0  # the MTP module lies on the last pipeline stage
    vocab_size: int = 16384
    # rope_scaling (YaRN)
    rope_factor: float = 64.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    # the hyper-connections
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    hc_alpha_init: float = 0.1  # the seed's alpha, all three
    hc_res_diag_init: float = 2.0  # the seed's B_res on the diagonal (0 off it)

    def __post_init__(self):
        if self.num_nextn_predict_layers:
            raise ValueError("no MTP module here: num_nextn_predict_layers is 0")
        if yarn_mscale(self.rope_factor, self.mscale) != yarn_mscale(
                self.rope_factor, self.mscale_all_dim):
            raise ValueError("mscale and mscale_all_dim differ: the rope tables would take a factor")

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "Xing4Config":
        rs = d.get("rope_scaling") or {}
        if rs and rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling {rs.get('type')!r}")
        flat = {"rope_factor": "factor", "rope_original": "original_max_position_embeddings",
                "beta_fast": "beta_fast", "beta_slow": "beta_slow", "mscale": "mscale",
                "mscale_all_dim": "mscale_all_dim"}
        return super().from_dict({**d, **{k: rs[v] for k, v in flat.items() if v in rs}})

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope) ** -0.5`` times the square of YaRN's factor at ``mscale_all_dim``."""
        return self.qk_head_dim ** -0.5 * yarn_mscale(self.rope_factor, self.mscale_all_dim) ** 2


# ---- a hyper-connection ---------------------------------------------------------


def sinkhorn(M, iters: int, eps: float):
    """M [n, n, N] positive -> (columns then rows divided by their sums + eps,
    ``iters`` times; the largest |column sum - 1| left, [N])."""
    def one(_, M):
        M = M / (jnp.sum(M, axis=0, keepdims=True) + eps)
        return M / (jnp.sum(M, axis=1, keepdims=True) + eps)

    M = lax.fori_loop(0, iters, one, M)
    return M, jnp.max(jnp.abs(jnp.sum(M, axis=0) - 1.0), axis=0)


def hc_maps(p, X, c: Xing4Config, scope: str):
    """X: n streams [B, T, C] -> (H_pre [n, N], H_post [n, N], H_res [n, n, N],
    the Sinkhorn residual [N]), N = B T tokens, float32."""
    n, (B, T, C) = len(X), X[0].shape
    with jax.named_scope(f"{scope}/maps"):
        phi = p["phi"].reshape(n, C, -1)
        z = sum(jnp.dot(x.reshape(B * T, C), phi[i], precision=lax.Precision.HIGHEST)
                for i, x in enumerate(X))  # [N, 2 n + n^2]: u_t phi, stream-major
        ms = sum(jnp.sum(x * x, axis=-1) for x in X).reshape(B * T) / (n * C)
        z = (z * lax.rsqrt(ms + c.hc_eps)[:, None]).T  # a token a lane
        a, b = p["alpha"], p["b"][:, None]
        pre = jax.nn.sigmoid(a[0] * z[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * z[n:2 * n] + b[n:2 * n])
        res = jnp.exp(jnp.clip(a[2] * z[2 * n:] + b[2 * n:], c.mhc_h_res_clamp_min,
                               c.mhc_h_res_clamp_max)).reshape(n, n, B * T)
        res, left = sinkhorn(res, c.hc_sinkhorn_iters, c.hc_eps)
    return pre, post, res, left


def hyper_connection(p, X, branch, c: Xing4Config, scope: str):
    """One sublayer: X, n streams [B, T, C] -> (X', what ``branch`` gave beside
    its output, [mean of 1 - trace(H_res) / n, largest Sinkhorn residual]).
    ``branch``: h [B, T, C] -> (y [B, T, C], aux)."""
    n, (B, T, C) = len(X), X[0].shape
    STAT_ADD("model.hc.sublayers")  # at trace time
    pre, post, res, left = hc_maps(p, X, c, scope)
    per_token = lambda m: m.reshape(B, T, 1)  # noqa: E731
    with jax.named_scope(f"{scope}/pre"):
        h = sum(per_token(pre[i]) * X[i] for i in range(n))
    y, aux = branch(h)
    with jax.named_scope(f"{scope}/post_res"):
        out = tuple(sum(per_token(res[i, j]) * X[j] for j in range(n)) + per_token(post[i]) * y
                    for i in range(n))
        offdiag = 1.0 - jnp.mean(sum(res[i, i] for i in range(n))) / n
    return out, aux, lax.stop_gradient(jnp.stack([offdiag, jnp.max(left)]))


def _attention(p, X, c: Xing4Config, rope, scope: str):
    """The hyper-connection around latent attention: the first sublayer of either kind of layer."""
    branch = lambda h: (mla_branch(p["attn"], h, p["ln1"], c, rope, scope, c.softmax_scale), None)  # noqa: E731
    X, _, reading = hyper_connection(p["hc_attn"], X, branch, c, f"{scope}/hc_attn")
    return X, reading


def dense_layer(p, X, c: Xing4Config, rope, scope: str = "model"):
    """-> (streams, the two sublayers' hyper-connection readings [2, 2])."""
    X, s0 = _attention(p, X, c, rope, scope)
    X, _, s1 = hyper_connection(p["hc_mlp"], X, lambda h: (dense_branch(p, h, c, scope), None), c,
                                f"{scope}/hc_mlp")
    return X, jnp.stack([s0, s1])


def moe_layer(p, X, c: Xing4Config, rope, scope: str = "model"):
    """-> (streams, chosen experts [B, T, k], held experts' loads, readings [2, 2])."""
    def experts(h):
        y, idx, counts = moe_branch(p, h, c, scope)
        return y, (idx.reshape(*h.shape[:2], -1), counts)

    X, s0 = _attention(p, X, c, rope, scope)
    X, (idx, counts), s1 = hyper_connection(p["hc_mlp"], X, experts, c, f"{scope}/hc_mlp")
    return X, idx, counts, jnp.stack([s0, s1])


# ---- the model ------------------------------------------------------------------


class Xing4(GlmMoeLite):
    """``apply(params, emb [B, T, C], ids [B, T]) -> (loss, {"counters": [6]})``;
    ``forward`` gives the logit terms and expert choices behind it. The
    branches' parameters are ``GlmMoeLite``'s; a layer adds ``hc_attn`` and
    ``hc_mlp``, and there is no MTP module."""

    counter_names = COUNTERS

    # -- parameters

    def _hc_init(self, key):
        c = self.cfg
        n = c.hc_mult
        b_res = c.hc_res_diag_init * jnp.eye(n, dtype=F32).reshape(-1)
        return {"phi": jax.random.normal(key, (n * c.hidden_size, 2 * n + n * n), F32)
                * c.initializer_range,
                "b": jnp.concatenate([jnp.zeros((2 * n,), F32), b_res]),
                "alpha": jnp.full((3,), c.hc_alpha_init, F32)}

    def _layer_init(self, key, moe: bool):
        ks = jax.random.split(key, 3)
        return {**super()._layer_init(ks[0], moe), "hc_attn": self._hc_init(ks[1]),
                "hc_mlp": self._hc_init(ks[2])}

    def init(self, rng) -> Dict[str, Any]:
        c = self.cfg
        ks = jax.random.split(rng, c.num_hidden_layers + 1)
        first = c.first_k_dense_replace
        moe = [self._layer_init(k, True) for k in ks[first:c.num_hidden_layers]]
        return {
            "dense": [self._layer_init(k, False) for k in ks[:first]],
            "moe": jax.tree.map(lambda *a: jnp.stack(a), *moe),
            "final_norm": jnp.ones((c.hidden_size,)),
            "head": jax.random.normal(ks[-1], (c.hidden_size, c.vocab_size), F32)
            * c.initializer_range,
        }

    # -- forward and loss

    def hidden_states(self, params, emb):
        """emb [B, T, C] -> (the streams' sum before the final norm, chosen
        experts [expert layers, B, T, k], held loads [expert layers, held],
        the sublayers' hyper-connection readings [sublayers, 2])."""
        c = self.cfg
        rope = yarn_rope_tables(emb.shape[1], c.qk_rope_head_dim, c.rope_theta, c.rope_factor,
                                c.rope_original, c.beta_fast, c.beta_slow)
        X = (emb.astype(F32),) * c.hc_mult
        # checkpoints that keep the scores' output and logsumexp, at trace time
        STAT_ADD("model.mla.keep_scores_sites", len(params["dense"]) + 1)
        STAT_ADD("model.moe.keep_output_sites")  # and the expert output: post_res's map reads it
        readings = []
        for p in params["dense"]:
            X, s = jax.checkpoint(lambda p, X: dense_layer(p, X, c, rope), policy=KEEP_SCORES)(p, X)
            readings.append(s)

        @partial(jax.checkpoint, policy=KEEP_EXPERTS)
        def body(X, p):
            X, idx, counts, s = moe_layer(p, X, c, rope)
            return X, (idx, counts, s)

        X, (choices, loads, s) = lax.scan(body, X, params["moe"])
        with jax.named_scope("model/hc_out"):
            x = sum(X)
        return x, choices, loads, jnp.concatenate(readings + [s.reshape(-1, 2)])

    def forward(self, params, emb, ids):
        """What one batch gives: ``parts`` [2] (the next-token cross-entropy,
        a mean over the T - 1 positions that have a target, and the batch's
        tokens: the first two counters, as the token driver reads every
        model's), ``token_logits`` [2, B, T] (the target's logit, then the
        logsumexp of all logits), ``router_choices`` [expert layers, B, T, k],
        the held experts' ``loads`` [expert layers, held], ``hc`` [2] (the mean
        of 1 - trace(H_res) / n over tokens and sublayers, the largest
        |column sum - 1| after the last Sinkhorn round) and the ``loss``. emb
        [B, T, C]: the token slot's pulled rows, CVM columns dropped; ids
        [B, T]: the record's token ids (whole numbers in float32 or int32),
        relative to the held slice."""
        c = self.cfg
        B, T, _ = emb.shape
        ids = feed_ids(emb, ids, c.seq_len)
        x, choices, loads, readings = self.hidden_states(params, emb)
        with jax.named_scope("loss/head"):
            tl, lse = next_token_logits(params, x, ids, c.rms_norm_eps, c.loss_block)
            has = (jnp.arange(T) < T - 1).astype(F32)
            loss = jnp.sum((lse - tl) * has) / (B * (T - 1))
        return {"parts": jnp.stack([loss, jnp.asarray(float(B * T))]),
                "token_logits": jnp.concatenate([tl, lse]), "router_choices": choices,
                "loads": loads, "loss": loss,
                "hc": jnp.stack([jnp.mean(readings[:, 0]), jnp.max(readings[:, 1])])}

    def apply(self, params, emb, ids):
        """The training loss of one batch (``forward``'s arguments) and the
        one array the step carries out beside it: ``counters``, named by
        ``counter_names``."""
        out = self.forward(params, emb, ids)
        with jax.named_scope("loss/head"):
            counters = jnp.concatenate([
                out["parts"], jnp.stack(load_counters(out["loads"].astype(F32))), out["hc"]])
        return out["loss"], {"counters": lax.stop_gradient(counters)}

    @staticmethod
    def record_counters(means) -> None:
        """A pass's mean counters into the monitor registry (literal names)."""
        STAT_SET("model.loss_main", float(means[0]))
        record_load_counters(*means[1:4])
        STAT_SET("model.hc_res_offdiag", float(means[4]))
        STAT_SET("model.hc_sinkhorn_residual", float(means[5]))
