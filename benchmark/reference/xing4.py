"""Xing4.0 (``xing4_0``), plain: written from the published config's keys, the
equations of DeepSeek-V2/V3 that its branches follow (latent attention, YaRN,
sigmoid-routed experts beside a shared one) and those of manifold-constrained
hyper-connections (mHC, arXiv:2512.24880, over Hyper-Connections,
arXiv:2409.19606) for its residual path; nothing of ``paddlebox_tpu`` is
imported. The plain helpers of ``reference/glm_moe_lite.py`` serve where they
take these widths (the two precisions ``_Math``, ``glu``, ``experts_part``,
``head_terms`` and the feed-forwards' seeded weights).

The state is X [B, T, n, C] float32, n = ``hc_mult`` streams, every stream the
token's row at the input. No layer adds its output to its input. A
hyper-connection around a branch F (``phi`` [n C, 2 n + n^2], ``b``, ``alpha``
[3]), around attention and around the feed-forward of every layer:

- ``u_t = vec(X[t])`` (stream-major); ``z_t = (u_t / sqrt(mean(u_t^2) + hc_eps))
  phi`` at ``highest``; ``H_pre = sigmoid(alpha_0 z_pre + b_pre)``; ``H_post = 2
  sigmoid(alpha_1 z_post + b_post)``; ``M = exp(clip(alpha_2 Z_res + B_res,
  mhc_h_res_clamp_min, mhc_h_res_clamp_max))`` (n x n, row-major), then
  ``hc_sinkhorn_iters`` rounds of: every column over its sum + hc_eps, every row
  over its sum + hc_eps (a Python loop); ``H_res = M``.
- ``h_t = sum_i H_pre[t, i] X[t, i]``; ``y = F(h)``;
  ``X'[t, i] = sum_j H_res[t, i, j] X[t, j] + H_post[t, i] y_t``.

Branches: latent attention as ``glm_moe_lite``'s at this model's widths (32
heads, nope / rope / v 128 / 64 / 128), its rope frequencies YaRN's (``f_i / factor``
below ``beta_slow`` turns over the original length, ``f_i`` above ``beta_fast``,
a linear ramp over the pair index between) and its softmax scale ``192 ** -0.5
* (0.1 ln(factor) mscale_all_dim + 1) ** 2``; SwiGLU 9,216 wide in the dense
layer; in an expert layer the shared expert plus the held experts' weighted
outputs (``experts_part``: sigmoid scores, top k of score + bias, weights
renormalised times ``routed_scaling_factor``). Output: ``RMSNorm(sum_i X[t, i])``,
the untied head, float32 next-token cross-entropy.

Departures from the published model, each also a line of the configuration's
``assumed``: no MTP module (``num_nextn_predict_layers`` 0: it lies on the last
pipeline stage); the streams start as copies of the token's row and end as
their sum; columns then rows in a Sinkhorn round, ``hc_eps`` in both
denominators and in the maps' norm, the clamp before ``exp``; the maps' norm
has no gain; seeded maps (``phi`` normal x ``initializer_range``, ``alpha``
``hc_alpha_init``, ``b_pre`` = ``b_post`` = 0, ``B_res`` ``hc_res_diag_init`` on
the diagonal); the router's correction bias a seeded buffer; rope pairs the
halves; no cross-document mask.

Two planted faults for the controls (``benchmark/control_xing4.py``), absent
unless the configuration handed in names them: ``hc_sinkhorn_iters`` set to 2,
and ``yarn_scale_left_out`` (the softmax scale ``192 ** -0.5``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm_moe_lite as glm

HI = jax.lax.Precision.HIGHEST
_Math = glm._Math


def _hc_init(key, c, lead=()):
    n, C = c["hc_mult"], c["hidden_size"]
    b = jnp.concatenate([jnp.zeros((2 * n,)), c["hc_res_diag_init"] * jnp.eye(n).reshape(-1)])
    return {"phi": glm._normal(key, lead + (n * C, 2 * n + n * n), c["initializer_range"]),
            "b": jnp.broadcast_to(b, lead + b.shape),
            "alpha": jnp.full(lead + (3,), c["hc_alpha_init"], jnp.float32)}


def _layers_init(key, c, moe: bool, lead=()):
    """One layer's leaves, or with ``lead`` = (layers,) a stack's, every leaf
    drawn whole: one draw a leaf, not one a layer and leaf (the seed's 700M
    normals compile in a third of the time)."""
    H, nh, std = c["hidden_size"], c["num_attention_heads"], c["initializer_range"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    k = jax.random.split(key, 12)
    w = lambda i, *shape: glm._normal(k[i], lead + shape, std)  # noqa: E731
    ones = lambda *shape: jnp.ones(lead + shape)  # noqa: E731
    p = {"attn": {"q_a": w(0, H, c["q_lora_rank"]), "q_a_norm": ones(c["q_lora_rank"]),
                  "q_b": w(1, c["q_lora_rank"], nh * (dn + dr)),
                  "kv_a": w(2, H, c["kv_lora_rank"] + dr), "kv_a_norm": ones(c["kv_lora_rank"]),
                  "kv_b": w(3, c["kv_lora_rank"], nh * (dn + dv)), "o": w(4, nh * dv, H)},
         "ln1": ones(H), "ln2": ones(H),
         "hc_attn": _hc_init(k[5], c, lead), "hc_mlp": _hc_init(k[6], c, lead)}
    if not moe:
        return {**p, "mlp": glm._glu_init(k[7], c, c["intermediate_size"], lead)}
    return {**p, "router": {"w": w(8, H, c["router_experts"]), "bias": w(9, c["router_experts"])},
            "shared": glm._glu_init(k[10], c, c["moe_intermediate_size"], lead),
            "experts": glm._glu_init(k[11], c, c["moe_intermediate_size"],
                                     lead + (c["n_routed_experts"],))}


def init(key, cfg: dict, feat_width: int) -> dict:
    """Dense leaves from the seed: normal(0, initializer_range) matrices, norms
    of ones, the router's correction bias a seeded buffer, the maps as above."""
    H = cfg["hidden_size"]
    if feat_width != 3 + H:
        raise ValueError(f"the token rows' embedx is the hidden size: {feat_width} != 3 + {H}")
    n, first = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    k = jax.random.split(key, first + 2)
    return {
        "dense": [_layers_init(k[i], cfg, False) for i in range(first)],
        "moe": _layers_init(k[first], cfg, True, lead=(n - first,)),
        "final_norm": jnp.ones((H,)),
        "head": glm._normal(k[first + 1], (H, cfg["vocab_size"]), cfg["initializer_range"]),
    }


# ---- YaRN -----------------------------------------------------------------------


def yarn_inv_freq(c: dict) -> np.ndarray:
    """The rope dims' frequencies [d / 2], float64 on the host."""
    d, base, rs = c["qk_rope_head_dim"], float(c["rope_theta"]), c["rope_scaling"]
    f = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def dim_of(turns):  # the pair index whose frequency turns this often over the original length
        return d * math.log(rs["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return f / rs["factor"] * ramp + f * (1.0 - ramp)


def softmax_scale(c: dict) -> float:
    rs = c["rope_scaling"]
    m = 1.0 if c.get("yarn_scale_left_out") else 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, inv_freq):
    """x [..., T, d]: rotate the pairs (i, i + d/2) by position * inv_freq[i]."""
    T, d = x.shape[-2], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    lo, hi = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def attention(p, x, c, m: _Math):
    """x [B, T, H], normed -> the attention branch's output, heads looped."""
    B, T, _ = x.shape
    nh, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    r, inv, scale = c["kv_lora_rank"], yarn_inv_freq(c), softmax_scale(c)
    c_q = m.norm(m.dot(x, p["q_a"]), p["q_a_norm"], c["rms_norm_eps"])
    q = m.dot(c_q, p["q_b"]).reshape(B, T, nh, dn + dr).transpose(2, 0, 1, 3)  # [h, B, T, .]
    ckv = m.dot(x, p["kv_a"])
    c_kv = m.norm(ckv[..., :r], p["kv_a_norm"], c["rms_norm_eps"])
    k_r = _rope(ckv[..., r:], inv)  # [B, T, dr], shared by the heads
    kv = m.dot(c_kv, p["kv_b"]).reshape(B, T, nh, dn + dv).transpose(2, 0, 1, 3)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    @jax.checkpoint
    def head(qh, kvh):
        s = (m.dot(qh[..., :dn], kvh[..., :dn], "btd,bsd->bts")
             + m.dot(_rope(qh[..., dn:], inv), k_r, "btd,bsd->bts"))
        s = jnp.where(causal, s * jnp.asarray(scale, s.dtype), -jnp.inf)
        return m.dot(jax.nn.softmax(s, axis=-1), kvh[..., dn:], "bts,bsd->btd")

    o = jax.lax.map(lambda a: head(*a), (q, kv))  # [h, B, T, dv]
    return m.dot(o.transpose(1, 2, 0, 3).reshape(B, T, nh * dv), p["o"])


# ---- a hyper-connection ---------------------------------------------------------


def hc_maps(p, X, c, m: _Math):
    """X [B, T, n, C] -> (H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n])."""
    B, T, n, C = X.shape
    dt, eps = m.dtype, c["hc_eps"]
    u = X.reshape(B, T, n * C).astype(dt)
    u = u / jnp.sqrt(jnp.mean(jnp.square(u), axis=-1, keepdims=True) + eps)
    z = jnp.matmul(u, p["phi"].astype(dt), precision=HI)
    a, b = p["alpha"].astype(dt), p["b"].astype(dt)
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(a[2] * z[..., 2 * n:] + b[2 * n:], c["mhc_h_res_clamp_min"],
                         c["mhc_h_res_clamp_max"])).reshape(B, T, n, n)
    for _ in range(c["hc_sinkhorn_iters"]):
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)  # every column over its sum
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)  # every row over its sum
    return pre, post, M


def hyper_connection(p, X, branch, c, m: _Math):
    """X [B, T, n, C] -> (X', what the branch gave beside its output)."""
    n = X.shape[2]
    pre, post, res = hc_maps(p, X, c, m)
    h = sum(pre[:, :, i, None] * X[:, :, i] for i in range(n))
    y, aux = branch(h)
    out = [sum(res[:, :, i, j, None] * X[:, :, j] for j in range(n)) + post[:, :, i, None] * y
           for i in range(n)]
    return jnp.stack(out, axis=2), aux


def _attn_branch(p, c, m):
    return lambda h: (attention(p["attn"], m.norm(h, p["ln1"], c["rms_norm_eps"]), c, m), None)


def dense_block(p, X, c, m):
    X, _ = hyper_connection(p["hc_attn"], X, _attn_branch(p, c, m), c, m)
    mlp = lambda h: (glm.glu(p["mlp"], m.norm(h, p["ln2"], c["rms_norm_eps"]), m), None)  # noqa: E731
    return hyper_connection(p["hc_mlp"], X, mlp, c, m)[0]


def expert_branch(p, h, c, m):
    """h [B, T, H] -> (shared + the held experts' part of norm(h), chosen [B, T, k])."""
    B, T, H = h.shape
    y, chosen = glm.experts_part(p, m.norm(h, p["ln2"], c["rms_norm_eps"]).reshape(B * T, H), c, m)
    return y.reshape(B, T, H), chosen.reshape(B, T, -1)


def expert_block(p, X, c, m):
    X, _ = hyper_connection(p["hc_attn"], X, _attn_branch(p, c, m), c, m)
    return hyper_connection(p["hc_mlp"], X, lambda h: expert_branch(p, h, c, m), c, m)


def forward(params: dict, emb, ids, cfg: dict, dtype=jnp.float32, record_weight=None):
    """emb [B, T, H] token rows, ids [B, T] -> (loss, {"parts": [the loss, the
    batch's tokens] (the program's first two counters), "token_logits": [2, B,
    T] (the target's logit, the logsumexp), "router_choices": [expert layers,
    B, T, k]}). ``record_weight`` [B] leaves records out of the mean."""
    m = _Math(dtype, jnp.dtype(cfg["matmul_dtype"]))
    B, T, H = emb.shape
    ids = ids.astype(jnp.int32)
    X = jnp.broadcast_to(emb.astype(dtype)[:, :, None, :], (B, T, cfg["hc_mult"], H))
    for p in params["dense"]:
        X = jax.checkpoint(lambda p, X: dense_block(p, X, cfg, m))(p, X)
    X, choices = jax.lax.scan(
        jax.checkpoint(lambda X, p: expert_block(p, X, cfg, m)), X, params["moe"])
    x = jnp.sum(X, axis=2)
    w = jnp.ones((B,), jnp.float32) if record_weight is None else jnp.asarray(record_weight)
    tgt = jnp.concatenate([ids[:, 1:], jnp.zeros((B, 1), jnp.int32)], axis=1)
    t, l = glm.head_terms(params["head"],
                          m.norm(x, params["final_norm"], cfg["rms_norm_eps"]).reshape(B * T, H),
                          tgt.reshape(-1), m)
    t, l = t.reshape(B, T), l.reshape(B, T)
    has = (jnp.arange(T) < T - 1).astype(l.dtype)
    nll = (l - t) * has * w[:, None].astype(l.dtype)
    loss = (jnp.sum(nll) / (jnp.sum(w) * (T - 1)).astype(l.dtype)).astype(jnp.float32)
    out = {"parts": jnp.stack([loss, jnp.asarray(float(B * T), jnp.float32)]),
           "token_logits": jnp.stack([t, l]).astype(jnp.float32), "router_choices": choices}
    return loss, jax.tree.map(jax.lax.stop_gradient, out)
