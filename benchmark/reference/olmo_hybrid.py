"""Olmo-Hybrid (``olmo_hybrid``), plain: written from the published config's
keys, the Gated DeltaNet layer (Yang, Kautz, Hatamizadeh, arXiv:2412.06464;
flash-linear-attention's ``GatedDeltaNet``, whose key names the config uses)
and OLMo 2's block (arXiv:2501.00656); nothing of ``paddlebox_tpu`` is
imported, no kernel, no chunked form. The two precisions of a pass, the
SwiGLU, the blocked head and the seeded normals are
``benchmark/reference/glm_moe_lite.py``'s (plain as well).

x is the float32 residual stream [B, T, hidden], the token rows at the input.
No layer has an input norm: a layer is ``h = x + norm(y(x); w_post_attn)``,
``x' = h + norm(SwiGLU(h); w_post_mlp)`` (SwiGLU ``intermediate_size`` wide),
y of the layer's kind (``layer_types[:num_hidden_layers]``):

- ``linear_attention``, per held head (``linear_num_value_heads``, keys of
  ``linear_key_head_dim``, values of ``linear_value_head_dim``): ``q, k, v =
  silu(conv(x W))``, each a causal depthwise convolution of kernel
  ``linear_conv_kernel_dim``, zero before the record's start (the sum over
  the kernel's taps written out); q and k divided by sqrt(sum of squares +
  1e-6), q times dk^-1/2; ``beta = sigmoid(x w_b)``, times 2 where
  ``linear_allow_neg_eigval``; ``alpha = exp(-exp(A_log) softplus(x w_a +
  dt_bias))`` (``ba`` holds w_b's columns, then w_a's); **the per-token
  recurrence** ``S = alpha S; S += beta (v - S k) k^T; o = S q`` (S [dv,
  dk], zero at the record's start), a scan over the
  tokens inside a scan over blocks of ``BLOCK`` tokens under
  ``jax.checkpoint``, so that only the blocks' boundary states are kept;
  ``y = W_o [o / sqrt(mean(o^2) + eps) w_n silu(x W_g)]`` (the norm over each
  head's dv, one weight for all heads).
- ``full_attention``: ``q = norm(x W_q; w_q)``, ``k = norm(x W_k; w_k)``, each
  over the held projection's columns (the one-chip statistic), ``v = x W_v``;
  heads of ``head_dim``; no position; causal softmax of ``q.k / sqrt(d)``,
  ``QUERY_BLOCK`` queries at a time; ``y = o W_o``.

Head: final norm, the untied ``W_head`` over the held vocabulary slice,
float32 cross-entropy against the id at i+1, the plain mean over the T - 1
positions; its two parts are the means over the target positions t <
``chunk`` (inside the first chunk, which no carried state reaches) and t >=
it. No router: ``router_choices`` is one [1, B, T, 1] zero array.

Two planted faults for the controls (``benchmark/control_olmo_hybrid.py``),
absent unless the configuration handed in names them:
``linear_allow_neg_eigval`` false (beta = sigmoid(.)) and ``decay_left_out``
(alpha = 1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.glm_moe_lite import HI, _glu_init, _Math, _normal, glu, head_terms

BLOCK = 64  # tokens of the recurrence whose per-token states exist at once
QUERY_BLOCK = 1024  # queries whose [Q, T] scores of one head exist at once


def _held(c: dict) -> list:
    return c["layer_types"][:c["num_hidden_layers"]]


def _linear_init(key, c):
    H, nh = c["hidden_size"], c["linear_num_value_heads"]
    dk, dv, K = c["linear_key_head_dim"], c["linear_value_head_dim"], c["linear_conv_kernel_dim"]
    k, std = jax.random.split(key, 12), c["initializer_range"]
    bound = 1.0 / np.sqrt(K)
    dt = jnp.exp(jax.random.uniform(k[10], (nh,), jnp.float32, np.log(1e-3), np.log(0.1)))
    return {"q": _normal(k[0], (H, nh * dk), std), "k": _normal(k[1], (H, nh * dk), std),
            "v": _normal(k[2], (H, nh * dv), std), "ba": _normal(k[3], (H, 2 * nh), std),
            "A_log": jnp.log(jax.random.uniform(k[5], (nh,), jnp.float32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) = dt
            "conv_q": jax.random.uniform(k[6], (K, nh * dk), jnp.float32, -bound, bound),
            "conv_k": jax.random.uniform(k[7], (K, nh * dk), jnp.float32, -bound, bound),
            "conv_v": jax.random.uniform(k[8], (K, nh * dv), jnp.float32, -bound, bound),
            "g": _normal(k[9], (H, nh * dv), std), "out_norm": jnp.ones((dv,)),
            "o": _normal(k[11], (nh * dv, H), std)}


def _full_init(key, c):
    H, n = c["hidden_size"], c["num_attention_heads"] * c["head_dim"]
    k, std = jax.random.split(key, 4), c["initializer_range"]
    return {"q": _normal(k[0], (H, n), std), "k": _normal(k[1], (H, n), std),
            "v": _normal(k[2], (H, n), std), "o": _normal(k[3], (n, H), std),
            "q_norm": jnp.ones((n,)), "k_norm": jnp.ones((n,))}


def _layer_init(key, c, linear: bool):
    H, k = c["hidden_size"], jax.random.split(key)
    attn = _linear_init(k[0], c) if linear else _full_init(k[0], c)
    return {**attn, "ln_post_attn": jnp.ones((H,)), "ln_post_mlp": jnp.ones((H,)),
            "mlp": _glu_init(k[1], c, c["intermediate_size"])}


def init(key, cfg: dict, feat_width: int) -> dict:
    """Dense leaves from the seed (the configuration's ``assumed``): the
    linear layers stacked in their order, the full layers stacked."""
    H = cfg["hidden_size"]
    if feat_width != 3 + H:
        raise ValueError(f"the token rows' embedx is the hidden size: {feat_width} != 3 + {H}")
    types = _held(cfg)
    L = types.index("full_attention")
    P = len(types) // (L + 1)
    k = jax.random.split(key, P + 1)
    stack = lambda trees: jax.tree.map(lambda *a: jnp.stack(a), *trees)  # noqa: E731

    kl = [jax.random.split(k[i], L + 1) for i in range(P)]
    return {"linear": stack([_layer_init(ki[j], cfg, True) for ki in kl for j in range(L)]),
            "full": stack([_layer_init(ki[L], cfg, False) for ki in kl]),
            "final_norm": jnp.ones((H,)),
            "head": _normal(k[P], (H, cfg["vocab_size"]), cfg["initializer_range"])}


def short_conv(z, w):
    """z [B, T, C], w [K, C]: silu(sum_j w[j] z_{t - K + 1 + j}), zero before t = 0."""
    K, (B, T, C) = w.shape[0], z.shape
    y = jnp.zeros_like(z)
    for j in range(K):
        lag = K - 1 - j
        shifted = jnp.concatenate([jnp.zeros((B, lag, C), z.dtype), z[:, :T - lag]], axis=1)
        y = y + w[j].astype(z.dtype) * shifted
    return jax.nn.silu(y)


def recurrence(q, k, v, beta, alpha):
    """The gated delta rule token by token: q, k [B, T, h, dk], v [B, T, h,
    dv], beta, alpha [B, T, h] -> o [B, T, h, dv]."""
    B, T, h, dk = q.shape
    dv = v.shape[-1]
    blk = BLOCK if T % BLOCK == 0 else T

    def token(S, xs):  # S [B, h, dv, dk]
        qt, kt, vt, bt, at = xs
        S = at[..., None, None] * S
        Sk = jnp.einsum("bhvk,bhk->bhv", S, kt, precision=HI)
        S = S + bt[..., None, None] * jnp.einsum("bhv,bhk->bhvk", vt - Sk, kt, precision=HI)
        return S, jnp.einsum("bhvk,bhk->bhv", S, qt, precision=HI)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    xs = tuple(a.swapaxes(0, 1).reshape(T // blk, blk, *a.shape[:1], *a.shape[2:])
               for a in (q, k, v, beta, alpha))
    _, o = jax.lax.scan(block, jnp.zeros((B, h, dv, dk), q.dtype), xs)
    return o.reshape(T, B, h, dv).swapaxes(0, 1)


def linear_attention(p, x, c, m: _Math):
    B, T, _ = x.shape
    nh, dk, dv = c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    unit = lambda z: z / jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(short_conv(m.dot(x, p["q"]), p["conv_q"]).reshape(B, T, nh, dk)) / np.sqrt(dk)
    k = unit(short_conv(m.dot(x, p["k"]), p["conv_k"]).reshape(B, T, nh, dk))
    v = short_conv(m.dot(x, p["v"]), p["conv_v"]).reshape(B, T, nh, dv)
    ba = m.dot(x, p["ba"])  # beta's projection, then the decay's: a column a head each
    beta = jax.nn.sigmoid(ba[..., :nh])
    if c["linear_allow_neg_eigval"]:
        beta = 2 * beta
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(m.dtype))
                    * jax.nn.softplus(ba[..., nh:] + p["dt_bias"].astype(m.dtype)))
    if c.get("decay_left_out", False):
        alpha = jnp.ones_like(alpha)
    o = recurrence(q, k, v, beta, alpha)
    gate = m.dot(x, p["g"]).reshape(B, T, nh, dv)
    o = m.norm(o, p["out_norm"], c["rms_norm_eps"]) * jax.nn.silu(gate)
    return m.dot(o.reshape(B, T, nh * dv), p["o"])


def full_attention(p, x, c, m: _Math):
    B, T, _ = x.shape
    nh, d, eps = c["num_attention_heads"], c["head_dim"], c["rms_norm_eps"]
    heads = lambda z: z.reshape(B, T, nh, d).transpose(2, 0, 1, 3)  # noqa: E731  [nh, B, T, d]
    q = heads(m.norm(m.dot(x, p["q"]), p["q_norm"], eps))
    k = heads(m.norm(m.dot(x, p["k"]), p["k_norm"], eps))
    v = heads(m.dot(x, p["v"]))
    Q = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    kj = jnp.arange(T)[None, :]

    def one_head(qh, kh, vh):  # [B, T, d] each

        @jax.checkpoint
        def block(qb, i0):  # Q queries from position i0 against every key
            s = m.dot(qb, kh, "btd,bsd->bts") / jnp.sqrt(jnp.asarray(d, m.dtype))
            seen = kj <= i0 + jnp.arange(Q)[:, None]
            return m.dot(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), vh, "bts,bsd->btd")

        ob = jax.lax.map(lambda a: block(*a), (qh.reshape(B, T // Q, Q, d).transpose(1, 0, 2, 3),
                                               jnp.arange(0, T, Q)))
        return ob.transpose(1, 0, 2, 3).reshape(B, T, d)

    o = jax.lax.map(lambda a: one_head(*a), (q, k, v))  # [nh, B, T, d]
    return m.dot(o.transpose(1, 2, 0, 3).reshape(B, T, nh * d), p["o"])


def layer(p, x, c, m: _Math, linear: bool):
    eps = c["rms_norm_eps"]
    y = linear_attention(p, x, c, m) if linear else full_attention(p, x, c, m)
    h = x + m.norm(y, p["ln_post_attn"], eps)
    return h + m.norm(glu(p["mlp"], h, m), p["ln_post_mlp"], eps)


def forward(params: dict, emb, ids, cfg: dict, dtype=jnp.float32, record_weight=None):
    """emb [B, T, H] token rows, ids [B, T] -> (loss, {"parts": [inside the
    first chunk, past it], "token_logits": [2, B, T] (the target's logit, the
    logsumexp), "router_choices": [1, B, T, 1] zeros (no router)}).
    ``record_weight`` [B] leaves records out of the mean."""
    m = _Math(dtype, jnp.dtype(cfg["matmul_dtype"]))
    B, T, H = emb.shape
    ids = ids.astype(jnp.int32)
    x = emb.astype(dtype)
    types = _held(cfg)
    L = types.index("full_attention")
    for i, kind in enumerate(types):  # layer by layer, each recomputed
        period, j = divmod(i, L + 1)
        linear = kind == "linear_attention"
        at = period * L + j if linear else period
        p = jax.tree.map(lambda a, at=at: a[at], params["linear" if linear else "full"])
        x = jax.checkpoint(lambda p, x, linear=linear: layer(p, x, cfg, m, linear))(p, x)
    w = jnp.ones((B,), jnp.float32) if record_weight is None else jnp.asarray(record_weight)
    tgt = jnp.concatenate([ids[:, 1:], jnp.zeros((B, 1), jnp.int32)], axis=1)
    t, l = head_terms(params["head"], m.norm(x, params["final_norm"], cfg["rms_norm_eps"]
                                             ).reshape(B * T, H), tgt.reshape(-1), m)
    t, l = t.reshape(B, T), l.reshape(B, T)
    pos, W = jnp.arange(T), cfg["chunk"]
    nll = (l - t) * w[:, None].astype(l.dtype)  # and its means, in the pass's own precision
    mean = lambda has: (jnp.sum(nll * has.astype(l.dtype))  # noqa: E731
                        / (jnp.sum(w) * jnp.sum(has)).astype(l.dtype)).astype(jnp.float32)
    parts = jnp.stack([mean((pos < T - 1) & (pos < W)), mean((pos < T - 1) & (pos >= W))])
    out = {"parts": parts, "token_logits": jnp.stack([t, l]).astype(jnp.float32),
           "router_choices": jnp.zeros((1, B, T, 1), jnp.int32)}
    return mean(pos < T - 1), jax.tree.map(jax.lax.stop_gradient, out)
