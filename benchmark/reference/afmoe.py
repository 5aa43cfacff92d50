"""Trinity-Mini (``afmoe``), plain: written from the published config's keys
and the family's published modelling code (transformers ``models/afmoe``);
nothing of ``paddlebox_tpu`` is imported, no kernel, no grouped product. The
two precisions of a pass, the SwiGLU, rope and the blocked head are
``benchmark/reference/glm_moe_lite.py``'s (plain as well).

x is the float32 residual stream [B, T, hidden], ``sqrt(hidden) * emb`` at the
input (``mup_enabled``). A layer, of kind ``held_layer_types[l]``:

- ``a = norm(x; w_in)``; ``q = a W_q`` (32 heads of 128), ``k = a W_k``,
  ``v = a W_v`` (4 heads of 128), ``g = a W_g`` (4096); RMSNorm over each
  head's 128 of q and of k (one weight for all heads); on a
  ``sliding_attention`` layer rope (theta ``rope_theta``, halves paired) on q
  and k, on a ``full_attention`` layer no position at all; query head h uses
  key-value head h // 8: the loop over the 4 key-value heads is written out,
  the 8 query heads of each go one at a time; key j is visible to query i iff
  j <= i and, on a sliding layer, i - j < ``sliding_window``: whole [Q, T]
  masks from ``arange``, Q queries at a time; softmax of q.k / sqrt(128);
  ``x += norm((o * sigmoid(g)) W_o; w_post_attn)``.
- ``m = norm(x; w_pre_mlp)``; ``x += norm(F(m); w_post_mlp)``. F of a dense
  layer: SwiGLU ``intermediate_size`` wide. F of an expert layer:
  ``s = sigmoid(m W_r)`` in float32; the top k of ``s + b``; weights
  ``s[chosen] / sum(s[chosen]) * route_scale``; the shared expert plus, for
  every expert held (``experts_offset .. + num_experts``), its SwiGLU on every
  token times the token's weight for it (zero where not chosen): a loop with a
  mask. As in the configuration file, ``num_experts`` counts the experts held
  and ``router_experts`` the router's outputs (the published 128).
- Head: final norm, ``W_head``, float32 cross-entropy against the id at i+1,
  the plain mean over the T - 1 positions; its two parts are the means over
  the target positions t < ``sliding_window`` and t >= it.

``ignore_window`` (a key the control adds, never a configuration's) is the
planted fault of ``benchmark/control_afmoe.py``: every layer full causal,
rope where it was.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.glm_moe_lite import HI, _glu_init, _Math, _normal, _rope, glu, head_terms

QUERY_BLOCK = 1024  # queries whose [Q, T] scores of one head exist at once


def _attn_init(key, c):
    H, d = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    k, std = jax.random.split(key, 5), c["initializer_range"]
    return {"q": _normal(k[0], (H, nq), std), "k": _normal(k[1], (H, nkv), std),
            "v": _normal(k[2], (H, nkv), std), "gate": _normal(k[3], (H, nq), std),
            "o": _normal(k[4], (nq, H), std), "q_norm": jnp.ones((d,)), "k_norm": jnp.ones((d,))}


def _layer_init(key, c, moe: bool):
    H, k, std = c["hidden_size"], jax.random.split(key, 5), c["initializer_range"]
    p = {"attn": _attn_init(k[0], c), "ln_in": jnp.ones((H,)), "ln_post_attn": jnp.ones((H,)),
         "ln_pre_mlp": jnp.ones((H,)), "ln_post_mlp": jnp.ones((H,))}
    if not moe:
        p["mlp"] = _glu_init(k[1], c, c["intermediate_size"])
        return p
    p["router"] = {"w": _normal(k[1], (H, c["router_experts"]), std),
                   "bias": _normal(k[2], (c["router_experts"],), std)}
    p["shared"] = _glu_init(k[3], c, c["moe_intermediate_size"])
    p["experts"] = _glu_init(k[4], c, c["moe_intermediate_size"], lead=(c["num_experts"],))
    return p


def init(key, cfg: dict, feat_width: int) -> dict:
    """Dense leaves from the seed: normal(0, initializer_range) matrices, norms
    of ones, the router's correction bias a seeded buffer."""
    H = cfg["hidden_size"]
    if feat_width != 3 + H:
        raise ValueError(f"the token rows' embedx is the hidden size: {feat_width} != 3 + {H}")
    n, first = len(cfg["held_layer_types"]), cfg["held_dense_layers"]
    k = jax.random.split(key, n + 1)
    moe = [_layer_init(k[i], cfg, True) for i in range(first, n)]
    return {
        "dense": [_layer_init(k[i], cfg, False) for i in range(first)],
        "moe": jax.tree.map(lambda *a: jnp.stack(a), *moe),
        "final_norm": jnp.ones((H,)),
        "head": _normal(k[n], (H, cfg["vocab_size"]), cfg["initializer_range"]),
    }


def attention(p, a, c, m: _Math, sliding: bool):
    """a [B, T, hidden], already normed -> (o * sigmoid(g)) W_o."""
    B, T, _ = a.shape
    nh, nkv, d, eps = (c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
                       c["rms_norm_eps"])
    heads = lambda z, n: z.reshape(B, T, n, d).transpose(2, 0, 1, 3)  # noqa: E731  [n, B, T, d]
    q = m.norm(heads(m.dot(a, p["q"]), nh), p["q_norm"], eps)
    k = m.norm(heads(m.dot(a, p["k"]), nkv), p["k_norm"], eps)
    v = heads(m.dot(a, p["v"]), nkv)
    if sliding:
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    windowed = sliding and not c.get("ignore_window", False)
    Q = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    kj = jnp.arange(T)[None, :]

    def one_head(qh, kh, vh):  # [B, T, d] each

        @jax.checkpoint
        def block(qb, i0):  # Q queries from position i0 against every key
            qi = i0 + jnp.arange(Q)[:, None]
            seen = (kj <= qi) & (qi - kj < c["sliding_window"]) if windowed else kj <= qi
            s = m.dot(qb, kh, "btd,bsd->bts") / jnp.sqrt(jnp.asarray(d, m.dtype))
            return m.dot(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), vh, "bts,bsd->btd")

        ob = jax.lax.map(lambda x: block(*x), (qh.reshape(B, T // Q, Q, d).transpose(1, 0, 2, 3),
                                               jnp.arange(0, T, Q)))
        return ob.transpose(1, 0, 2, 3).reshape(B, T, d)

    group = nh // nkv
    outs = []
    for g in range(nkv):  # query heads g * group .. + group read key-value head g
        outs.append(jax.lax.map(lambda qh, g=g: one_head(qh, k[g], v[g]),
                                q[g * group:(g + 1) * group]))
    o = jnp.concatenate(outs).transpose(1, 2, 0, 3).reshape(B, T, nh * d)
    gate = jax.nn.sigmoid(m.dot(a, p["gate"]))
    return m.dot(o * gate, p["o"])


def experts_part(p, x, c, m: _Math):
    """x [N, H] -> (shared expert + the held experts' weighted outputs, chosen [N, k])."""
    top, off, held = c["num_experts_per_tok"], c["experts_offset"], c["num_experts"]
    # float32 at highest; the lower-precision control routes in its own precision
    s = jax.nn.sigmoid(jnp.matmul(x.astype(m.dtype), p["router"]["w"].astype(m.dtype), precision=HI))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(p["router"]["bias"]).astype(s.dtype), top)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    w = picked / jnp.sum(picked, axis=1, keepdims=True) * c["route_scale"]
    y = glu(p["shared"], x, m)
    for e in range(held):  # expert off + e on every token, times the token's weight for it
        w_e = jnp.sum(jnp.where(chosen == off + e, w, 0.0), axis=1, keepdims=True)
        pe = jax.tree.map(lambda a, e=e: a[e], p["experts"])
        y = y + jax.checkpoint(lambda pe, x, w_e: glu(pe, x, m) * w_e.astype(m.dtype))(pe, x, w_e)
    return y, chosen


def layer(p, x, c, m: _Math, sliding: bool, moe: bool):
    B, T, H = x.shape
    eps = c["rms_norm_eps"]
    y = attention(p["attn"], m.norm(x, p["ln_in"], eps), c, m, sliding)
    x = x + m.norm(y, p["ln_post_attn"], eps)
    h = m.norm(x, p["ln_pre_mlp"], eps)
    if moe:
        f, chosen = experts_part(p, h.reshape(B * T, H), c, m)
        f, chosen = f.reshape(B, T, H), chosen.reshape(B, T, -1)
    else:
        f, chosen = glu(p["mlp"], h, m), None
    return x + m.norm(f, p["ln_post_mlp"], eps), chosen


def forward(params: dict, emb, ids, cfg: dict, dtype=jnp.float32, record_weight=None):
    """emb [B, T, H] token rows, ids [B, T] -> (loss, {"parts": [inside the
    first window, past it], "token_logits": [2, B, T] (the target's logit, the
    logsumexp), "router_choices": [expert layers, B, T, k]}).
    ``record_weight`` [B] leaves records out of the mean."""
    m = _Math(dtype, jnp.dtype(cfg["matmul_dtype"]))
    B, T, H = emb.shape
    ids = ids.astype(jnp.int32)
    x = emb.astype(dtype)
    if cfg["mup_enabled"]:
        x = x * jnp.sqrt(jnp.asarray(H, dtype))
    kinds = [t == "sliding_attention" for t in cfg["held_layer_types"]]
    first = cfg["held_dense_layers"]
    choices = []
    for i, sliding in enumerate(kinds):  # the layers differ in kind: one by one, each recomputed
        moe = i >= first
        p = params["dense"][i] if not moe else jax.tree.map(lambda a, i=i: a[i - first],
                                                            params["moe"])
        x, chosen = jax.checkpoint(
            lambda p, x, s=sliding, e=moe: layer(p, x, cfg, m, s, e))(p, x)
        if moe:
            choices.append(chosen)
    w = jnp.ones((B,), jnp.float32) if record_weight is None else jnp.asarray(record_weight)
    tgt = jnp.concatenate([ids[:, 1:], jnp.zeros((B, 1), jnp.int32)], axis=1)
    t, l = head_terms(params["head"], m.norm(x, params["final_norm"], cfg["rms_norm_eps"]
                                             ).reshape(B * T, H), tgt.reshape(-1), m)
    t, l = t.reshape(B, T), l.reshape(B, T)
    pos, W = jnp.arange(T), cfg["sliding_window"]
    nll = (l - t) * w[:, None].astype(l.dtype)  # and its means, in the pass's own precision
    mean = lambda has: (jnp.sum(nll * has.astype(l.dtype))  # noqa: E731
                        / (jnp.sum(w) * jnp.sum(has)).astype(l.dtype)).astype(jnp.float32)
    parts = jnp.stack([mean((pos < T - 1) & (pos < W)), mean((pos < T - 1) & (pos >= W))])
    out = {"parts": parts, "token_logits": jnp.stack([t, l]).astype(jnp.float32),
           "router_choices": jnp.stack(choices)}
    return mean(pos < T - 1), jax.tree.map(jax.lax.stop_gradient, out)
