"""SmallThinker-21BA3B (``smallthinker``), plain: written from the published
config's keys and the family's published modelling code (PowerInfer's
``modeling_smallthinker.py``; llama.cpp's ``llm_build_smallthinker``); nothing
of ``paddlebox_tpu`` is imported, no kernel, no grouped product. The two
precisions of a pass, rope and the blocked head are
``benchmark/reference/glm_moe_lite.py``'s (plain as well).

x is the float32 residual stream [B, T, hidden], the token's row at the input
(no scale). A layer, of kind ``held_sliding_layout[l]`` (1 = sliding, and then
``held_rope_layout[l]`` is 1 too):

1. The router, **ahead of attention**: ``r = x W_r`` in float32 (64 logits,
   from the layer's input itself, before ``input_layernorm``); the top 6
   logits; weights ``softmax(r[chosen])`` over the six (a softmax over all 64
   renormalised over the chosen: the same numbers). No bias, no scale.
2. ``a = norm(x; w_in)``; ``q = a W_q`` (28 heads of 128), ``k = a W_k``,
   ``v = a W_v`` (4 heads of 128); on a sliding layer rope (theta
   ``rope_theta``, halves paired) on q and k, on a full layer no position at
   all; query head h uses key-value head h // 7: the loop over the 4
   key-value heads is written out, the 7 query heads of each go one at a
   time; key j is visible to query i iff j <= i and, on a sliding layer,
   i - j < ``sliding_window_size``: whole [Q, T] masks from ``arange``, Q
   queries at a time; softmax of q.k / sqrt(128); ``x += o W_o``.
3. ``m = norm(x; w_post_attn)``; for every expert held (``experts_offset .. +
   moe_num_primary_experts``), ``(m W_up * relu(m W_gate)) W_down`` on every
   token times the token's weight for it from step 1 (zero where not chosen):
   a loop with a mask; ``x +=`` their sum. No shared expert: a token none of
   whose six is held adds nothing. As in the configuration file,
   ``moe_num_primary_experts`` counts the experts held and ``router_experts``
   the router's outputs (the published 64).

Head: final norm, ``W_head``, float32 cross-entropy against the id at i+1, the
plain mean over the T - 1 positions; its two parts are the means over the
target positions t < ``sliding_window_size`` and t >= it.

Departures from the published model (the configuration's ``assumed``): the
router's input and the gate's ReLU are the modelling code's, not keys of
``config.json``; no attention biases; no secondary experts; every layer an
expert layer; no cross-document mask; the embedding is the pass's sparse
table; one chip's share of the experts and of the vocabulary.

``router_after_attention`` (a key the control adds, never a configuration's)
is the planted fault of ``benchmark/control_smallthinker.py``: the router
reads the post-attention stream, where every other model's router stands. Its
two faults inside attention are keys of the same sort: ``ignore_window`` (every
sliding layer full causal, rope where it was: ``control_afmoe``'s fault) and
``swap_kv_heads`` (the query heads of groups 0 and 1 read each other's
key-value head).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.glm_moe_lite import HI, _Math, _normal, _rope, head_terms

QUERY_BLOCK = 1024  # queries whose [Q, T] scores of one head exist at once


def _layer_init(key, c):
    H, d, I = c["hidden_size"], c["head_dim"], c["moe_ffn_hidden_size"]
    nq, nkv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    G, k, std = c["moe_num_primary_experts"], jax.random.split(key, 8), c["initializer_range"]
    return {
        "attn": {"q": _normal(k[0], (H, nq), std), "k": _normal(k[1], (H, nkv), std),
                 "v": _normal(k[2], (H, nkv), std), "o": _normal(k[3], (nq, H), std)},
        "ln_in": jnp.ones((H,)), "ln_post_attn": jnp.ones((H,)),
        "router": {"w": _normal(k[4], (H, c["router_experts"]), std)},
        "experts": {"gate": _normal(k[5], (G, H, I), std), "up": _normal(k[6], (G, H, I), std),
                    "down": _normal(k[7], (G, I, H), std)},
    }


def init(key, cfg: dict, feat_width: int) -> dict:
    """Dense leaves from the seed: normal(0, initializer_range) matrices, norms
    of ones."""
    H = cfg["hidden_size"]
    if feat_width != 3 + H:
        raise ValueError(f"the token rows' embedx is the hidden size: {feat_width} != 3 + {H}")
    n = len(cfg["held_sliding_layout"])
    k = jax.random.split(key, n + 1)
    return {
        "layers": jax.tree.map(lambda *a: jnp.stack(a), *[_layer_init(k[i], cfg) for i in range(n)]),
        "final_norm": jnp.ones((H,)),
        "head": _normal(k[n], (H, cfg["vocab_size"]), cfg["initializer_range"]),
    }


def route(p, x, c, m: _Math):
    """x [N, H] -> (chosen experts [N, 6], their weights [N, 6])."""
    # float32 at highest; the lower-precision control routes in its own precision
    r = jnp.matmul(x.astype(m.dtype), p["router"]["w"].astype(m.dtype), precision=HI)
    picked, chosen = jax.lax.top_k(r, c["moe_num_active_primary_experts"])
    return chosen, jax.nn.softmax(picked, axis=1)


def attention(p, a, c, m: _Math, sliding: bool):
    """a [B, T, hidden], already normed -> o W_o."""
    B, T, _ = a.shape
    nh, nkv, d = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    heads = lambda z, n: z.reshape(B, T, n, d).transpose(2, 0, 1, 3)  # noqa: E731  [n, B, T, d]
    q, k, v = heads(m.dot(a, p["q"]), nh), heads(m.dot(a, p["k"]), nkv), heads(m.dot(a, p["v"]), nkv)
    if sliding:
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    windowed = sliding and not c.get("ignore_window", False)
    Q = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    kj = jnp.arange(T)[None, :]

    def one_head(qh, kh, vh):  # [B, T, d] each

        @jax.checkpoint
        def block(qb, i0):  # Q queries from position i0 against every key
            qi = i0 + jnp.arange(Q)[:, None]
            seen = (kj <= qi) & (qi - kj < c["sliding_window_size"]) if windowed else kj <= qi
            s = m.dot(qb, kh, "btd,bsd->bts") / jnp.sqrt(jnp.asarray(d, m.dtype))
            return m.dot(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), vh, "bts,bsd->btd")

        ob = jax.lax.map(lambda x: block(*x), (qh.reshape(B, T // Q, Q, d).transpose(1, 0, 2, 3),
                                               jnp.arange(0, T, Q)))
        return ob.transpose(1, 0, 2, 3).reshape(B, T, d)

    group = nh // nkv
    outs = []
    for g in range(nkv):  # query heads g * group .. + group read key-value head g
        kv = g ^ 1 if c.get("swap_kv_heads", False) and g < 2 else g
        outs.append(jax.lax.map(lambda qh, kv=kv: one_head(qh, k[kv], v[kv]),
                                q[g * group:(g + 1) * group]))
    o = jnp.concatenate(outs).transpose(1, 2, 0, 3).reshape(B, T, nh * d)
    return m.dot(o, p["o"])


def relu_glu(p, x, m: _Math):
    return m.dot(m.dot(x, p["up"]) * jax.nn.relu(m.dot(x, p["gate"])), p["down"])


def experts_part(p, x, chosen, w, c, m: _Math):
    """x [N, H], routed as (chosen, w) [N, 6] -> the held experts' weighted outputs."""
    off, held = c["experts_offset"], c["moe_num_primary_experts"]
    y = jnp.zeros_like(x)
    for e in range(held):  # expert off + e on every token, times the token's weight for it
        w_e = jnp.sum(jnp.where(chosen == off + e, w, 0.0), axis=1, keepdims=True)
        pe = jax.tree.map(lambda a, e=e: a[e], p["experts"])
        y = y + jax.checkpoint(lambda pe, x, w_e: relu_glu(pe, x, m) * w_e.astype(m.dtype))(pe, x, w_e)
    return y


def layer(p, x, c, m: _Math, sliding: bool):
    B, T, H = x.shape
    eps = c["rms_norm_eps"]
    after = c.get("router_after_attention", False)
    if not after:
        chosen, w = route(p, x.reshape(B * T, H), c, m)
    x = x + attention(p["attn"], m.norm(x, p["ln_in"], eps), c, m, sliding)
    if after:
        chosen, w = route(p, x.reshape(B * T, H), c, m)
    f = experts_part(p, m.norm(x, p["ln_post_attn"], eps).reshape(B * T, H), chosen, w, c, m)
    return x + f.reshape(B, T, H), chosen.reshape(B, T, -1)


def forward(params: dict, emb, ids, cfg: dict, dtype=jnp.float32, record_weight=None):
    """emb [B, T, H] token rows, ids [B, T] -> (loss, {"parts": [inside the
    first window, past it], "token_logits": [2, B, T] (the target's logit, the
    logsumexp), "router_choices": [layers, B, T, k]}). ``record_weight`` [B]
    leaves records out of the mean."""
    m = _Math(dtype, jnp.dtype(cfg["matmul_dtype"]))
    B, T, H = emb.shape
    ids = ids.astype(jnp.int32)
    x = emb.astype(dtype)
    choices = []
    for i, kind in enumerate(cfg["held_sliding_layout"]):  # the layers differ in kind: one by one
        p = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        x, chosen = jax.checkpoint(lambda p, x, s=bool(kind): layer(p, x, cfg, m, s))(p, x)
        choices.append(chosen)
    w = jnp.ones((B,), jnp.float32) if record_weight is None else jnp.asarray(record_weight)
    tgt = jnp.concatenate([ids[:, 1:], jnp.zeros((B, 1), jnp.int32)], axis=1)
    t, l = head_terms(params["head"], m.norm(x, params["final_norm"], cfg["rms_norm_eps"]
                                             ).reshape(B * T, H), tgt.reshape(-1), m)
    t, l = t.reshape(B, T), l.reshape(B, T)
    pos, W = jnp.arange(T), cfg["sliding_window_size"]
    nll = (l - t) * w[:, None].astype(l.dtype)  # and its means, in the pass's own precision
    mean = lambda has: (jnp.sum(nll * has.astype(l.dtype))  # noqa: E731
                        / (jnp.sum(w) * jnp.sum(has)).astype(l.dtype)).astype(jnp.float32)
    parts = jnp.stack([mean((pos < T - 1) & (pos < W)), mean((pos < T - 1) & (pos >= W))])
    out = {"parts": parts, "token_logits": jnp.stack([t, l]).astype(jnp.float32),
           "router_choices": jnp.stack(choices)}
    return mean(pos < T - 1), jax.tree.map(jax.lax.stop_gradient, out)
