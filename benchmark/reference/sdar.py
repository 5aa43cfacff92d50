"""SDAR-30B-A3B (``sdar``), plain: written from the published config's keys,
the parent family's published modelling code for the layer (grouped-query
attention with QK-norm, rope on every layer, 128 SwiGLU experts top 8 with a
softmax renormalised over the chosen, no shared expert) and the block-diffusion
training of BD3-LMs (arXiv:2503.09573) with the masked-diffusion loss in its
fixed-count form (LLaDA, arXiv:2502.09992; RADD, arXiv:2406.03736); nothing
of ``paddlebox_tpu`` is imported, no kernel, no grouped product. The two
precisions of a pass, rope and the blocked head are
``benchmark/reference/glm_moe_lite.py``'s (plain as well).

A record is 2 L ids (``seq_len`` = 2 x ``data_len``): the clean tokens c_0 ..
c_{L-1}, then the noised copy n_0 .. n_{L-1}, n_i = ``mask_id`` where the
data job masked position i, c_i elsewhere. For index u: pos(u) = u mod L,
half(u) = u // L (0 clean, 1 noisy), blk(u) = pos(u) // ``block_length``. x
is the float32 residual stream [B, 2 L, hidden], the key's row at the input
(no scale). A layer:

1. ``a = norm(x; w_in)``; ``q = a W_q`` (32 heads of 128), ``k = a W_k``,
   ``v = a W_v`` (4 heads of 128), no biases; RMSNorm over each head's 128 of
   q and of k (one weight each for all heads); rope (theta ``rope_theta``,
   halves paired, all 128 dims) on q and k at pos(u): both halves carry the
   positions 0 .. L - 1; query head h uses key-value head h // 8: the loop
   over the 4 key-value heads is written out, the 8 query heads of each go
   one at a time; key w is visible to query u iff
   half(u) = 0: half(w) = 0 and blk(w) <= blk(u);
   half(u) = 1: half(w) = 0 and blk(w) < blk(u), or half(w) = 1 and
   blk(w) = blk(u): whole [Q, 2 L] masks from ``arange``, Q queries at a time;
   softmax of q.k / sqrt(128); ``x += o W_o``.
2. ``m = norm(x; w_post_attn)``; ``r = m W_r`` in float32 (128 logits); the
   top 8 logits; weights ``softmax(r[chosen])`` over the eight
   (``norm_topk_prob``: a softmax over all 128 renormalised over the chosen is
   the same numbers); for every expert held (``experts_offset .. +
   num_experts``), ``(silu(m W_gate) * (m W_up)) W_down`` on every token times
   the token's weight for it (zero where not chosen): a loop with a mask;
   ``x +=`` their sum. No shared expert: a token none of whose eight is held
   adds nothing. As in the configuration file, ``num_experts`` counts the
   experts held and ``router_experts`` the router's outputs (the published
   128).

Head: the noisy half alone, ``h = norm(x[L:]; w_final)``, ``W_head``, float32
``CE_i = logsumexp_i - logit_i[c_i]`` (position i predicts token i: no
shift); ``M_i = [n_i = mask_id]``, m_b the masked positions of block b;
``loss = (1 / L) sum_i M_i (block_length / m_blk(i)) CE_i``. Its two parts are
the same sum over the target positions i < L / 2 and i >= L / 2, each over
its L / 2 positions: their mean is the loss.

Departures from the published model (the configuration's ``assumed``):
``block_length`` 4 and the noise (a count uniform on 1 .. 4 a block, that
many positions masked without replacement, weight 4 / count) are the
family's convention and the fixed-count bound, not keys of ``config.json``;
position i of the noisy half predicts token i and the softmax runs over the
whole held slice, the MASK id's row among the logits; ``mask_id`` is the last
id of the held slice; QK-norm, pre-norm residuals, no biases and rope over
all 128 dims are the parent family's modelling code; L 8,192 of 32,768; no
cross-document mask; the embedding is the pass's sparse table; one chip's
share of the experts and of the vocabulary.

``leak`` (a key the control adds, never a configuration's) is the planted
fault of ``benchmark/control_sdar.py``, this architecture's own: a noisy query
also sees the clean copy of its own block (blk(w) <= blk(u)), the answer
beside the question.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.glm_moe_lite import HI, _Math, _normal, _rope, glu, head_terms

QUERY_BLOCK = 1024  # queries whose [Q, 2 L] scores of one head exist at once


def _layer_init(key, c):
    H, d, I = c["hidden_size"], c["head_dim"], c["moe_intermediate_size"]
    nq, nkv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    G, k, std = c["num_experts"], jax.random.split(key, 8), c["initializer_range"]
    return {
        "attn": {"q": _normal(k[0], (H, nq), std), "k": _normal(k[1], (H, nkv), std),
                 "v": _normal(k[2], (H, nkv), std), "o": _normal(k[3], (nq, H), std),
                 "q_norm": jnp.ones((d,)), "k_norm": jnp.ones((d,))},
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
    n = cfg["num_hidden_layers"]
    k = jax.random.split(key, n + 1)
    return {
        "layers": jax.tree.map(lambda *a: jnp.stack(a), *[_layer_init(k[i], cfg) for i in range(n)]),
        "final_norm": jnp.ones((H,)),
        "head": _normal(k[n], (H, cfg["vocab_size"]), cfg["initializer_range"]),
    }


def visible(u, w, c):
    """Whether key index w is visible to query index u (arrays that broadcast)."""
    L, n = c["data_len"], c["block_length"]
    bu, bw = u % L // n, w % L // n
    clean, noisy = w // L == 0, w // L == 1
    past = bw <= bu if c.get("leak", False) else bw < bu  # the planted fault: its own clean block too
    return jnp.where(u // L == 0, clean & (bw <= bu), clean & past | noisy & (bw == bu))


def route(p, x, c, m: _Math):
    """x [N, H] -> (chosen experts [N, 8], their weights [N, 8])."""
    # float32 at highest; the lower-precision control routes in its own precision
    r = jnp.matmul(x.astype(m.dtype), p["router"]["w"].astype(m.dtype), precision=HI)
    picked, chosen = jax.lax.top_k(r, c["num_experts_per_tok"])
    return chosen, jax.nn.softmax(picked, axis=1)


def attention(p, a, c, m: _Math):
    """a [B, 2 L, hidden], already normed -> o W_o."""
    B, T, _ = a.shape
    nh, nkv, d, L = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"], c["data_len"]
    eps = c["rms_norm_eps"]
    heads = lambda z, n: z.reshape(B, T, n, d).transpose(2, 0, 1, 3)  # noqa: E731  [n, B, T, d]
    # both halves carry the positions 0 .. L - 1
    rope = lambda z: _rope(z.reshape(-1, B, 2, L, d), c["rope_theta"]).reshape(-1, B, T, d)  # noqa: E731
    q = rope(m.norm(heads(m.dot(a, p["q"]), nh), p["q_norm"], eps))
    k = rope(m.norm(heads(m.dot(a, p["k"]), nkv), p["k_norm"], eps))
    v = heads(m.dot(a, p["v"]), nkv)
    Q = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T
    kj = jnp.arange(T)[None, :]

    def one_head(qh, kh, vh):  # [B, T, d] each

        @jax.checkpoint
        def block(qb, i0):  # Q queries from index i0 against every key
            seen = visible(i0 + jnp.arange(Q)[:, None], kj, c)
            s = m.dot(qb, kh, "btd,bsd->bts") / jnp.sqrt(jnp.asarray(d, m.dtype))
            return m.dot(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), vh, "bts,bsd->btd")

        ob = jax.lax.map(lambda x: block(*x), (qh.reshape(B, T // Q, Q, d).transpose(1, 0, 2, 3),
                                               jnp.arange(0, T, Q)))
        return ob.transpose(1, 0, 2, 3).reshape(B, T, d)

    group = nh // nkv
    outs = [jax.lax.map(lambda qh, g=g: one_head(qh, k[g], v[g]), q[g * group:(g + 1) * group])
            for g in range(nkv)]  # query heads g * group .. + group read key-value head g
    o = jnp.concatenate(outs).transpose(1, 2, 0, 3).reshape(B, T, nh * d)
    return m.dot(o, p["o"])


def experts_part(p, x, chosen, w, c, m: _Math):
    """x [N, H], routed as (chosen, w) [N, 8] -> the held experts' weighted outputs."""
    off, held = c["experts_offset"], c["num_experts"]
    y = jnp.zeros_like(x)
    for e in range(held):  # expert off + e on every token, times the token's weight for it
        w_e = jnp.sum(jnp.where(chosen == off + e, w, 0.0), axis=1, keepdims=True)
        pe = jax.tree.map(lambda a, e=e: a[e], p["experts"])
        y = y + jax.checkpoint(lambda pe, x, w_e: glu(pe, x, m) * w_e.astype(m.dtype))(pe, x, w_e)
    return y


def layer(p, x, c, m: _Math):
    B, T, H = x.shape
    eps = c["rms_norm_eps"]
    x = x + attention(p["attn"], m.norm(x, p["ln_in"], eps), c, m)
    flat = m.norm(x, p["ln_post_attn"], eps).reshape(B * T, H)
    chosen, w = route(p, flat, c, m)
    return x + experts_part(p, flat, chosen, w, c, m).reshape(B, T, H), chosen.reshape(B, T, -1)


def forward(params: dict, emb, ids, cfg: dict, dtype=jnp.float32, record_weight=None):
    """emb [B, 2 L, H] token rows, ids [B, 2 L] (clean, then noised) -> (loss,
    {"parts": [target positions i < L / 2, the others], "token_logits": [2, B,
    L] (the target's logit, the logsumexp, of every noisy position),
    "router_choices": [layers, B, 2 L, k]}). ``record_weight`` [B] leaves
    records out of the mean."""
    m = _Math(dtype, jnp.dtype(cfg["matmul_dtype"]))
    B, T, H = emb.shape
    L, n = cfg["data_len"], cfg["block_length"]
    ids = ids.astype(jnp.int32)
    x, choices = jax.lax.scan(  # the layers are alike: one body over their stacked weights
        jax.checkpoint(lambda x, p: layer(p, x, cfg, m)), emb.astype(dtype), params["layers"])
    w = jnp.ones((B,), jnp.float32) if record_weight is None else jnp.asarray(record_weight)
    t, l = head_terms(params["head"], m.norm(x[:, L:], params["final_norm"], cfg["rms_norm_eps"]
                                             ).reshape(B * L, H), ids[:, :L].reshape(-1), m)
    t, l = t.reshape(B, L), l.reshape(B, L)
    masked = (ids[:, L:] == cfg["mask_id"]).reshape(B, L // n, n)
    count = jnp.sum(masked, axis=-1, keepdims=True)  # at least 1 by the generator
    weight = jnp.where(masked, n / jnp.maximum(count, 1), 0.0).reshape(B, L)
    # the weighted sums, in the pass's own precision
    nll = (l - t) * (weight * w[:, None]).astype(l.dtype)
    share = lambda lo, hi: (jnp.sum(nll[:, lo:hi])  # noqa: E731
                            / (jnp.sum(w) * (hi - lo)).astype(l.dtype)).astype(jnp.float32)
    out = {"parts": jnp.stack([share(0, L // 2), share(L // 2, L)]),
           "token_logits": jnp.stack([t, l]).astype(jnp.float32), "router_choices": choices}
    return share(0, L), jax.tree.map(jax.lax.stop_gradient, out)
