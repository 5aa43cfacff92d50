"""GLM-4.7-Flash (``glm4_moe_lite``), plain: written from the published
config's keys and the equations of DeepSeek-V2/V3 that the architecture
follows; nothing of ``paddlebox_tpu/models/`` is imported.

x is the float32 residual stream [B, T, hidden]; RMSNorm (eps
``rms_norm_eps``), pre-norm residual blocks.

- Latent attention, per head: ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` (per
  head nope | rope); ``[c_kv | k_r] = x W_kva``, ``c_kv = norm(c_kv)``,
  ``[k_nope | v] = c_kv W_kvb``; rope (theta ``rope_theta``, halves paired)
  on ``q_rope`` and on the one ``k_r`` all heads share; scores
  ``q.k / sqrt(nope + rope)``, causal softmax inside the record, ``W_o``.
- Layer 0: SwiGLU ``W_down(silu(x W_gate) * (x W_up))``.
- Expert layers: ``s = sigmoid(x W_r)`` in float32; the top k of ``s + b``;
  weights ``s[chosen] / sum(s[chosen]) * routed_scaling_factor``; the shared
  expert plus, for every expert this instance holds
  (``experts_offset .. + experts_held``), its SwiGLU on every token times
  the token's weight for it (zero where it was not chosen): a dense loop
  with a mask. What the absent experts would add is left out. As in the
  configuration file, ``n_routed_experts`` counts the experts held and
  ``router_experts`` the router's outputs (the published 64).
- Head: final norm, ``W_head``, float32 cross-entropy against the id at i+1.
- MTP, depth 1: ``[norm(emb(t_{i+1})) | norm(h_i)] W_eh``, one expert layer,
  its norm, the same head, cross-entropy against the id at i+2; the loss is
  ``main + mtp_loss_weight * mtp``.

Matrix products take their operands in ``mm_dtype`` (bfloat16 as the
configuration states) and accumulate in float32 at ``highest``; everything
else is in ``dtype`` (float32; bfloat16 for the lower-precision control,
which computes the products in bfloat16 too). Heads, experts and blocks of
head positions are recomputed in the backward so that the published widths
fit one chip; no kernel is used.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _attn_init(key, c):
    H, nh = c["hidden_size"], c["num_attention_heads"]
    dq = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    k = jax.random.split(key, 5)
    std = c["initializer_range"]
    return {
        "q_a": _normal(k[0], (H, c["q_lora_rank"]), std),
        "q_a_norm": jnp.ones((c["q_lora_rank"],)),
        "q_b": _normal(k[1], (c["q_lora_rank"], nh * dq), std),
        "kv_a": _normal(k[2], (H, c["kv_lora_rank"] + c["qk_rope_head_dim"]), std),
        "kv_a_norm": jnp.ones((c["kv_lora_rank"],)),
        "kv_b": _normal(k[3], (c["kv_lora_rank"], nh * (c["qk_nope_head_dim"] + c["v_head_dim"])),
                        std),
        "o": _normal(k[4], (nh * c["v_head_dim"], H), std),
    }


def _glu_init(key, c, width, lead=()):
    H, k, std = c["hidden_size"], jax.random.split(key, 3), c["initializer_range"]
    return {"gate": _normal(k[0], lead + (H, width), std), "up": _normal(k[1], lead + (H, width), std),
            "down": _normal(k[2], lead + (width, H), std)}


def _layer_init(key, c, moe: bool):
    H, k, std = c["hidden_size"], jax.random.split(key, 5), c["initializer_range"]
    p = {"attn": _attn_init(k[0], c), "ln1": jnp.ones((H,)), "ln2": jnp.ones((H,))}
    if not moe:
        p["mlp"] = _glu_init(k[1], c, c["intermediate_size"])
        return p
    p["router"] = {"w": _normal(k[1], (H, c["router_experts"]), std),
                   "bias": _normal(k[2], (c["router_experts"],), std)}
    p["shared"] = _glu_init(k[3], c, c["moe_intermediate_size"])
    p["experts"] = _glu_init(k[4], c, c["moe_intermediate_size"], lead=(c["n_routed_experts"],))
    return p


def init(key, cfg: dict, feat_width: int) -> dict:
    """Dense leaves from the seed: normal(0, initializer_range) matrices, norms
    of ones, the router's correction bias a seeded buffer."""
    H = cfg["hidden_size"]
    if feat_width != 3 + H:
        raise ValueError(f"the token rows' embedx is the hidden size: {feat_width} != 3 + {H}")
    n, first = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    k = jax.random.split(key, n + 3)
    moe = [_layer_init(k[i], cfg, True) for i in range(first, n)]
    return {
        "dense": [_layer_init(k[i], cfg, False) for i in range(first)],
        "moe": jax.tree.map(lambda *a: jnp.stack(a), *moe),
        "final_norm": jnp.ones((H,)),
        "head": _normal(k[n], (H, cfg["vocab_size"]), cfg["initializer_range"]),
        "mtp": {"enorm": jnp.ones((H,)), "hnorm": jnp.ones((H,)),
                "eh_proj": _normal(k[n + 1], (2 * H, H), cfg["initializer_range"]),
                "block": _layer_init(k[n + 2], cfg, True), "norm": jnp.ones((H,))},
    }


# a product's einsum -> (its first operand's cotangent from (g, second), its second's from (first, g))
_TRANSPOSED = {
    "...k,kn->...n": ("...n,kn->...k", "...k,...n->kn"),
    "btd,bsd->bts": ("bts,bsd->btd", "btd,bts->bsd"),
    "bts,bsd->btd": ("btd,bsd->bts", "bts,btd->bsd"),
}


class _Math:
    """The two precisions of one forward and backward pass: ``dtype`` for
    everything but the operands of a matrix product, which are cast to
    ``mm_dtype``, in the backward pass too (the cotangent is an operand of
    both of a product's transposes), and accumulate in float32."""

    def __init__(self, dtype, mm_dtype):
        self.dtype = dtype
        self.mm = mm_dtype if dtype == jnp.float32 else dtype
        self.acc = jnp.float32 if dtype == jnp.float32 else dtype

    def dot(self, a, b, spec=None):
        """``a @ b`` over the last axis of a and the first of b, or one of the
        attention's two einsums."""
        spec = spec or "...k,kn->...n"
        to_a, to_b = _TRANSPOSED[spec]
        mm, acc = self.mm, self.acc

        def einsum(sp, x, y):
            return jnp.einsum(sp, x.astype(mm), y.astype(mm), precision=HI,
                              preferred_element_type=acc)

        @jax.custom_vjp
        def product(a, b):
            return einsum(spec, a, b)

        def fwd(a, b):
            return product(a, b), (a, b)

        def bwd(res, g):  # both transposes, their operands in mm like the product's own
            a, b = res
            return einsum(to_a, g, b).astype(a.dtype), einsum(to_b, a, g).astype(b.dtype)

        product.defvjp(fwd, bwd)
        return product(a, b).astype(self.dtype)

    def norm(self, x, w, eps):
        x = x.astype(self.dtype)
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x / jnp.sqrt(ms + eps) * w.astype(self.dtype)


def _rope(x, theta):
    """x [..., T, d]: rotate the pairs (i, i + d/2) by position / theta^(2i/d)."""
    T, d = x.shape[-2], x.shape[-1]
    freq = (1.0 / np.power(float(theta), np.arange(0, d, 2) / d)).astype(np.float32)  # float64 first
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    lo, hi = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def attention(p, x, c, m: _Math):
    B, T, _ = x.shape
    nh, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    r = c["kv_lora_rank"]
    c_q = m.norm(m.dot(x, p["q_a"]), p["q_a_norm"], c["rms_norm_eps"])
    q = m.dot(c_q, p["q_b"]).reshape(B, T, nh, dn + dr).transpose(2, 0, 1, 3)  # [h, B, T, .]
    ckv = m.dot(x, p["kv_a"])
    c_kv = m.norm(ckv[..., :r], p["kv_a_norm"], c["rms_norm_eps"])
    k_r = _rope(ckv[..., r:], c["rope_theta"])  # [B, T, dr], shared by the heads
    kv = m.dot(c_kv, p["kv_b"]).reshape(B, T, nh, dn + dv).transpose(2, 0, 1, 3)
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(qh, kvh):
        s = (m.dot(qh[..., :dn], kvh[..., :dn], "btd,bsd->bts")
             + m.dot(_rope(qh[..., dn:], c["rope_theta"]), k_r, "btd,bsd->bts"))
        s = jnp.where(causal, s / jnp.sqrt(jnp.asarray(dn + dr, s.dtype)), -jnp.inf)
        return m.dot(jax.nn.softmax(s, axis=-1), kvh[..., dn:], "bts,bsd->btd")

    o = jax.lax.map(lambda a: head(*a), (q, kv))  # [h, B, T, dv]
    return m.dot(o.transpose(1, 2, 0, 3).reshape(B, T, nh * dv), p["o"])


def glu(p, x, m: _Math):
    return m.dot(jax.nn.silu(m.dot(x, p["gate"])) * m.dot(x, p["up"]), p["down"])


def experts_part(p, x, c, m: _Math):
    """x [N, H] -> (shared expert + the held experts' weighted outputs, chosen [N, k])."""
    k, off, held = c["num_experts_per_tok"], c["experts_offset"], c["n_routed_experts"]
    # float32 at highest; the lower-precision control routes in its own precision
    s = jax.nn.sigmoid(jnp.matmul(x.astype(m.dtype), p["router"]["w"].astype(m.dtype), precision=HI))
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(p["router"]["bias"]).astype(s.dtype), k)
    picked = jnp.take_along_axis(s, chosen, axis=1)
    w = picked / jnp.sum(picked, axis=1, keepdims=True) * c["routed_scaling_factor"]
    y = glu(p["shared"], x, m)

    @jax.checkpoint
    def one(pe, e):  # expert e on every token, times the token's weight for it
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1, keepdims=True)
        return glu(pe, x, m) * w_e.astype(m.dtype)

    each = jax.lax.map(lambda a: one(*a), (p["experts"], off + jnp.arange(held)))
    return y + jnp.sum(each, axis=0), chosen


def dense_block(p, x, c, m):
    h = x + attention(p["attn"], m.norm(x, p["ln1"], c["rms_norm_eps"]), c, m)
    return h + glu(p["mlp"], m.norm(h, p["ln2"], c["rms_norm_eps"]), m)


def expert_block(p, x, c, m):
    B, T, H = x.shape
    h = x + attention(p["attn"], m.norm(x, p["ln1"], c["rms_norm_eps"]), c, m)
    y, chosen = experts_part(p, m.norm(h, p["ln2"], c["rms_norm_eps"]).reshape(B * T, H), c, m)
    return h + y.reshape(B, T, H), chosen.reshape(B, T, -1)


def head_terms(head, h, targets, m: _Math, block: int = 1024):
    """h [N, H] -> (logit of the target, logsumexp of all logits), [N] each."""
    N = h.shape[0]
    blk = block if N % block == 0 else N

    @jax.checkpoint
    def one(hb, tb):
        z = m.dot(hb, head).astype(jnp.float32 if m.dtype == jnp.float32 else m.dtype)
        return z[jnp.arange(blk), tb], jax.nn.logsumexp(z, axis=-1)

    t, l = jax.lax.map(lambda a: one(*a), (h.reshape(N // blk, blk, -1), targets.reshape(-1, blk)))
    return t.reshape(N), l.reshape(N)


def forward(params: dict, emb, ids, cfg: dict, dtype=jnp.float32, record_weight=None):
    """emb [B, T, H] token rows, ids [B, T] -> (loss, {"parts": [main, mtp],
    "token_logits": [4, B, T] (main target logit, MTP's, main logsumexp,
    MTP's), "router_choices": [layers + 1, B, T, k]}). ``record_weight`` [B]
    leaves records out of the mean (the planted fault)."""
    m = _Math(dtype, jnp.dtype(cfg["matmul_dtype"]))
    B, T, H = emb.shape
    ids = ids.astype(jnp.int32)
    x = emb.astype(dtype)
    for p in params["dense"]:
        x = jax.checkpoint(lambda p, x: dense_block(p, x, cfg, m))(p, x)
    # the expert layers are alike: one body over their stacked weights
    x, choices = jax.lax.scan(
        jax.checkpoint(lambda x, p: expert_block(p, x, cfg, m)), x, params["moe"])
    choices = list(choices)
    mt = params["mtp"]
    nxt = jnp.concatenate([emb[:, 1:], jnp.zeros_like(emb[:, :1])], axis=1).astype(dtype)

    def mtp_block(mt, x, nxt):
        cat = jnp.concatenate([m.norm(nxt, mt["enorm"], cfg["rms_norm_eps"]),
                               m.norm(x, mt["hnorm"], cfg["rms_norm_eps"])], axis=-1)
        return expert_block(mt["block"], m.dot(cat, mt["eh_proj"]), cfg, m)

    xm, ch = jax.checkpoint(mtp_block)(mt, x, nxt)
    choices.append(ch)
    w = jnp.ones((B,), jnp.float32) if record_weight is None else jnp.asarray(record_weight)
    parts, terms = [], []
    for depth, (h, norm) in enumerate(((x, params["final_norm"]), (xm, mt["norm"])), start=1):
        tgt = jnp.concatenate([ids[:, depth:], jnp.zeros((B, depth), jnp.int32)], axis=1)
        t, l = head_terms(params["head"], m.norm(h, norm, cfg["rms_norm_eps"]).reshape(B * T, H),
                          tgt.reshape(-1), m)
        t, l = t.reshape(B, T), l.reshape(B, T)
        has = (jnp.arange(T) < T - depth).astype(l.dtype)
        nll = (l - t) * has * w[:, None].astype(l.dtype)  # and its mean, in the pass's own precision
        parts.append((jnp.sum(nll) / (jnp.sum(w) * (T - depth)).astype(l.dtype)).astype(jnp.float32))
        terms.append((t, l))
    loss = parts[0] + cfg["mtp_loss_weight"] * parts[1]
    out = {"parts": jnp.stack(parts),
           "token_logits": jnp.stack([terms[0][0], terms[1][0], terms[0][1], terms[1][1]]
                                     ).astype(jnp.float32),
           "router_choices": jnp.stack(choices)}
    return loss, jax.tree.map(jax.lax.stop_gradient, out)
