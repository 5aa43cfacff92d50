"""Deep & Cross, plain: x0 = the flattened slot features; n_cross rank-one
crosses x <- x0 * (x . w) + b + x; a ReLU tower over x0; one linear head over
[x ; tower]. float32 at ``highest`` matmul precision unless ``dtype`` says
otherwise (the lower-precision control); the tower's weights and activations
in ``tower_dtype`` where the configuration states one."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def init(key, cfg: dict, feat_width: int) -> dict:
    d = cfg["num_slots"] * feat_width
    hidden = list(cfg["hidden"])
    ks = jax.random.split(key, cfg["n_cross"] + len(hidden) + 1)
    dims = [d] + hidden
    return {
        "cross_w": [jax.random.normal(ks[i], (d,)) * d ** -0.5
                    for i in range(cfg["n_cross"])],
        "cross_b": [jnp.zeros((d,)) for _ in range(cfg["n_cross"])],
        "mlp": [
            {"w": jax.random.normal(ks[cfg["n_cross"] + i], (dims[i], dims[i + 1]))
             * (2.0 / (dims[i] + dims[i + 1])) ** 0.5,
             "b": jnp.zeros((dims[i + 1],))}
            for i in range(len(hidden))
        ],
        "out": {"w": jax.random.normal(ks[-1], (hidden[-1] + d, 1))
                * (2.0 / (hidden[-1] + d + 1)) ** 0.5,
                "b": jnp.zeros((1,))},
    }


def forward(params: dict, slot_feats, cfg: dict, dtype=jnp.float32, tower_dtype=None):
    """slot_feats [B, S, F] -> logits [B]."""
    x0, td = slot_feats.reshape(slot_feats.shape[0], -1).astype(dtype), tower_dtype or dtype
    x = x0
    for w, b in zip(params["cross_w"], params["cross_b"]):
        x = x0 * jnp.dot(x, w.astype(dtype), precision=HI)[:, None] + b.astype(dtype) + x
    h = x0.astype(td)
    for lay in params["mlp"]:
        h = jax.nn.relu(jnp.dot(h, lay["w"].astype(td), precision=HI) + lay["b"].astype(td))
    fused = jnp.concatenate([x, h.astype(dtype)], axis=1)
    out = params["out"]
    return (jnp.dot(fused, out["w"].astype(dtype), precision=HI)
            + out["b"].astype(dtype))[:, 0]
