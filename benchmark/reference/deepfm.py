"""DeepFM, plain: first order = the embed_w column summed over slots; FM
second order over the embedx block, 0.5 * ((sum v)^2 - sum v^2); a ReLU tower
over the flattened slot features with a linear head; a scalar bias. float32
at ``highest`` matmul precision unless ``dtype`` says otherwise; the tower's
weights and activations in ``tower_dtype`` where the configuration states one."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def init(key, cfg: dict, feat_width: int) -> dict:
    hidden = list(cfg["hidden"])
    dims = [cfg["num_slots"] * feat_width] + hidden
    ks = jax.random.split(key, len(hidden) + 1)
    return {
        "mlp": [
            {"w": jax.random.normal(ks[i], (dims[i], dims[i + 1]))
             * (2.0 / (dims[i] + dims[i + 1])) ** 0.5,
             "b": jnp.zeros((dims[i + 1],))}
            for i in range(len(hidden))
        ],
        "out": {"w": jax.random.normal(ks[-1], (hidden[-1], 1))
                * (2.0 / (hidden[-1] + 1)) ** 0.5,
                "b": jnp.zeros((1,))},
        "b": jnp.zeros(()),
    }


def forward(params: dict, slot_feats, cfg: dict, dtype=jnp.float32, tower_dtype=None):
    """slot_feats [B, S, F] = [log show, log ctr, embed_w, embedx...] -> logits [B]."""
    f, td = slot_feats.astype(dtype), tower_dtype or dtype
    D = cfg["embedx_dim"]
    first = jnp.sum(f[:, :, 2], axis=1)
    v = f[:, :, f.shape[2] - D:]
    sum_v = jnp.sum(v, axis=1)
    fm = 0.5 * jnp.sum(sum_v * sum_v - jnp.sum(v * v, axis=1), axis=1)
    h = f.reshape(f.shape[0], -1).astype(td)
    for lay in params["mlp"]:
        h = jax.nn.relu(jnp.dot(h, lay["w"].astype(td), precision=HI) + lay["b"].astype(td))
    h, out = h.astype(dtype), params["out"]
    deep = (jnp.dot(h, out["w"].astype(dtype), precision=HI) + out["b"].astype(dtype))[:, 0]
    return params["b"].astype(dtype) + first + fm + deep
