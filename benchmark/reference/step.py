"""One plain training step, shared by every model kind: pull the batch's
rows, CVM on the show/click columns (one key a slot, so the sequence pool of a
slot is its one record), the model's forward, mean sigmoid cross-entropy,
gradients, sparse adagrad push with show/click counters, dense Adam.

float32 throughout with ``highest`` matmul precision; ``dtype=bfloat16`` is
the lower-precision control, ``half_batch`` the planted fault (the second
half of every batch left out, the mean taken over the rest). Imports nothing
of the program and takes nothing the program made: rows come from
``table_init``, dense weights from the kind's ``init``, batches from the
generator's arrays.
"""

from __future__ import annotations

from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import table_init


def row_layout(cfg: dict) -> dict:
    """Columns of a PLAIN row: show, clk, embed_w, embedx[D], g2 of embed_w,
    g2 of embedx."""
    D = int(cfg["embedx_dim"])
    return {"D": D, "pull": 3 + D, "width": 5 + D, "g2_e": 3 + D, "g2_x": 4 + D,
            "init_cols": list(range(2, 3 + D))}


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def localize(keys: np.ndarray):
    """keys uint64 [n_steps, B, S] -> (sorted distinct keys, per step the
    padded distinct local rows [U_pad] and each occurrence's position in them
    [B * S]). The pad entry is row ``len(distinct)``: a spare zero row. U_pad
    is rounded up, so that seeds share a compiled step."""
    uniq_all, local = np.unique(keys, return_inverse=True)
    local = local.reshape(keys.shape[0], -1)
    per_step = [np.unique(step, return_inverse=True) for step in local]
    u_pad = _round_up(max(len(u) for u, _ in per_step), 8192)
    rows = np.full((len(per_step), u_pad), len(uniq_all), np.int32)
    for i, (u, _) in enumerate(per_step):
        rows[i, : len(u)] = u
    inverse = np.stack([inv for _, inv in per_step]).astype(np.int32)
    return uniq_all, rows, inverse


def make_step(forward: Callable, cfg: dict, dtype=jnp.float32,
              half_batch: bool = False) -> Callable:
    lay, so, ad = row_layout(cfg), cfg["sparse_opt"], cfg["dense_opt"]
    B, S, D = int(cfg["batch_size"]), int(cfg["num_slots"]), lay["D"]
    tower = jnp.dtype(cfg["tower_dtype"]) if cfg.get("tower_dtype") else None
    weight = np.ones(B, np.float32)
    if half_batch:
        weight[B // 2:] = 0.0

    def loss_fn(params, pulled, inverse, labels):
        feats = pulled[inverse].reshape(B, S, lay["pull"])
        log_show = jnp.log(feats[..., 0:1] + 1.0)
        x = jnp.concatenate(
            [log_show, jnp.log(feats[..., 1:2] + 1.0) - log_show, feats[..., 2:]], -1)
        z = forward(params, x, cfg, dtype, tower)
        y = labels.astype(z.dtype)
        per = jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        w = jnp.asarray(weight, z.dtype)
        return jnp.sum(per * w) / jnp.sum(w)

    def step(state, rows, inverse, labels):
        table, params, mu, nu, t = state
        old = table[rows]
        live = old[:, 0:1] >= so["embedx_threshold"]
        pulled = jnp.concatenate(
            [old[:, :3], jnp.where(live, old[:, 3:3 + D], 0.0)], axis=1)
        loss, (gp, gu) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, pulled, inverse, labels)
        occ_w = jnp.repeat(jnp.asarray(weight, old.dtype), S)
        occ_y = jnp.repeat(labels.astype(old.dtype), S) * occ_w
        n = rows.shape[0]
        show = old[:, 0] + jax.ops.segment_sum(occ_w, inverse, num_segments=n)
        clk = old[:, 1] + jax.ops.segment_sum(occ_y, inverse, num_segments=n)
        ig = so["initial_g2sum"]
        gw = gu[:, 2:3]
        g2_e = old[:, lay["g2_e"]] + jnp.sum(gw * gw, axis=1)
        new_w = old[:, 2:3] - (so["embed_lr"] * jnp.sqrt(ig / (ig + g2_e)))[:, None] * gw
        gx = jnp.where(live, gu[:, 3:3 + D], 0.0)
        g2_x = old[:, lay["g2_x"]] + jnp.mean(gx * gx, axis=1)
        new_x = old[:, 3:3 + D] - (so["embedx_lr"] * jnp.sqrt(ig / (ig + g2_x)))[:, None] * gx
        wb = so["weight_bounds"]
        new = jnp.concatenate(
            [show[:, None], clk[:, None], jnp.clip(new_w, -wb, wb),
             jnp.clip(new_x, -wb, wb), g2_e[:, None], g2_x[:, None]], axis=1)
        table = table.at[rows].set(new.astype(table.dtype))

        t = t + 1
        b1, b2 = ad["b1"], ad["b2"]
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, gp)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, gp)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        params = jax.tree.map(
            lambda p, m, v: (p - ad["lr"] * (m / c1) / (jnp.sqrt(v / c2) + ad["eps"])
                             ).astype(p.dtype), params, mu, nu)
        return (table, params, mu, nu, t), loss

    return jax.jit(step, donate_argnums=(0,))


def run_steps(forward: Callable, weights, cfg: dict, table_seed: int,
              keys: np.ndarray, labels: np.ndarray, sample_keys: np.ndarray,
              dtype=jnp.float32, half_batch: bool = False) -> dict:
    """Follow ``keys`` [n_steps, B, S] / ``labels`` [n_steps, B] from the
    seed's state. Returns what the comparison reads: each step's loss, the
    sampled keys' rows before and after, the dense leaves before and after,
    Adam's first moment."""
    lay = row_layout(cfg)
    uniq, rows, inverse = localize(keys)
    open_rows = table_init.init_rows(
        uniq, table_seed, lay["width"], lay["init_cols"], cfg["sparse_opt"]["initial_range"])
    spare = _round_up(len(uniq) + 1, 65536) - len(uniq)  # zero rows; the first is the pad's
    table = jnp.concatenate(
        [jnp.asarray(open_rows), jnp.zeros((spare, lay["width"]))]).astype(dtype)
    params = jax.tree.map(lambda w: jnp.array(w, dtype), weights)  # a copy: the step donates
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    state = (table, params, zeros(), zeros(), 0)
    step = make_step(forward, cfg, dtype, half_batch)
    losses: List[float] = []
    for i in range(len(keys)):
        state, loss = step(state, jnp.asarray(rows[i]), jnp.asarray(inverse[i]),
                           jnp.asarray(labels[i], jnp.float32))
        losses.append(float(loss))
    at = np.searchsorted(uniq, sample_keys)
    as_f32 = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float32), tree)  # noqa: E731
    return {
        "losses": np.asarray(losses, np.float64),
        "open_rows": open_rows[at],
        "rows": np.asarray(state[0][at], np.float32),
        "open_params": as_f32(weights),
        "params": as_f32(state[1]),
        "mu": as_f32(state[2]),
    }
