"""One plain training step of a token pass, and the loop over a superstep's
batches: pull the batch's token rows (show, clk, embed_w, embedx), hand the
embedx block to the language model as [B, T, hidden] in record order with
the ids as targets, gradients, sparse adagrad push with show counters (clk
0: the label is unused), dense Adam with the configuration's linear warm-up. Rows come from ``table_init``'s per-key
rule, dense weights from the kind's ``init``, batches from the generator's
ids; nothing of the program is imported.

float32 with the configuration's bfloat16 matrix-product operands;
``dtype=bfloat16`` is the lower-precision control (table, parameters and
every operation in bfloat16), ``half_batch`` the planted fault (the second
half of every batch's records left out of loss, gradient and counters).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import table_init
from benchmark.reference.step import _round_up, localize, row_layout

KEY_BASE = 10**12  # key = KEY_BASE + token id (benchmark/gen_tokens.py)


def make_step(forward: Callable, cfg: dict, dtype=jnp.float32, half_batch: bool = False):
    lay, so, ad = row_layout(cfg), cfg["sparse_opt"], cfg["dense_opt"]
    B, T, D = int(cfg["batch_size"]), int(cfg["seq_len"]), lay["D"]
    weight = np.ones(B, np.float32)
    if half_batch:
        weight[B // 2:] = 0.0

    def loss_fn(params, pulled, inverse, ids):
        emb = pulled[inverse][:, 3:].reshape(B, T, D)
        return forward(params, emb, ids, cfg, dtype, record_weight=weight)

    def step(state, rows, inverse, ids):
        table, params, mu, nu, t = state
        old = table[rows]
        live = old[:, 0:1] >= so["embedx_threshold"]
        pulled = jnp.concatenate([old[:, :3], jnp.where(live, old[:, 3:3 + D], 0.0)], axis=1)
        (loss, out), (gp, gu) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
            params, pulled, inverse, ids)
        occ_w = jnp.repeat(jnp.asarray(weight, old.dtype), T)
        n = rows.shape[0]
        show = old[:, 0] + jax.ops.segment_sum(occ_w, inverse, num_segments=n)
        ig = so["initial_g2sum"]
        gw = gu[:, 2:3]
        g2_e = old[:, lay["g2_e"]] + jnp.sum(gw * gw, axis=1)
        new_w = old[:, 2:3] - (so["embed_lr"] * jnp.sqrt(ig / (ig + g2_e)))[:, None] * gw
        gx = jnp.where(live, gu[:, 3:3 + D], 0.0)
        g2_x = old[:, lay["g2_x"]] + jnp.mean(gx * gx, axis=1)
        new_x = old[:, 3:3 + D] - (so["embedx_lr"] * jnp.sqrt(ig / (ig + g2_x)))[:, None] * gx
        wb = so["weight_bounds"]
        new = jnp.concatenate(
            [show[:, None], old[:, 1:2], jnp.clip(new_w, -wb, wb), jnp.clip(new_x, -wb, wb),
             g2_e[:, None], g2_x[:, None]], axis=1)
        table = table.at[rows].set(new.astype(table.dtype))

        t = t + 1
        b1, b2 = ad["b1"], ad["b2"]
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, gp)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, gp)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        lr = ad["lr"] * jnp.minimum(1.0, t / ad["warmup_steps"])  # t counts from 1
        params = jax.tree.map(
            lambda p, m, v: (p - lr * (m / c1) / (jnp.sqrt(v / c2) + ad["eps"])
                             ).astype(p.dtype), params, mu, nu)
        return (table, params, mu, nu, t), (loss, out)

    return jax.jit(step, donate_argnums=(0,))


def run_steps(forward: Callable, weights, cfg: dict, table_seed: int, ids: np.ndarray,
              sample_keys: np.ndarray, dtype=jnp.float32, half_batch: bool = False) -> dict:
    """Follow ``ids`` [n_steps, B, T] from the seed's state. ``weights`` are
    taken over (the step donates them: a second copy of 2.7 GB of parameters
    would not fit beside gradients and Adam's moments). Returns what the
    comparison reads: each step's loss and its two parts, step 1's per-token
    logit terms and expert choices, the sampled keys' rows before and after,
    the dense leaves before and after, Adam's first moment."""
    lay = row_layout(cfg)
    keys = (np.asarray(ids, np.int64) + KEY_BASE).astype(np.uint64)
    uniq, rows, inverse = localize(keys.reshape(keys.shape[0], keys.shape[1], -1))
    open_rows = table_init.init_rows(
        uniq, table_seed, lay["width"], lay["init_cols"], cfg["sparse_opt"]["initial_range"])
    spare = _round_up(len(uniq) + 1, 1024) - len(uniq)  # zero rows; the first is the pad's
    table = jnp.concatenate(
        [jnp.asarray(open_rows), jnp.zeros((spare, lay["width"]))]).astype(dtype)
    as_f32 = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float32), tree)  # noqa: E731
    open_params = as_f32(weights)
    params = weights if dtype == jnp.float32 else jax.tree.map(lambda w: w.astype(dtype), weights)
    del weights
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)  # noqa: E731
    state = (table, params, zeros(), zeros(), 0)
    del params, table
    step = make_step(forward, cfg, dtype, half_batch)
    losses, parts, first = [], [], None
    for i in range(len(ids)):
        state, (loss, out) = step(state, jnp.asarray(rows[i]), jnp.asarray(inverse[i]),
                                  jnp.asarray(ids[i], jnp.int32))
        losses.append(float(loss))
        parts.append(np.asarray(out["parts"], np.float64))
        if first is None:
            first = {"token_logits": np.asarray(out["token_logits"], np.float32),
                     "router_choices": np.asarray(out["router_choices"])}
    at = np.searchsorted(uniq, sample_keys)
    return {
        "losses": np.asarray(losses, np.float64), "parts": np.stack(parts), **first,
        "open_rows": open_rows[at],
        "rows": np.asarray(state[0][at], np.float32),
        "open_params": open_params,
        "params": as_f32(state[1]),
        "mu": as_f32(state[2]),
    }
