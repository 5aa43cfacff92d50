"""The comparison that decides ``correct`` for a training cell: what the timed
path's first superstep left behind against the plain reference's. Every number
is a gap, smaller is better, held to a limit from ``benchmark/limits/``.

- ``open_rows_gap``: the pass-open rows of the sampled keys, largest
  difference as a share of the initial range.
- ``early_loss_gap``: the loss of steps 1 and 2, largest relative difference.
  Step 2's loss is the first that the state's change shows in, and round-off
  has not yet grown: it reads the same from seed to seed. ``loss_gap``, over
  all the steps, swings thirty times between seeds (a gap of 1e-7 at step 2
  grows three to ten times a step) and is read but given no limit.
- ``counter_gap``: show and click counters of the sampled rows, largest
  absolute difference (whole numbers in float32: exact).
- ``sparse_grad_gap``: the gradient as sparse adagrad got it, read from its
  g2 sums; ``sparse_delta_gap``: the embeddings' change. Leaves: the embed_w
  column and the embedx block of the sampled rows.
- ``dense_grad_gap``: Adam's first moment, by the median leaf: the worst leaf
  is a cross layer's weight or a bias whose gradient is a sum of terms that
  cancel, and swings a thousand times between seeds
  (``dense_grad_gap_worst_leaf``: read, no limit). ``dense_delta_gap``: the
  dense parameters' change, by the worst leaf.

A gap of norms is |program's norm - reference's norm| over the reference's
norm of that leaf or of the median leaf, whichever is larger. Leaves whose
reference gradient is under a thousandth of the median leaf's are left out of
``dense_delta_gap``: Adam moves them by round-off alone.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import numpy as np


def _norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))


EARLY_STEPS = 2


def _leaf_gaps(prog: list, ref: list, keep=None) -> list:
    floor = float(np.median(ref)) if ref else 0.0
    out = []
    for i, (p, r) in enumerate(zip(prog, ref)):
        if keep is not None and not keep[i]:
            continue
        denom = max(r, floor)
        out.append(abs(p - r) / denom if denom > 0 else (0.0 if p == 0 else math.inf))
    return out


def _worst(prog: list, ref: list, keep=None) -> float:
    return max(_leaf_gaps(prog, ref, keep), default=0.0)


def gaps(prog: dict, ref: dict, cfg: dict) -> Dict[str, float]:
    D, r = int(cfg["embedx_dim"]), float(cfg["sparse_opt"]["initial_range"])
    pr, rr, ro = prog["rows"], ref["rows"], ref["open_rows"]
    loss_gaps = np.abs(prog["losses"] - ref["losses"]) / np.abs(ref["losses"])
    out = {
        "open_rows_gap": float(np.max(np.abs(prog["open_rows"] - ro))) / r,
        "early_loss_gap": float(np.max(loss_gaps[:EARLY_STEPS])),
        "loss_gap": float(np.max(loss_gaps)),
        "counter_gap": float(np.max(np.abs(pr[:, :2] - rr[:, :2]))),
    }
    # g2_e is a sum of squares and g2_x a mean of squares: their roots' norms
    out["sparse_grad_gap"] = _worst(
        [math.sqrt(max(float(np.sum(pr[:, c], dtype=np.float64)), 0.0)) for c in (3 + D, 4 + D)],
        [math.sqrt(max(float(np.sum(rr[:, c], dtype=np.float64)), 0.0)) for c in (3 + D, 4 + D)])
    blocks = (slice(2, 3), slice(3, 3 + D))
    out["sparse_delta_gap"] = _worst(
        [_norm(pr[:, b] - prog["open_rows"][:, b]) for b in blocks],
        [_norm(rr[:, b] - ro[:, b]) for b in blocks])
    leaves = jax.tree.leaves
    mu_ref = [_norm(a) for a in leaves(ref["mu"])]
    mu_gaps = _leaf_gaps([_norm(a) for a in leaves(prog["mu"])], mu_ref)
    out["dense_grad_gap"] = float(np.median(mu_gaps))
    out["dense_grad_gap_worst_leaf"] = max(mu_gaps)
    moved = [g >= 1e-3 * float(np.median(mu_ref)) for g in mu_ref]
    out["dense_delta_gap"] = _worst(
        [_norm(a - b) for a, b in zip(leaves(prog["params"]), leaves(prog["open_params"]))],
        [_norm(a - b) for a, b in zip(leaves(ref["params"]), leaves(ref["open_params"]))],
        keep=moved)
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: [value, limit]}): every limit's number has to be
    there, finite and within it."""
    table = {k: [values.get(k, math.nan), lim] for k, lim in limits.items()}
    ok = all(math.isfinite(v) and v <= lim for v, lim in table.values())
    return ok, table
