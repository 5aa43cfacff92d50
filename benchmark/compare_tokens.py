"""The comparison that decides ``correct`` for a token-pass cell: what the
timed path's first superstep left behind against the plain reference's. The
numbers of ``benchmark/compare.py`` that a token pass shares (``open_rows_gap``,
``counter_gap``, ``sparse_grad_gap``, ``sparse_delta_gap``, ``dense_grad_gap``
by the median leaf, ``dense_delta_gap``, ``loss_gap``) are that module's own;
beside them:

- ``early_loss_gap``: the loss of steps 1 and 2, its main and its MTP part
  apart, largest relative difference.
- ``logit_gap``: step 1, every ``LOGIT_STRIDE``-th position of every record,
  both heads: the target's logit and the logsumexp of all logits (logits, not
  an argmax), the median absolute difference over the standard deviation of
  the reference's target logits. The median, because a token whose expert
  choice flipped (below) differs by percents and the rest by rounding: the
  root mean square (``logit_rms_gap``, read, no limit) counts the flips again.
- ``router_flip_share``: step 1, the share of (layer, token, k) expert
  choices that the other side did not make. Not zero: two scores a rounding
  apart swap places in the top k.
"""

from __future__ import annotations

from typing import Dict

import jax
import numpy as np

from benchmark import compare

LOGIT_STRIDE = 8


def gaps(prog: dict, ref: dict, cfg: dict) -> Dict[str, float]:
    out = compare.gaps(prog, ref, cfg)
    rel = np.abs(prog["parts"] - ref["parts"]) / np.abs(ref["parts"])  # [steps, 2]
    out["early_loss_gap"] = float(np.max(rel[:compare.EARLY_STEPS]))
    p = prog["token_logits"][..., ::LOGIT_STRIDE].astype(np.float64)
    r = ref["token_logits"][..., ::LOGIT_STRIDE].astype(np.float64)
    T = prog["token_logits"].shape[-1]
    has = (np.arange(T)[::LOGIT_STRIDE] < T - 2)  # positions with a target at both heads
    p, r = p[..., has], r[..., has]
    out["logit_gap"] = float(np.median(np.abs(p - r)) / np.std(r[:2]))
    out["logit_rms_gap"] = float(np.sqrt(np.mean(np.square(p - r))) / np.std(r[:2]))
    a = np.sort(prog["router_choices"], axis=-1)
    b = np.sort(ref["router_choices"], axis=-1)
    made = (a[..., :, None] == b[..., None, :]).any(axis=-1)
    out["router_flip_share"] = float(1.0 - np.mean(made))
    return out


LEAF_SAMPLE = 1 << 20


def leaf_table(prog: dict, ref: dict) -> Dict[str, list]:
    """Every dense leaf by its path, over at most ``LEAF_SAMPLE`` of its
    elements (evenly strided): [elements, the root mean square of the
    reference's change over the compared steps, of the program's, the share
    of elements the reference moved at all, |program's norm of Adam's first
    moment - reference's| over the reference's]. Read, not compared: it shows
    which leaves a step of the warm-up's size can move (a float32 norm weight
    at 1.0 moves by whole ulps of 6e-8 or not at all)."""
    rows = {}
    trees = (ref["params"], ref["open_params"], prog["params"], prog["open_params"],
             ref["mu"], prog["mu"])
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(trees[0])[0]]
    for path, *leaves in zip(paths, *(jax.tree.leaves(t) for t in trees)):
        stride = -(-leaves[0].size // LEAF_SAMPLE)
        rp, ro, pp, po, rm, pm = (np.asarray(a).ravel()[::stride].astype(np.float64) for a in leaves)
        rd, pd, rn = rp - ro, pp - po, np.linalg.norm(rm)
        rows[path] = [int(leaves[0].size), float(np.sqrt(np.mean(rd * rd))),
                      float(np.sqrt(np.mean(pd * pd))), float(np.mean(rd != 0)),
                      float(abs(np.linalg.norm(pm) - rn) / rn) if rn else 0.0]
    return rows
