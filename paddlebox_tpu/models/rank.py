"""Join-phase rank model: a base CTR tower + rank_attention over the pv
rank matrix.

The reference's join phase feeds pv-merged batches whose ``rank_offset``
encodes each ad's rank and its peers' positions; RankAttention mixes
features across the pv before the final logit (box_wrapper.h RankAttention
+ rank_attention_op.cu). Here that is one wrapper usable around any base
model with ``init``/``apply`` (DeepFM, WideDeep, ...), consumed by the
pv-phase tests.
"""

from __future__ import annotations

import jax

from paddlebox_tpu.ops.ctr_ops import rank_attention


class RankDeepFM:
    """Base model + rank_attention tower over the pv rank matrix."""

    def __init__(self, base, in_dim: int, max_rank: int = 3):
        self.base = base
        self.max_rank = max_rank
        self.in_dim = in_dim

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {
            "base": self.base.init(k1),
            "rank_param": 0.01
            * jax.random.normal(
                k2, (self.max_rank * self.max_rank * self.in_dim, 1)
            ),
        }

    def apply(self, params, slot_feats, dense=None, rank_offset=None):
        logit = self.base.apply(params["base"], slot_feats, dense)
        if rank_offset is not None:
            x = slot_feats.reshape(slot_feats.shape[0], -1)
            att = rank_attention(
                x, rank_offset, params["rank_param"], self.max_rank
            )
            logit = logit + att[:, 0]
        return logit
