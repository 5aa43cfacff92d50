"""CTR model interface.

A model consumes the per-slot pooled+CVM'd features (output of
fused_seqpool_cvm: ``[batch, num_slots, feat_width]`` where
``feat_width = cvm_offset + embedx_dim`` in the join phase) plus an optional
dense float block, and produces one logit per instance (or per task).

This replaces the reference's static-graph model building
(fluid.layers._pull_box_sparse + fused_seqpool_cvm + fc stacks,
python/paddle/fluid/layers/nn.py:680, contrib/layers/nn.py:1337-2350) with
plain init/apply pairs over pytrees.
"""

from __future__ import annotations

from typing import Any, Protocol

import jax.numpy as jnp


class CTRModel(Protocol):
    num_slots: int
    feat_width: int
    dense_dim: int

    def init(self, rng) -> Any:  # params pytree
        ...

    def apply(self, params: Any, slot_feats: jnp.ndarray, dense: jnp.ndarray | None) -> jnp.ndarray:
        """-> logits [batch] (or [batch, n_tasks] for multi-task models)."""
        ...


class SequenceLossModel(Protocol):
    """A model that takes one sparse slot's pulled rows as a sequence and owns
    its loss (a language model over token ids: ``models/glm_moe_lite.py``,
    ``models/afmoe.py``, ``models/smallthinker.py``, ``models/sdar.py``,
    ``models/xing4.py``).

    ``sequence_feed = True`` on the model object is the declaration:
    ``CTRTrainer`` carries it to the step builders as
    ``TrainStepConfig.sequence_len``. Every record holds exactly
    ``seq_len`` keys in its one sparse slot; the step hands ``apply`` the
    pulled rows unpooled, ``[batch, seq_len, embedx_dim]`` in record order
    with the CVM columns dropped, and the record's dense float slot
    (``dense_dim`` values: for a language model its token ids, the targets);
    ``apply`` returns ``(loss, {"counters": ...})``. No seqpool+CVM, BCE or
    AUC runs; the gradient of the rows goes through the push like any
    feature's, and ``counters`` (one stacked vector named by
    ``counter_names``) rides in the step's metrics."""

    sequence_feed: bool
    seq_len: int
    dense_dim: int
    counter_names: tuple

    def init(self, rng) -> Any:
        ...

    def apply(self, params: Any, emb: jnp.ndarray, dense: jnp.ndarray):
        """-> (loss scalar, {"counters": [n]})."""
        ...

    def record_counters(self, means) -> None:
        ...
