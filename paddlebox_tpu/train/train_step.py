"""The jitted train step — the whole per-batch pipeline in one XLA program.

This one function replaces the reference's per-batch op loop
(BoxPSWorker::TrainFiles boxps_worker.cc:420-466: pull_box_sparse →
fused_seqpool_cvm → dense ops → push_box_sparse → dense sync → AUC):

    pull rows → seqpool+CVM → model fwd/bwd → sparse adagrad scatter →
    dense optimizer (+ cross-device psum) → AUC accumulate

Everything is static-shape; the host packer (data/device_pack.py) prepared
row ids / segment ids / padding. On a mesh the same local step runs under
shard_map with the table sharded and dense grads/metrics psum'd — the
single-device path is the degenerate axis_name=None case.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from paddlebox_tpu.metrics.auc import AucState, auc_init, auc_update
from paddlebox_tpu.ops.pull_push import (
    pull_sparse_rows,
    pull_sparse_rows_extended,
    push_sparse_rows,
)
from paddlebox_tpu.ops.seqpool_cvm import fused_seqpool_cvm
from paddlebox_tpu.table.optimizers import SparseOptimizerConfig
from paddlebox_tpu.table.value_layout import ValueLayout


class TrainState(NamedTuple):
    table: jnp.ndarray  # [rows, width] pass working-set (flat across shards)
    params: Any  # dense model params
    opt_state: Any  # optax state
    auc: AucState
    step: jnp.ndarray  # int32 scalar


@dataclass(frozen=True)
class TrainStepConfig:
    num_slots: int
    batch_size: int
    layout: ValueLayout
    sparse_opt: SparseOptimizerConfig = SparseOptimizerConfig()
    use_cvm: bool = True
    clk_filter: bool = False
    pull_scale: float = 1.0
    auc_buckets: int = 100_000
    axis_name: Optional[str] = None  # set on a mesh; None = single device
    slot_lr: Optional[tuple] = None  # per-slot lr multipliers, len num_slots
    # join-phase models taking the pv rank matrix get it as a 4th arg:
    # model_apply(params, slot_feats, dense, rank_offset)
    model_takes_rank_offset: bool = False
    # extended pull (pull_box_extended_sparse parity): layout must have
    # expand_embed_dim > 0; the model receives sum-pooled expand embeddings
    # [B, S, E] as its last positional arg and their grads flow back into
    # the table's expand block
    use_expand: bool = False
    # dense sync mode (BoxPSWorker sync_mode_, boxps_worker.cc:239-240):
    #  "step"  - allreduce dense grads every step (default; DP-sync parity)
    #  "kstep" - LocalSGD: local updates, params averaged across the mesh
    #            every param_sync_step steps + at pass end (DenseKStepNode/
    #            ALL parity — the NCCL reduce-scatter + closed SyncDense +
    #            allgather hierarchy collapses into one XLA all-reduce)
    #  "async" - device never updates dense params; gparams are returned in
    #            metrics for a host AsyncDenseTable (B6) pull/push loop
    dense_sync_mode: str = "step"
    param_sync_step: int = 16  # K for "kstep"
    # NaN/Inf containment (check_nan_var_names parity,
    # trainer_desc.proto:43): a batch with a non-finite loss or gradient is
    # SKIPPED in its entirety — no sparse push, no dense update, no AUC —
    # instead of silently poisoning the table; metrics report nan_skipped.
    check_nan: bool = False
    # AdjustInsWeight parity (downpour_worker.cc:271-340): up-weight the
    # LOSS of instances whose nid slot's show count is under threshold —
    # w = max(w, log(e + (T - nid_show)/T * ratio)) — so rarely-shown ads
    # still learn. (nid_slot_index, threshold, ratio); the nid slot is
    # assumed single-feasign like the reference. Only the loss weight
    # changes: show/clk counters keep their unweighted (or pv-ghost 0/1)
    # semantics, exactly as the reference's push records do.
    adjust_ins_weight: Optional[tuple] = None
    # a sequence-feed model (models/base.py::SequenceLossModel): every record
    # holds exactly this many keys in the one sparse slot; the model gets
    # the pulled rows unpooled as [batch, sequence_len, embedx] with the
    # record's dense slot and returns (loss, outputs) — no seqpool+CVM, BCE
    # or AUC. 0 = a CTR model. CTRTrainer fills it in from the model object.
    sequence_len: int = 0

    def __post_init__(self):
        if self.sequence_len and (
            self.num_slots != 1 or self.use_expand or self.model_takes_rank_offset
            or self.adjust_ins_weight is not None or self.axis_name is not None
            or self.dense_sync_mode != "step"
        ):
            raise NotImplementedError(
                "a sequence-feed model trains one sparse slot on one device with "
                "the dense optimizer in the step (no expand block, rank matrix, "
                "instance weights, mesh axis or async/kstep dense mode)"
            )
        if self.adjust_ins_weight is not None:
            nid, thr, ratio = self.adjust_ins_weight
            if not (0 <= nid < self.num_slots) or thr <= 0 or ratio < 0:
                raise ValueError(
                    f"adjust_ins_weight=(nid_slot, threshold>0, ratio>=0), "
                    f"got {self.adjust_ins_weight!r} with {self.num_slots} slots"
                )
        if self.dense_sync_mode not in ("step", "kstep", "async"):
            raise ValueError(
                f"dense_sync_mode {self.dense_sync_mode!r} not in "
                "('step', 'kstep', 'async')"
            )
        if self.dense_sync_mode == "kstep" and self.param_sync_step < 1:
            raise ValueError("param_sync_step must be >= 1 for kstep")


def init_train_state(
    table: jnp.ndarray,
    params: Any,
    dense_opt: optax.GradientTransformation,
    auc_buckets: int = 100_000,
) -> TrainState:
    return TrainState(
        table=table,
        params=params,
        opt_state=dense_opt.init(params),
        auc=auc_init(auc_buckets),
        step=jnp.zeros((), jnp.int32),
    )


def local_forward_backward(
    model_apply: Callable,
    cfg: TrainStepConfig,
    params: Any,
    flat: jnp.ndarray,  # [L, PW] pulled records per flat key
    segments: jnp.ndarray,  # [L]
    labels: jnp.ndarray,  # [b]
    dense: Optional[jnp.ndarray],
    ins_weight: Optional[jnp.ndarray] = None,  # [b] 0 masks ghost-padded ins
    rank_offset: Optional[jnp.ndarray] = None,  # [b, 2R+1] join-phase pv matrix
    loss_denom: Optional[jnp.ndarray] = None,  # weighted-loss denominator
    eval_mode: bool = False,  # forward only: grads come back as None
):
    """Shared fwd/bwd body: seqpool+CVM -> model -> BCE, grads wrt (params, flat).

    Used by both the single-device and the mesh-sharded step so the numerics
    can never diverge between them. With ``ins_weight`` the loss is the
    weighted mean, so weight-0 ghosts (pv batch padding) produce exactly zero
    gradient everywhere. ``loss_denom`` overrides the weight-sum denominator —
    the mesh step passes the GLOBAL (psum'd) weight sum so per-device ghost
    imbalance cannot skew sample weighting.

    A sequence-feed model gets the slot's rows as ``[B, T, embedx]`` in
    record order (CVM columns dropped) with the record's dense slot, and
    returns ``(loss, outputs)`` itself; ``preds`` is then its outputs dict.
    """

    def loss_fn(p, flat_records):
        if cfg.sequence_len:
            B, T = cfg.batch_size, cfg.sequence_len
            if flat_records.shape[0] < B * T:
                raise ValueError(
                    f"a batch of {B} records of {T} keys needs {B * T} pulled "
                    f"rows, the feed is padded to {flat_records.shape[0]}"
                )
            emb = flat_records[: B * T, cfg.layout.cvm_offset:].reshape(B, T, -1)
            return model_apply(p, emb, dense)
        if cfg.use_expand:  # trailing expand columns pool separately
            E = cfg.layout.expand_dim
            expand_flat = flat_records[:, -E:]
            flat_records = flat_records[:, :-E]
        slot_feats = fused_seqpool_cvm(
            flat_records,
            segments,
            num_slots=cfg.num_slots,
            batch_size=cfg.batch_size,
            use_cvm=cfg.use_cvm,
            clk_filter=cfg.clk_filter,
        )
        extra = []
        if cfg.model_takes_rank_offset:
            extra.append(rank_offset)
        if cfg.use_expand:
            # sum-pool expand per (slot, ins): [B, S, E] (pad segments drop)
            with jax.named_scope("seqpool_cvm"):
                pooled = jax.ops.segment_sum(
                    expand_flat,
                    segments,
                    num_segments=cfg.num_slots * cfg.batch_size,
                ).reshape(cfg.num_slots, cfg.batch_size, E)
                extra.append(jnp.transpose(pooled, (1, 0, 2)))
        with jax.named_scope("model"):
            logits = model_apply(p, slot_feats, dense, *extra)
        with jax.named_scope("loss"):
            loss_vec = optax.sigmoid_binary_cross_entropy(logits, labels)
            if ins_weight is not None:
                denom = (
                    loss_denom
                    if loss_denom is not None
                    else jnp.maximum(jnp.sum(ins_weight), 1.0)
                )
                loss = jnp.sum(loss_vec * ins_weight) / denom
            else:
                loss = jnp.mean(loss_vec)
            return loss, jax.nn.sigmoid(logits)

    if eval_mode:
        loss, preds = loss_fn(params, flat)
        return loss, preds, None, None
    (loss, preds), (gparams, gflat) = jax.value_and_grad(
        loss_fn, argnums=(0, 1), has_aux=True
    )(params, flat)
    return loss, preds, gparams, gflat


@jax.named_scope("merge")
def scale_and_merge_grads(
    cfg: TrainStepConfig,
    gflat: jnp.ndarray,  # [L, PW]
    segments: jnp.ndarray,  # [L]
    inverse: jnp.ndarray,  # [L] flat key -> merge position
    labels: jnp.ndarray,  # [b]
    num_segments: int,
    grad_div: float = 1.0,
    ins_weight: Optional[jnp.ndarray] = None,  # [b] ghosts -> 0 show/clk
):
    """Shared push-side merge: slot-lr scale, pad mask, per-position sums.

    Returns (merged grads, show counts, clk counts), each [num_segments, ...].
    ``grad_div`` rescales local-mean grads to global-mean on a mesh.
    """
    S, b = cfg.num_slots, cfg.batch_size
    if grad_div != 1.0:
        gflat = gflat / grad_div
    if cfg.slot_lr is not None:
        slot_of_key = jnp.minimum(segments // b, S - 1)
        lr_tab = jnp.asarray(cfg.slot_lr, jnp.float32)
        gflat = gflat * lr_tab[slot_of_key][:, None]
    pad_mask = (segments < S * b).astype(jnp.float32)  # [L] 0 on pad keys
    ins_of_key = segments % b
    # valid = pad mask x instance weight: ghosts add no show/clk
    valid = (
        pad_mask if ins_weight is None else pad_mask * jnp.take(ins_weight, ins_of_key)
    )
    gflat = gflat * pad_mask[:, None]
    # ONE segment reduction for grads + show + clk: scatter passes dominate
    # the push side on TPU, and three width-w scatters cost ~3x one
    # width-(w+2) scatter (PushMergeCopy fuses the same way, box_wrapper.cu)
    ext = jnp.concatenate(
        [gflat, valid[:, None], (jnp.take(labels, ins_of_key) * valid)[:, None]],
        axis=1,
    )
    summed = jax.ops.segment_sum(ext, inverse, num_segments=num_segments)
    return summed[:, :-2], summed[:, -2], summed[:, -1]


@jax.named_scope("loss")
def adjusted_loss_weight(
    cfg: TrainStepConfig,
    flat: jnp.ndarray,  # [L, PW(+E)] pulled records (col 0 = show)
    segments: jnp.ndarray,  # [L]
    ins_weight: Optional[jnp.ndarray],  # [b] pv/ghost weights or None
    b: int,
):
    """(loss_weight [b], loss_denom scalar-or-None) for AdjustInsWeight.

    Shared by both step builders: nid_show per instance comes from the nid
    slot's pulled show column (single-feasign slot, downpour_worker.cc:310
    asserts the same); the denominator stays the REAL-instance count so
    up-weighting doesn't silently renormalize away.
    """
    nid, thr, ratio = cfg.adjust_ins_weight
    S = cfg.num_slots
    slot_of_key = segments // b
    ins_of_key = segments % b
    is_nid = (slot_of_key == nid) & (segments < S * b)
    nid_show = jax.ops.segment_max(
        jnp.where(is_nid, flat[:, 0], -jnp.inf), ins_of_key, num_segments=b
    )
    base = ins_weight if ins_weight is not None else jnp.ones((b,), jnp.float32)
    adj = jnp.log(jnp.e + (thr - nid_show) / thr * ratio)
    loss_w = jnp.where(
        (nid_show >= 0) & (nid_show < thr), jnp.maximum(base, adj), base
    )
    # weight-0 ghosts (pv padding carries a REAL ad's nid) must stay
    # exactly zero — up-weighting may never resurrect them
    loss_w = jnp.where(base > 0, loss_w, base)
    denom = (
        jnp.asarray(float(b))
        if ins_weight is None
        else jnp.maximum(jnp.sum(ins_weight), 1.0)
    )
    return loss_w, denom


def make_train_step(
    model_apply: Callable,
    dense_opt: optax.GradientTransformation,
    cfg: TrainStepConfig,
    eval_mode: bool = False,
) -> Callable:
    """Build ``step(state, batch_dict) -> (state, metrics)`` (pure, jittable).

    ``batch_dict`` fields: uniq_rows [U], inverse [L], segments [L],
    labels [B], optional dense [B, Dd]. See data/device_pack.py.

    ``eval_mode`` is the SetTestMode path (box_wrapper.cc:623,
    infer_from_dataset executor.py:1520): forward + metrics only — no
    sparse push, no dense update; table/params/opt_state return
    bit-identical.
    """
    lay, opt = cfg.layout, cfg.sparse_opt
    S, B = cfg.num_slots, cfg.batch_size
    owns_loss = bool(cfg.sequence_len)

    def step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        uniq_rows = batch["uniq_rows"]
        inverse = batch["inverse"]
        segments = batch["segments"]
        labels = batch["labels"]
        dense = batch.get("dense")
        ins_weight = batch.get("ins_weight")
        rank_offset = batch.get("rank_offset")
        U = uniq_rows.shape[0]

        with jax.named_scope("pull"):
            if cfg.use_expand:
                rec_u, exp_u = pull_sparse_rows_extended(
                    state.table, uniq_rows, lay, opt.embedx_threshold, cfg.pull_scale
                )
                pulled_u = jnp.concatenate([rec_u, exp_u], axis=1)  # [U, PW+E]
            else:
                pulled_u = pull_sparse_rows(
                    state.table, uniq_rows, lay, opt.embedx_threshold, cfg.pull_scale
                )  # [U, PW]
            with jax.named_scope("expand"):
                flat = jnp.take(pulled_u, inverse, axis=0)  # [L, PW(+E)]

        loss_w, loss_denom = ins_weight, None
        if cfg.adjust_ins_weight is not None and not eval_mode:
            loss_w, loss_denom = adjusted_loss_weight(
                cfg, flat, segments, ins_weight, B
            )
        loss, preds, gparams, gflat = local_forward_backward(
            model_apply, cfg, state.params, flat, segments, labels, dense,
            ins_weight=loss_w, rank_offset=rank_offset,
            loss_denom=loss_denom, eval_mode=eval_mode,
        )
        finite = None
        if cfg.check_nan and not eval_mode:
            with jax.named_scope("nan_guard"):
                gsum = loss + jnp.sum(gflat)
                for leaf in jax.tree.leaves(gparams):
                    gsum = gsum + jnp.sum(leaf)
                finite = jnp.isfinite(gsum)
                if cfg.axis_name is not None:
                    # all devices share the table: one bad device skips everywhere
                    finite = (
                        jax.lax.psum((~finite).astype(jnp.int32), cfg.axis_name) == 0
                    )
                # where, not multiply: NaN * 0 is still NaN
                gflat = jnp.where(finite, gflat, 0.0)
        if eval_mode:
            new_table = state.table
            new_params, new_opt_state = state.params, state.opt_state
            if cfg.axis_name is not None:
                loss = jax.lax.pmean(loss, cfg.axis_name)
        else:
            # --- sparse push: per-slot lr scaling happens at flat
            # resolution (a key deduped across slots gets each slot's
            # scaled contribution), then grads merge per unique row —
            # PushMergeCopy parity.
            with jax.named_scope("push"):
                guniq, show_counts, clk_counts = scale_and_merge_grads(
                    cfg, gflat, segments, inverse, labels, num_segments=U,
                    ins_weight=ins_weight,
                )
            if finite is not None:
                # a zeroed push is an exact identity on the table (adagrad
                # g2 += 0, step 0, show/clk += 0) — the skipped batch never
                # happened as far as the sparse model is concerned. where,
                # not multiply: a NaN label rides into clk via segment_sum
                with jax.named_scope("nan_guard"):
                    show_counts = jnp.where(finite, show_counts, 0.0)
                    clk_counts = jnp.where(finite, clk_counts, 0.0)

            with jax.named_scope("push"):
                new_table = push_sparse_rows(
                    state.table, uniq_rows, guniq, show_counts, clk_counts, lay, opt
                )

            # --- dense sync: psum over the DP axis (K-step/NCCL allreduce
            # parity)
            if cfg.axis_name is not None:
                gparams = jax.lax.pmean(gparams, cfg.axis_name)
                loss = jax.lax.pmean(loss, cfg.axis_name)
            if cfg.dense_sync_mode == "async":
                # host AsyncDenseTable owns the dense optimizer: hand grads
                # back
                new_params, new_opt_state = state.params, state.opt_state
            else:
                with jax.named_scope("dense_opt"):
                    updates, new_opt_state = dense_opt.update(
                        gparams, state.opt_state, state.params
                    )
                    new_params = optax.apply_updates(state.params, updates)
            if finite is not None:
                # skipped batch: dense params + optimizer moments stay put
                with jax.named_scope("nan_guard"):
                    new_params = jax.tree.map(
                        lambda new, old: jnp.where(finite, new, old),
                        new_params, state.params,
                    )
                    new_opt_state = jax.tree.map(
                        lambda new, old: jnp.where(finite, new, old),
                        new_opt_state, state.opt_state,
                    )

        if owns_loss:  # no prediction per instance to rank: the buckets stay
            new_auc = state.auc
        else:
            with jax.named_scope("auc"):
                auc_mask = None if ins_weight is None else (ins_weight > 0)
                if finite is not None:
                    fin_mask = jnp.broadcast_to(finite, labels.shape)
                    auc_mask = fin_mask if auc_mask is None else (auc_mask & fin_mask)
                new_auc = auc_update(state.auc, preds, labels, auc_mask)
        # a skipped batch never happened: the step counter (which paces
        # kstep param syncs and dump sampling) must not advance either
        step_inc = (
            jnp.ones((), jnp.int32) if finite is None else finite.astype(jnp.int32)
        )
        # preds/labels ride along for the host-side metric registry
        # (AddAucMonitor parity) — small [B] arrays, no sync forced
        metrics = {"loss": loss, "step": state.step + step_inc}
        if owns_loss:  # the model's own outputs: its counters in one stacked array
            metrics.update(preds)
        else:
            metrics.update(preds=preds, labels=labels)
        if finite is not None:
            metrics["nan_skipped"] = (~finite).astype(jnp.int32)
        if cfg.dense_sync_mode == "async" and not eval_mode:
            metrics["gparams"] = gparams
        return (
            TrainState(
                table=new_table,
                params=new_params,
                opt_state=new_opt_state,
                auc=new_auc,
                step=state.step + step_inc,
            ),
            metrics,
        )

    return step


def jit_train_step(step: Callable) -> Callable:
    """Single-device jit with table donation (in-place HBM update)."""
    return jax.jit(step, donate_argnums=(0,))
