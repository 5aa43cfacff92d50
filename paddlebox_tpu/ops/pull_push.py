"""Device-side sparse pull/push over the pass working-set table.

TPU-native replacement for the reference's pull/push hot path
(PullSparseCase/PushSparseGradCase, box_wrapper_impl.h:25-253, kernels in
box_wrapper.cu): keys were already remapped host-side to dense row ids, so

- pull  = gather rows + embedx activity gating + scale     (static shapes)
- push  = vectorized sparse-AdaGrad column math + one scatter back

Both run *inside* the jitted train step; the optimizer lives on device, not
in a parameter server. The table row layout is ``ValueLayout``:
``[show, clk, extras..., embed_w, embedx[D], embed_g2, embedx_g2]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from paddlebox_tpu.table.optimizers import SparseOptimizerConfig
from paddlebox_tpu.table.value_layout import FeatureType, ValueLayout


def embedx_active_mask(
    layout: ValueLayout, show: jnp.ndarray, embedx_threshold: float
) -> jnp.ndarray:
    """Activation mask for the embedx block, from the key's show count.

    Row-level threshold gate (the closed lib's ``embedding_size > 0``
    signal, box_wrapper.cu:54-63) — or, for FeatureType.VARIABLE, the
    graded per-column unlock (column j needs show >= threshold *
    2^quarter(j)): cold keys expose a short vector, hot keys the full one
    (B3 VARIABLE; dim policy re-derived openly, see
    value_layout.FeatureType). Shared by pull AND push so locked dims can
    neither be seen nor trained.
    """
    if layout.feature_type is FeatureType.VARIABLE:
        D = layout.embedx_dim
        quarter = jnp.arange(D, dtype=jnp.int32) * 4 // max(D, 1)
        need = embedx_threshold * jnp.exp2(quarter.astype(jnp.float32))
        return show[:, None] >= need[None, :]
    return (show >= embedx_threshold)[:, None]


@jax.named_scope("table_gather")  # with its gating: XLA may fuse the two
def pull_sparse_rows(
    table: jnp.ndarray,  # [rows, width]
    rows: jnp.ndarray,  # int32 [U] (deduped, padded with the padding row)
    layout: ValueLayout,
    embedx_threshold: float,
    scale: float = 1.0,
) -> jnp.ndarray:
    """Gather pull records [U, pull_width] = [show, clk, .., embed_w, embedx].

    embedx columns are zeroed per ``embedx_active_mask``: for keys whose
    show count has not reached the activation threshold — the open analog
    of the closed lib's ``embedding_size > 0`` signal consumed by PullCopy
    (box_wrapper.cu:54-63) — or, on VARIABLE layouts, per-column as the
    graded dims unlock.
    """
    picked = jnp.take(table, rows, axis=0)  # [U, width]
    cvm_block = picked[:, : layout.cvm_offset]
    embedx = picked[:, layout.embedx_col : layout.embedx_col + layout.embedx_dim]
    active = embedx_active_mask(layout, picked[:, layout.SHOW], embedx_threshold)
    embedx = jnp.where(active, embedx * scale, 0.0)
    return jnp.concatenate([cvm_block, embedx], axis=1)


@jax.named_scope("table_gather")
def pull_sparse_rows_extended(
    table: jnp.ndarray,  # [rows, width]
    rows: jnp.ndarray,  # int32 [U]
    layout: ValueLayout,
    embedx_threshold: float,
    scale: float = 1.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(pull records [U, pull_width], expand embeddings [U, expand_dim]).

    The pull_box_extended_sparse analog (pull_box_extended_sparse_op.h:26-95):
    each key yields its normal record plus a second, independently trained
    expand embedding (same activation gating).
    """
    if layout.expand_dim == 0:
        raise ValueError("layout has no expand block (expand_embed_dim == 0)")
    picked = jnp.take(table, rows, axis=0)
    cvm_block = picked[:, : layout.cvm_offset]
    show = picked[:, layout.SHOW]
    # embedx follows the layout's gating (incl. VARIABLE graded dims);
    # the expand block stays row-level gated — its dims are an independent
    # second embedding, not a prefix-extensible vector
    active = embedx_active_mask(layout, show, embedx_threshold)
    row_active = (show >= embedx_threshold)[:, None]
    embedx = picked[:, layout.embedx_col : layout.embedx_col + layout.embedx_dim]
    embedx = jnp.where(active, embedx * scale, 0.0)
    expand = picked[:, layout.expand_col : layout.expand_col + layout.expand_dim]
    expand = jnp.where(row_active, expand * scale, 0.0)
    return jnp.concatenate([cvm_block, embedx], axis=1), expand


def push_sparse_rows(
    table: jnp.ndarray,  # [rows, width]
    rows: jnp.ndarray,  # int32 [U] deduped rows (padding row allowed)
    grads: jnp.ndarray,  # [U, pull_width] d(loss)/d(pull record)
    show_counts: jnp.ndarray,  # f32 [U] occurrences of the key in this batch
    clk_counts: jnp.ndarray,  # f32 [U] summed clicks over those occurrences
    layout: ValueLayout,
    opt: SparseOptimizerConfig,
    lr_scale: jnp.ndarray | float = 1.0,  # scalar or [U] slot-lr multiplier
) -> jnp.ndarray:
    """Apply sparse AdaGrad + counter updates; returns the new table.

    Mirrors the closed PushSparseGPU contract (push record = show, clk,
    grads; box_wrapper.cu PushCopy fills show/clk from the batch) with the
    optimizer semantics documented in table/optimizers.py.
    """
    # the same gather as the pull's: XLA keeps one of the two
    with jax.named_scope("table_gather"):
        old = jnp.take(table, rows, axis=0)  # [U, width]
    new_rows = sparse_update_rows(
        old, grads, show_counts, clk_counts, layout, opt, lr_scale
    )
    with jax.named_scope("table_scatter"):
        # Scatter the *delta* with add-semantics: with host dedup rows are
        # unique and this equals a set; without dedup
        # (enable_pullpush_dedup_keys=0) a key occurring in several slots
        # contributes each occurrence's update deterministically
        # (sequential-push semantics) instead of last-write-wins.
        return table.at[rows].add(new_rows - old)


@jax.named_scope("sparse_opt")
def sparse_update_rows(
    old: jnp.ndarray,  # [U, width] current rows
    grads: jnp.ndarray,  # [U, pull_width] d(loss)/d(pull record)
    show_counts: jnp.ndarray,  # f32 [U]
    clk_counts: jnp.ndarray,  # f32 [U]
    layout: ValueLayout,
    opt: SparseOptimizerConfig,
    lr_scale: jnp.ndarray | float = 1.0,
) -> jnp.ndarray:
    """Row-wise sparse optimizer math shared by the single-device scatter path
    and the sharded owner-side merge path (rows with all-zero records are
    identity: g2 += 0, step 0, counters += 0).

    ``grads`` may be [U, pull_width] or [U, pull_width + expand_dim] — the
    extended form (pull_sparse_rows_extended) appends expand-embedding grads,
    updated with their own adagrad g2 scalar (static shapes: the branch
    resolves at trace time).
    """
    co, D = layout.cvm_offset, layout.embedx_dim
    with_expand = grads.shape[1] == layout.extended_push_width and layout.expand_dim > 0

    show = old[:, layout.SHOW] + show_counts
    clk = old[:, layout.CLK] + clk_counts

    # --- embed_w (+ any conv/pcoc extras: cols 2..cvm_offset) scalar adagrad.
    # grads[:, :2] correspond to the show/clk passthrough columns of the pull
    # record; they receive CVM-transform gradients in principle, but counters
    # are PS statistics, not weights — the reference likewise ignores them.
    w_grad = grads[:, 2:co]  # [U, co-2] (embed_w last)
    g2_e = old[:, layout.embed_g2_col] + jnp.sum(w_grad * w_grad, axis=1)
    scale_e = jnp.sqrt(opt.initial_g2sum / (opt.initial_g2sum + g2_e))
    step_e = (opt.embed_lr * lr_scale * scale_e)[:, None] * w_grad
    new_w = old[:, 2:co] - step_e
    new_w = jnp.clip(new_w, -opt.weight_bounds, opt.weight_bounds)

    # --- embedx vector adagrad with one shared g2 scalar (mean energy).
    # The activation mask MUST match the pull's (incl. VARIABLE graded
    # dims): grads are taken w.r.t. the pulled record, so a locked dim's
    # gradient is nonzero even though the model saw a zero — without the
    # mask it would train on phantom inputs and inflate g2.
    x_grad = grads[:, co : co + D]
    x_active = embedx_active_mask(layout, old[:, layout.SHOW], opt.embedx_threshold)
    x_grad = jnp.where(x_active, x_grad, 0.0)
    g2_x = old[:, layout.embedx_g2_col] + jnp.mean(x_grad * x_grad, axis=1)
    scale_x = jnp.sqrt(opt.initial_g2sum / (opt.initial_g2sum + g2_x))
    new_x = old[:, co : co + D] - (opt.embedx_lr * lr_scale * scale_x)[:, None] * x_grad
    new_x = jnp.clip(new_x, -opt.weight_bounds, opt.weight_bounds)

    cols = [show[:, None], clk[:, None], new_w, new_x]
    if layout.expand_dim:
        E = layout.expand_dim
        ec = layout.expand_col
        if with_expand:
            # expand is row-level gated (an independent second embedding,
            # not a prefix-extensible vector) — mirrors the extended pull
            row_active = (old[:, layout.SHOW] >= opt.embedx_threshold)[:, None]
            e_grad = grads[:, co + D : co + D + E]
            e_grad = jnp.where(row_active, e_grad, 0.0)
        else:  # plain push on an expand-capable layout: expand untouched
            e_grad = jnp.zeros((old.shape[0], E), old.dtype)
        g2_p = old[:, layout.expand_g2_col] + jnp.mean(e_grad * e_grad, axis=1)
        scale_p = jnp.sqrt(opt.initial_g2sum / (opt.initial_g2sum + g2_p))
        new_p = old[:, ec : ec + E] - (opt.embedx_lr * lr_scale * scale_p)[:, None] * e_grad
        cols.append(jnp.clip(new_p, -opt.weight_bounds, opt.weight_bounds))
        cols += [g2_e[:, None], g2_x[:, None], g2_p[:, None]]
    else:
        cols += [g2_e[:, None], g2_x[:, None]]
    return jnp.concatenate(cols, axis=1)
