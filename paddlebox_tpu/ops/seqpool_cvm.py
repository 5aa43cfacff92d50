"""Fused sequence-pool + CVM over ragged slot batches.

Parity with the reference's fused_seqpool_cvm op family
(operators/fused/fused_seqpool_cvm_op.cu): per (slot, instance) sum-pool of
the pulled key records, then the CVM transform on the leading show/click
columns:

    out[0] = log(show_sum + 1)
    out[1] = log(clk_sum + 1) - log(show_sum + 1)        (join phase, use_cvm)
    out[2:] passthrough
  or, update phase (use_cvm=False): strip the first two columns
  (FusedCVMKernelNoCVM, fused_seqpool_cvm_op.cu:166-182).

Options mirrored: pad_value, need_filter (drop keys failing
(show-clk)*show_coeff + clk*clk_coeff >= threshold, :90-118), clk_filter
(join with show only, :145-164), quant_ratio (round(v*q)/q, :60-88),
embed_threshold_filter variant (`_with_diff_thres`).

The ragged pooling is a segment-sum over host-precomputed segment ids
(slot * batch + ins), which XLA lowers to a single scatter-add — the
device-side bookkeeping the reference does in CUDA lives in the host packer
here. Autodiff provides the backward (the reference hand-writes it).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def cvm_transform(pooled: jnp.ndarray, use_cvm: bool = True) -> jnp.ndarray:
    """CVM on pooled records [..., width]: show/clk -> log CTR features.

    Parity: cvm_op (operators/cvm_op.h:26-38) and FusedCVMKernelWithCVM.
    """
    show = pooled[..., 0:1]
    clk = pooled[..., 1:2]
    log_show = jnp.log(show + 1.0)
    log_clk = jnp.log(clk + 1.0)
    if use_cvm:
        return jnp.concatenate([log_show, log_clk - log_show, pooled[..., 2:]], axis=-1)
    return pooled[..., 2:]


def cvm_with_conv_transform(
    pooled: jnp.ndarray, use_cvm: bool = True, show_filter: bool = False
) -> jnp.ndarray:
    """CVM for CONV layouts [show, clk, conv, ...] (cvm_offset 4 family).

    Parity with FusedCVMWithConvKernelNormal / WithOutShow
    (fused_seqpool_cvm_with_conv_op.cu:55-110):
      out = [log(show+1), log(clk+1), log(conv+1) - log(clk+1), rest]
      show_filter drops the show column (join-with-show-only mode).
    """
    if not use_cvm:
        return pooled[..., 3:]
    log_show = jnp.log(pooled[..., 0:1] + 1.0)
    log_clk = jnp.log(pooled[..., 1:2] + 1.0)
    log_conv = jnp.log(pooled[..., 2:3] + 1.0)
    cols = [log_show, log_clk, log_conv - log_clk, pooled[..., 3:]]
    if show_filter:
        cols = cols[1:]
    return jnp.concatenate(cols, axis=-1)


def cvm_with_pcoc_transform(
    pooled: jnp.ndarray, pclk_num: int = 3, use_cvm: bool = True
) -> jnp.ndarray:
    """CVM for PCOC layouts [show, clk, join_show, join_clk, pclk*, ...]
    (cvm_offset 2 + 2 + pclk_num).

    Parity with FusedCVMWithPCOCKernelWithCVM
    (fused_seqpool_cvm_with_pcoc_op.cu:120-155):
      out[0]              = log(show+1)
      out[1]              = log(clk+1) - log(show+1)
      out[2 : 2+p]        = log(pclk_k+1) - log(join_show+1)
      out[2+p : 2+2p]     = log(pclk_k+1) - log(join_clk+1)
      rest                  passthrough (the embedx block)
    """
    cvm_in = 4 + pclk_num
    if not use_cvm:
        return pooled[..., cvm_in:]
    log_show = jnp.log(pooled[..., 0:1] + 1.0)
    log_clk = jnp.log(pooled[..., 1:2] + 1.0)
    log_jshow = jnp.log(pooled[..., 2:3] + 1.0)
    log_jclk = jnp.log(pooled[..., 3:4] + 1.0)
    log_pclk = jnp.log(pooled[..., 4:cvm_in] + 1.0)
    return jnp.concatenate(
        [
            log_show,
            log_clk - log_show,
            log_pclk - log_jshow,
            log_pclk - log_jclk,
            pooled[..., cvm_in:],
        ],
        axis=-1,
    )


def _seqpool(
    records: jnp.ndarray,
    segments: jnp.ndarray,
    num_slots: int,
    batch_size: int,
    pad_value: float,
    need_filter: bool,
    show_coeff: float,
    clk_coeff: float,
    threshold,  # float, or per-slot [num_slots] vector (diff_thres variant)
    quant_ratio: Optional[int],
    cvm_cols: int = 2,
) -> jnp.ndarray:
    """Shared sum-pool half: filter/quant at key level, then segment-sum.
    Returns [num_slots, batch, width]."""
    vals = records
    if need_filter:
        # key-level filter on raw show/clk (SeqPoolKernelEmbedQuantFilter;
        # per-slot thresholds = FusedSeqpoolKernelDiffThresFilter,
        # fused_seqpool_cvm_with_diff_thres_op.cu:92-118)
        score = (vals[:, 0] - vals[:, 1]) * show_coeff + vals[:, 1] * clk_coeff
        thr = jnp.asarray(threshold, jnp.float32)
        if thr.ndim == 1:
            slot_of_key = jnp.minimum(segments // batch_size, num_slots - 1)
            thr = thr[slot_of_key]
        keep = score >= thr
        vals = jnp.where(keep[:, None], vals, 0.0)
    if quant_ratio:
        q = float(quant_ratio)
        head = vals[:, :cvm_cols]
        tail = jnp.round(vals[:, cvm_cols:] * q) / q
        vals = jnp.concatenate([head, tail], axis=1)

    num_segments = num_slots * batch_size
    pooled = jax.ops.segment_sum(vals, segments, num_segments=num_segments + 1)
    pooled = pooled[:num_segments].reshape(num_slots, batch_size, -1)
    if pad_value != 0.0:
        # slots with zero keys for an instance pool to pad_value, not 0
        ones = jax.ops.segment_sum(
            jnp.ones((records.shape[0],), records.dtype),
            segments,
            num_segments=num_segments + 1,
        )[:num_segments].reshape(num_slots, batch_size)
        pooled = jnp.where((ones == 0)[..., None], pad_value, pooled)
    return pooled


@jax.named_scope("seqpool_cvm")
def fused_seqpool_cvm(
    records: jnp.ndarray,  # [L, width] pulled per-key records (flat, padded)
    segments: jnp.ndarray,  # int32 [L] = slot * batch + ins; pads -> num_segments
    num_slots: int,
    batch_size: int,
    use_cvm: bool = True,
    pad_value: float = 0.0,
    need_filter: bool = False,
    show_coeff: float = 0.2,
    clk_coeff: float = 1.0,
    threshold: float = 0.96,
    quant_ratio: Optional[int] = None,
    clk_filter: bool = False,
) -> jnp.ndarray:
    """-> [batch, num_slots, out_width] pooled + CVM'd slot features.

    ``segments`` may contain the value ``num_slots * batch_size`` for padded
    entries; those rows fall into a trash segment that is dropped.
    """
    pooled = _seqpool(
        records, segments, num_slots, batch_size, pad_value,
        need_filter, show_coeff, clk_coeff, threshold, quant_ratio,
    )
    out = cvm_transform(pooled, use_cvm=use_cvm)
    if use_cvm and clk_filter:
        # join with show only: drop the click column (col 1)
        out = jnp.concatenate([out[..., 0:1], out[..., 2:]], axis=-1)
    return jnp.transpose(out, (1, 0, 2))  # -> [batch, slots, width]


def fused_seqpool_cvm_with_diff_thres(
    records: jnp.ndarray,
    segments: jnp.ndarray,
    num_slots: int,
    batch_size: int,
    threshold_vec,  # [num_slots] per-slot filter thresholds
    use_cvm: bool = True,
    pad_value: float = 0.0,
    show_coeff: float = 0.2,
    clk_coeff: float = 1.0,
    quant_ratio: Optional[int] = None,
    clk_filter: bool = False,
) -> jnp.ndarray:
    """Per-slot-threshold variant (fused_seqpool_cvm_with_diff_thres_op.cu):
    identical to fused_seqpool_cvm but the key filter compares against the
    key's slot's threshold."""
    return fused_seqpool_cvm(
        records, segments, num_slots, batch_size,
        use_cvm=use_cvm, pad_value=pad_value, need_filter=True,
        show_coeff=show_coeff, clk_coeff=clk_coeff,
        threshold=threshold_vec, quant_ratio=quant_ratio, clk_filter=clk_filter,
    )


@jax.named_scope("seqpool_cvm")
def fused_seqpool_cvm_with_conv(
    records: jnp.ndarray,  # [L, width] CONV layout: [show, clk, conv, embedx...]
    segments: jnp.ndarray,
    num_slots: int,
    batch_size: int,
    use_cvm: bool = True,
    pad_value: float = 0.0,
    show_filter: bool = False,
) -> jnp.ndarray:
    """CONV (q-value) variant -> [batch, slots, out_width]
    (fused_seqpool_cvm_with_conv_op.cu; cvm_offset 4, box_wrapper.h:526)."""
    pooled = _seqpool(
        records, segments, num_slots, batch_size, pad_value,
        False, 0.0, 0.0, 0.0, None, cvm_cols=3,
    )
    out = cvm_with_conv_transform(pooled, use_cvm=use_cvm, show_filter=show_filter)
    return jnp.transpose(out, (1, 0, 2))


@jax.named_scope("seqpool_cvm")
def fused_seqpool_cvm_with_pcoc(
    records: jnp.ndarray,  # [L, width] PCOC layout (cvm_offset 4 + pclk_num)
    segments: jnp.ndarray,
    num_slots: int,
    batch_size: int,
    pclk_num: int = 3,
    use_cvm: bool = True,
    pad_value: float = 0.0,
    quant_ratio: Optional[int] = None,
) -> jnp.ndarray:
    """PCOC variant -> [batch, slots, out_width]
    (fused_seqpool_cvm_with_pcoc_op.cu; cvm_offset 8 = 4 + 3 pclk + embed_w
    packing per box_wrapper.h:524)."""
    pooled = _seqpool(
        records, segments, num_slots, batch_size, pad_value,
        False, 0.0, 0.0, 0.0, quant_ratio, cvm_cols=4 + pclk_num,
    )
    out = cvm_with_pcoc_transform(pooled, pclk_num=pclk_num, use_cvm=use_cvm)
    return jnp.transpose(out, (1, 0, 2))
