"""Pallas TPU kernels for the sparse pull/push hot path.

The reference's hot path is hand-written CUDA (PullCopy/PushCopy and the
dedup scatter-gather family, box_wrapper.cu:31-800). On TPU the equivalent
ops are row gathers/writebacks over the pass working-set array; XLA's
take/scatter lowerings are the baseline, and these Pallas kernels are the
hand-tuned alternative doing **explicit row DMA**: the row-id vector is
scalar-prefetched (PrefetchScalarGridSpec), the table stays unblocked in
HBM (memory_space=ANY), and each grid step issues ``make_async_copy`` for a
block of rows — all copies in flight concurrently before one wait
(box_wrapper.cu's coalesced gather, TPU idiom).

Mosaic constrains *blocked* specs to (8, 128)-aligned tiles, which a
(1, width) row gather can't satisfy — manual DMA from ANY space has no such
constraint, so arbitrary row widths work.

Integration: ops/pull_push.py routes through these when the active
``KernelPlan`` (ops/kernel_plan.py — per-(op, backend, shape-bucket)
registry loaded from tools/kernel_plan.json, regenerated by
tools/tune_kernels.py from op_probe sweeps) selects "pallas" for the shape.
Selection clamps to native unless the backend is TPU and the table width is
lane-aligned (W % 128 == 0 — Mosaic cannot slice narrower rows out of a
lane-tiled HBM memref); the legacy ``use_pallas_sparse`` flag survives as
the builtin plan's fallback preference. CPU tests run interpret mode.

Measured (v5p single chip, R=1M x W=128, U=160k rows): XLA take 2.8 ms vs
this kernel 9.2 ms; scatter-set 7.4 ms. XLA's native gather wins at CTR
shapes, so the flag DEFAULTS OFF and the kernels stand as correct,
benchmarked infrastructure for wider-row layouts where per-row DMA
amortizes better — re-measure before enabling in production.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddlebox_tpu.ops.kernel_plan import PALLAS_BLK as _BLK  # rows per grid step
from paddlebox_tpu.ops.kernel_plan import PALLAS_LANE as LANE  # Mosaic lane width

# (_BLK is also the out-block sublane size; table widths must be a LANE
# multiple to DMA-slice. The constants live in kernel_plan.py so the plan's
# eligibility clamp and these kernels can never disagree.)


def _gather_kernel(rows_ref, table_ref, out_ref, sems):
    i = pl.program_id(0)
    for j in range(_BLK):  # static unroll: _BLK concurrent row DMAs
        r = rows_ref[i * _BLK + j]
        pltpu.make_async_copy(table_ref.at[r], out_ref.at[j], sems.at[j]).start()
    for j in range(_BLK):
        r = rows_ref[i * _BLK + j]
        pltpu.make_async_copy(table_ref.at[r], out_ref.at[j], sems.at[j]).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def pull_rows_pallas(
    table: jnp.ndarray,  # [R, W] f32
    rows: jnp.ndarray,  # [U] int32 row ids (duplicates fine); U % 8 == 0
    interpret: bool = False,
) -> jnp.ndarray:
    """Gather ``table[rows]`` -> [U, W] via explicit HBM->VMEM row DMAs."""
    U = rows.shape[0]
    R, W = table.shape
    if U % _BLK != 0:
        raise ValueError(
            f"U={U} must be a multiple of {_BLK} (pad with the padding row)"
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(U // _BLK,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # whole table, HBM
        out_specs=pl.BlockSpec((_BLK, W), lambda i, rows_ref: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_BLK,))],
    )
    return pl.pallas_call(
        _gather_kernel,
        out_shape=jax.ShapeDtypeStruct((U, W), table.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(rows.astype(jnp.int32), table)


def _writeback_kernel(rows_ref, table_in_ref, new_rows_ref, out_ref, sems):
    del table_in_ref  # aliased with out_ref; untouched rows pass through
    i = pl.program_id(0)
    for j in range(_BLK):
        r = rows_ref[i * _BLK + j]
        pltpu.make_async_copy(new_rows_ref.at[j], out_ref.at[r], sems.at[j]).start()
    for j in range(_BLK):
        r = rows_ref[i * _BLK + j]
        pltpu.make_async_copy(new_rows_ref.at[j], out_ref.at[r], sems.at[j]).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_rows_pallas(
    table: jnp.ndarray,  # [R, W] f32 (in-place via pallas aliasing when the
    # caller's enclosing jit donates it; no eager-level donation here)
    rows: jnp.ndarray,  # [U] int32 row ids; U % 8 == 0
    new_rows: jnp.ndarray,  # [U, W] updated row contents
    interpret: bool = False,
) -> jnp.ndarray:
    """Write updated rows back into the table (PushCopy writeback analog).

    Rows must be unique EXCEPT for repeats carrying byte-identical contents
    (the packer's padding-row repeats) — the push path merges real
    duplicates first (PushMergeCopy parity), so per-row set semantics is
    exact. The table aliases in/out: untouched rows never move.
    """
    U, W = new_rows.shape
    if U % _BLK != 0:
        raise ValueError(f"U={U} must be a multiple of {_BLK}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(U // _BLK,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # table (aliased out)
            pl.BlockSpec((_BLK, W), lambda i, rows_ref: (i, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_BLK,))],
    )
    return pl.pallas_call(
        _writeback_kernel,
        out_shape=jax.ShapeDtypeStruct(table.shape, table.dtype),
        grid_spec=grid_spec,
        input_output_aliases={1: 0},  # table (first arg after scalars) -> out
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
        interpret=interpret,
    )(rows.astype(jnp.int32), table, new_rows)
