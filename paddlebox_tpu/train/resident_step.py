"""Device-resident pass feed: upload the pass once, feed only indices.

The classic feed path ships ~10 bytes/key/batch (uniq_rows + inverse +
segments) from host to device every batch — the MiniBatchGpuPack H2D copy
(data_feed.h:1492-1504), fine over PCIe, dominant over a bandwidth-limited
host<->TPU transport. This path exploits what the reference cannot: the
whole pass is immutable once `begin_pass` runs (PadBoxSlotDataset keeps
`input_records_` frozen for the pass, data_set.cc:1628-1683), so the
row-resolved key stream can live in device HBM for the pass:

- **Upload once per pass**: flat row ids for every key of every record
  (`rows`), per-record per-slot absolute offsets (`off`), labels, optional
  dense features. ~8 bytes/key, once.
- **Per batch**: feed is ONE [B] int32 record-index vector (~16 KB). The
  jitted step rebuilds the batch on device: ragged gather via a scatter
  of segment starts + prefix sum, then cross-slot dedup via sort +
  segment scan (DedupKeysAndFillIdx parity, box_wrapper_impl.h:103 — the
  reference runs the same dedup as a device kernel, not on the host).
- **Superstep**: `lax.scan` over K batches per dispatch amortizes the
  host->device dispatch round-trip (BoxPSWorker's batch loop
  boxps_worker.cc:420-466 collapses into one XLA program per K batches).

The produced per-batch arrays are bit-compatible with BatchPacker.pack
(same slot-major flat order, same padding conventions), and the train-step
body is REUSED from train_step.make_train_step — the resident tier changes
where the batch is assembled, never what the step computes.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from paddlebox_tpu import config
from paddlebox_tpu.data.device_pack import _round_bucket
from paddlebox_tpu.train.table_format import TableFormatProgram
from paddlebox_tpu.train.train_step import TrainStepConfig, make_train_step
from paddlebox_tpu.utils.monitor import STAT_SET
from paddlebox_tpu.utils.trace import record_event

config.define_flag(
    "enable_resident_feed",
    1,
    "keep the pass's row stream resident in device HBM and feed only "
    "record indices per batch (single-device and single-host-mesh fast "
    "path; 0 = classic per-batch host packing)",
)
config.define_flag(
    "resident_scan_batches",
    8,
    "minibatches per dispatched superstep (lax.scan length); higher "
    "amortizes dispatch latency, lower returns metrics sooner",
)


class ResidentPass:
    """Pass-scoped device arrays + static pad shapes for the resident feed.

    Built once per (store, working set); ~8 bytes/key of HBM. ``ensure``
    grows the frozen pad shapes to cover a batch partition (sticky, like
    BatchPacker.freeze_shapes — one compiled program per pass).
    """

    def __init__(
        self,
        store,  # ColumnarRecords
        ws,  # PassWorkingSet (finalized)
        schema,
        dense_slot: Optional[str] = None,
        dense_dim: int = 0,
        label_slot: Optional[str] = None,
        bucket: Optional[int] = None,
        plan=None,  # MeshPlan of the mesh tier (None = single device)
        transport=None,  # host plane; multi-host placement + lockstep
    ):
        self.store = store
        self.ws = ws
        self.num_slots = store.n_sparse
        self.bucket = bucket or config.get_flag("batch_bucket_rounding")
        self.n_table_rows = ws.n_mesh_shards * ws.capacity
        self.pad_row = self.n_table_rows - 1
        with record_event("resident.resolve_rows", "pass") as span:
            rows = store.resolve_rows(ws)
        STAT_SET(
            "resident.resolve_keys_per_s", len(rows) / max(span.seconds, 1e-9)
        )
        if len(store.u64_values) >= (1 << 31):  # int32 src indexing
            raise ValueError("pass too large for resident feed (>=2^31 keys)")
        self._host_rows = rows
        self._key_counts = store.key_counts()
        self.transport = transport
        # multi-host: every host holds a DIFFERENT pass (its local records),
        # so the resident arrays can't replicate — each device carries its
        # own host's copy ([n_dev, ...] device-axis sharded, sizes
        # allreduce-max-padded so every host builds the same global shape)
        self.per_device = (
            plan is not None
            and transport is not None
            and transport.n_ranks > 1
        )

        def _pad(a, n, fill=0):
            if a.shape[0] == n:
                return a
            out = np.full((n,) + a.shape[1:], fill, dtype=a.dtype)
            out[: a.shape[0]] = a
            return out

        if self.per_device:
            self._seq = 0
            L_max = transport.allreduce_max(len(rows), "res-L-size")
            N_max = transport.allreduce_max(len(store), "res-N-size")
        else:
            L_max, N_max = len(rows), len(store)

        @record_event("resident.upload", "pass")
        def place(a):
            if self.per_device:
                from paddlebox_tpu.parallel.mesh import put_per_device_copies

                return put_per_device_copies(plan, a)
            if plan is not None:
                # single-host mesh: replicate ONCE here — left on the
                # default device, every superstep dispatch would re-copy
                # the pass arrays to the other chips for its P() argument
                from paddlebox_tpu.parallel.mesh import put_replicated

                return put_replicated(plan, a)
            return jnp.asarray(a)

        self.rows = place(_pad(rows.astype(np.int32), L_max))
        # per-(record, slot) offsets into the flat row stream. Wire-compact
        # form: per-slot COUNTS fit uint8 (CTR slots hold a handful of
        # feasigns), so the upload ships [N, S] bytes + an [N] int32 base
        # instead of [N, S+1] int32 — ~4x less than the offset matrix, the
        # bulk of the resident upload after `rows`. Offsets rebuild on
        # device as a per-batch cumsum (batch_offsets). Falls back to the
        # full matrix when any slot exceeds 255 keys.
        slot_counts = np.diff(store.u64_offsets.astype(np.int64), axis=1)
        compact = slot_counts.size and slot_counts.max() <= 255
        if self.per_device:
            # lockstep the representation: one host falling back to the
            # offset matrix while another compresses would desync shapes
            compact = transport.allreduce_max(0 if compact else 1, "res-rep") == 0
        if compact:
            self.base = place(_pad(store.u64_base.astype(np.int32), N_max))
            self.counts = place(_pad(slot_counts.astype(np.uint8), N_max))
            self.off = None
        else:
            off = store.u64_base[:, None] + store.u64_offsets.astype(np.int64)
            self.base = None
            self.counts = None
            self.off = place(_pad(off.astype(np.int32), N_max))  # [N, S+1]
        label_name = label_slot or schema.label_slot
        if label_name is not None:
            li = schema.float_slot_index(label_name)
            labels = store.float_slot_matrix(li, 1)[:, 0]
        else:
            labels = np.zeros(len(store), np.float32)
        self.labels = place(_pad(labels.astype(np.float32), N_max))
        self.dense = None
        if dense_slot is not None and dense_dim:
            di = schema.float_slot_index(dense_slot)
            self.dense = place(
                _pad(
                    np.asarray(store.float_slot_matrix(di, dense_dim)), N_max
                )
            )
        self.L_pad = 0
        self.U_pad = 0
        self.K_pad = 0  # mesh tier: per-(device, shard) request bucket
        # keyed by the exact index bytes, not a hash — a collision would
        # freeze U_pad too small and silently merge distinct rows
        self._uniq_cache: Dict[bytes, int] = {}
        self._mesh_cache: Dict = {}  # (device, idx bytes) -> (L, bucket max)

    @record_event("resident.ensure", "pass")
    def ensure(self, batch_indices) -> None:
        """Freeze/grow L_pad and U_pad to cover every batch in the partition
        (exact per-batch max key and unique-row counts; results cached per
        index block so repeated passes over the same partition are free).
        Uncached blocks sweep in ONE native GIL-released call
        (pbx_block_stats with ns=1: total uniques) — the counter side of
        the reference's pass equalization (data_set.cc:2069-2135), keeping
        pass prepare off the Python critical path."""
        blocks = [np.asarray(idx) for idx in batch_indices]
        fps = [b.tobytes() for b in blocks]
        pending, seen = [], set()
        for b, fp in zip(blocks, fps):
            if fp not in self._uniq_cache and fp not in seen:
                pending.append((fp, b))
                seen.add(fp)
        if pending:
            stats = _native_pad_stats(
                self, [b for _, b in pending], self.n_table_rows, 1
            )
            if stats is not None:
                for (fp, _), U in zip(pending, stats[1]):
                    self._uniq_cache[fp] = max(int(U), 1)
            else:
                from paddlebox_tpu.data.record_store import _ragged_indices

                for fp, idx in pending:
                    base = self.store.u64_base[idx]
                    counts = self._key_counts[idx]
                    rows = self._host_rows[_ragged_indices(base, counts)]
                    self._uniq_cache[fp] = (
                        len(np.unique(rows)) if len(rows) else 1
                    )
        max_L, max_U = 1, 1
        for b, fp in zip(blocks, fps):
            max_L = max(max_L, int(self._key_counts[b].sum()))
            max_U = max(max_U, self._uniq_cache[fp])
        self.L_pad = max(self.L_pad, _round_bucket(max_L, self.bucket))
        # +1 keeps a dedicated slot for the invalid tail even when a batch
        # is exactly at the unique maximum
        self.U_pad = max(self.U_pad, _round_bucket(max_U + 1, self.bucket))



@jax.named_scope("offsets")
def _batch_offsets(arrs: Dict[str, jnp.ndarray], idx: jnp.ndarray) -> jnp.ndarray:
    """[B, S+1] absolute flat-stream offsets for a batch, from whichever
    resident representation was uploaded (full matrix, or base+uint8
    counts rebuilt by cumsum on device)."""
    if arrs.get("off") is not None:
        return arrs["off"][idx]
    c = arrs["counts"][idx].astype(jnp.int32)  # [B, S]
    cum = jnp.cumsum(c, axis=1)
    zero = jnp.zeros((cum.shape[0], 1), jnp.int32)
    return arrs["base"][idx][:, None] + jnp.concatenate([zero, cum], axis=1)


@jax.named_scope("ragged_rows")
def _ragged_rows(
    rows_res: jnp.ndarray,
    off_b: jnp.ndarray,  # [B, S+1] this batch's absolute offsets
    S: int,
    B: int,
    L_pad: int,
    pad_value,
):
    """Shared ragged gather: batch offsets -> (rows_flat, segments, valid)
    in slot-major flat order. ``pad_value`` fills invalid tail rows (the
    single-device tier pads with the real padding row; the mesh tier with
    an out-of-range sentinel its sort treats as +inf).

    Flat positions and segment starts are both sorted, so nothing is
    searched: every segment marks the flat position it begins at, and a
    prefix sum over the positions carries the marks forward. Segment s
    begins at ``begin[s]`` and its keys lie ``delta[s]`` further on in the
    pass's row stream; marks of 1 sum to the segment id, marks of
    ``delta[s] - delta[s-1]`` telescope to ``delta`` of the segment a
    position is in. Zero-length segments share a begin with the next one
    and need no case of their own."""
    lens_b = off_b[:, 1:] - off_b[:, :-1]
    starts_b = off_b[:, :-1]
    lens_flat = lens_b.T.reshape(-1)  # [S*B] slot-major
    starts_flat = starts_b.T.reshape(-1)
    cum = jnp.cumsum(lens_flat)
    L_real = cum[-1]
    pos = jnp.arange(L_pad, dtype=jnp.int32)
    with jax.named_scope("segment_scan"):
        begin = cum - lens_flat  # non-decreasing; begin[0] == 0
        delta = starts_flat - begin
        zero = jnp.zeros((1,), jnp.int32)

        def carried(marks):
            # segments that begin at or past L_pad hold no position: dropped
            at_begin = jnp.zeros((L_pad,), jnp.int32).at[begin]
            return jnp.cumsum(
                at_begin.add(marks, mode="drop", indices_are_sorted=True)
            )

        seg_c = carried(jnp.concatenate([zero, jnp.ones((S * B - 1,), jnp.int32)]))
        ahead = carried(delta - jnp.concatenate([zero, delta[:-1]]))
    with jax.named_scope("row_gather"):
        src = jnp.clip(pos + ahead, 0, rows_res.shape[0] - 1)
        valid = pos < L_real
        rows_flat = jnp.where(valid, rows_res[src], pad_value)
    segments = jnp.where(valid, seg_c, S * B)  # seg_c IS slot*B + ins
    return rows_flat, segments, valid


@jax.named_scope("build_batch")
def build_device_batch(
    rp: ResidentPass, cfg: TrainStepConfig, idx: jnp.ndarray
) -> Dict[str, jnp.ndarray]:
    """[B] record indices -> the classic step's batch dict, all on device.

    Produces the same arrays BatchPacker.pack ships from the host (slot-
    major flat order, pads -> padding row / U_pad-1 / S*B trash segment),
    so make_train_step's body consumes either source interchangeably.
    """
    S, B = cfg.num_slots, cfg.batch_size
    L_pad, U_pad = rp.L_pad, rp.U_pad
    off_b = _batch_offsets(
        {"off": rp.off, "base": rp.base, "counts": rp.counts}, idx
    )
    rows_flat, segments, valid = _ragged_rows(
        rp.rows, off_b, S, B, L_pad, rp.pad_row
    )
    # cross-slot dedup on device: sort rows, first-occurrence scan
    INF = jnp.int32(rp.n_table_rows)
    with jax.named_scope("dedup_sort"):
        sort_keys = jnp.where(valid, rows_flat, INF)
        sorted_rows, perm = jax.lax.sort_key_val(
            sort_keys, jnp.arange(L_pad, dtype=jnp.int32)
        )
    with jax.named_scope("dedup_scan"):
        real = sorted_rows < INF
        first = (
            jnp.concatenate(
                [jnp.ones((1,), jnp.bool_), sorted_rows[1:] != sorted_rows[:-1]]
            )
            & real
        )
        segid = jnp.minimum(jnp.cumsum(first.astype(jnp.int32)) - 1, U_pad - 1)
        segid = jnp.where(real, segid, U_pad - 1)
        uniq = jax.ops.segment_max(
            jnp.where(real, sorted_rows, -1), segid, num_segments=U_pad
        )
        uniq_rows = jnp.where(uniq >= 0, uniq, rp.pad_row).astype(jnp.int32)
    with jax.named_scope("inverse_scatter"):
        inverse = jnp.zeros((L_pad,), jnp.int32).at[perm].set(segid)
    batch = {
        "uniq_rows": uniq_rows,
        "inverse": inverse,
        "segments": segments,
        "labels": rp.labels[idx],
    }
    if rp.dense is not None:
        batch["dense"] = rp.dense[idx]
    return batch


def per_batch_metrics(mstack: Dict[str, jnp.ndarray]) -> tuple:
    """A scan's stacked metrics ``{field: [K, ...]}`` as K dicts of one batch
    each. Every superstep calls this on its own scan outputs before it
    returns (K x fields static slices inside the program, microseconds), so
    the host indexes a tuple between two dispatches and launches nothing:
    an eager ``v[j]`` is a device program of its own, queued behind the
    superstep in flight."""
    k = len(next(iter(mstack.values())))
    return tuple({f: v[j] for f, v in mstack.items()} for j in range(k))


def make_resident_superstep(
    model_apply: Callable,
    dense_opt,
    cfg: TrainStepConfig,
    rp: ResidentPass,
    eval_mode: bool = False,
) -> Callable:
    """Build ``superstep(state, idx_block [K, B]) -> (state, metrics[K])``.

    One dispatch runs K full train steps via lax.scan; metrics come back a
    batch at a time, a tuple of K dicts (per_batch_metrics). The per-step
    body is the classic make_train_step — only batch assembly is resident.
    The table crosses a dispatch in the layout the scan carries it in
    (train/table_format.py)."""
    raw_step = make_train_step(model_apply, dense_opt, cfg, eval_mode=eval_mode)
    if cfg.sequence_len and not np.all(rp._key_counts == cfg.sequence_len):
        # the rows reach the model as [B, T, embedx]: a record of another
        # length would shift every record behind it
        raise ValueError(
            f"a sequence feed of {cfg.sequence_len} keys a record, but the pass has "
            f"records of {int(rp._key_counts.min())} to {int(rp._key_counts.max())}"
        )

    def body(state, idx):
        batch = build_device_batch(rp, cfg, idx)
        return raw_step(state, batch)

    def superstep(state, idx_block):
        state, mstack = jax.lax.scan(body, state, idx_block)
        return state, per_batch_metrics(mstack)

    return TableFormatProgram(superstep)


# ---- resident pv (join-phase) tier -----------------------------------------


class ResidentPvFeed:
    """The pass's PvPlan uploaded to device HBM once.

    Join-phase batches are pass-deterministic after ``preprocess_instance``
    (PvPlan), so the per-batch feed shrinks to a [K] vector of BATCH
    POSITIONS — even smaller than the flat tier's [K, B] index feed. The
    jitted step gathers the batch's record indices, rank_offset, and
    ins_weight from these resident arrays (the reference keeps pv batches on
    the same MiniBatchGpuPack fast path as flat ones, data_feed.cc:2404-2522;
    here they additionally skip the host entirely).

    Mesh layout: idx/ro/w reshape to [n_b, n_dev, ...] and shard on the
    device axis, so each device stores and reads only its own block.
    """

    def __init__(self, plan, mesh_plan=None):
        idx = plan.idx.astype(np.int32)
        ro = plan.rank_offset
        w = plan.ins_weight
        self.n_batches = plan.n_batches
        if mesh_plan is None:
            self.idx = jnp.asarray(idx)  # [n_b, B]
            self.ro = jnp.asarray(ro)  # [n_b, B, R]
            self.w = jnp.asarray(w)  # [n_b, B]
        else:
            from paddlebox_tpu.parallel.mesh import put_axis1_blocks

            nd_local = mesh_plan.n_devices // jax.process_count()
            if plan.n_devices != nd_local:
                raise ValueError(
                    f"PvPlan built for {plan.n_devices} devices, this "
                    f"process packs for {nd_local}"
                )
            n_b, B = idx.shape
            b = B // nd_local

            def shard(a, *trail):
                # [n_b, n_local, b, ...] local blocks -> global
                # [n_b, n_dev, b, ...] sharded on the device axis
                # (single- and multi-host; hosts contribute their own
                # plans' blocks, n_b locksteped via min_batches)
                return put_axis1_blocks(
                    mesh_plan, a.reshape(n_b, nd_local, b, *trail)
                )

            self.idx = shard(idx)  # [n_b, n_dev, b]
            self.ro = shard(ro, ro.shape[-1])  # [n_b, n_dev, b, R]
            self.w = shard(w)  # [n_b, n_dev, b]


def make_resident_pv_superstep(
    model_apply: Callable,
    dense_opt,
    cfg: TrainStepConfig,
    rp: ResidentPass,
    feed: ResidentPvFeed,
    eval_mode: bool = False,
) -> Callable:
    """``superstep(state, pos_block [K]) -> (state, metrics[K])``: the pv
    analog of make_resident_superstep. Batch assembly reuses
    build_device_batch (ghosts are ordinary repeated records; their
    weight-0 rows add no loss, no show/clk, no AUC — same contract as the
    host-packed pv path)."""
    raw_step = make_train_step(model_apply, dense_opt, cfg, eval_mode=eval_mode)

    def body(state, pos):
        batch = build_device_batch(rp, cfg, feed.idx[pos])
        batch["ins_weight"] = feed.w[pos]
        batch["rank_offset"] = feed.ro[pos]
        return raw_step(state, batch)

    def superstep(state, pos_block):
        state, mstack = jax.lax.scan(body, state, pos_block)
        return state, per_batch_metrics(mstack)

    return TableFormatProgram(superstep)


def make_resident_pv_mesh_superstep(
    model_apply: Callable,
    dense_opt,
    cfg: TrainStepConfig,
    rp: ResidentPass,
    feed: ResidentPvFeed,
    plan,
    eval_mode: bool = False,
) -> Callable:
    """Mesh pv superstep: ``superstep(state, pos_block [K])``.

    Single- AND multi-host: the pv arrays are device-axis sharded (each
    device holds its own [n_b, 1, b] block — on a multi-host mesh, of its
    own host's locksteped plan); the position feed is replicated (n_b is
    equalized via ghost batches). Per-device batch assembly and step body
    are shared with the flat mesh tier; multi-host additionally requires
    per-device resident pass arrays (rp.per_device)."""
    import jax as _jax

    from paddlebox_tpu.parallel.mesh import shard_map as _mesh_shard_map
    from jax.sharding import PartitionSpec as P

    from paddlebox_tpu.train.sharded_step import (
        make_local_mesh_step,
        mesh_metric_specs,
        mesh_state_specs,
    )

    if _jax.process_count() > 1 and not rp.per_device:
        raise RuntimeError(
            "multi-host resident pv feed needs per-device pass arrays — "
            "build the ResidentPass with plan= and a multi-rank transport="
        )
    local_step = make_local_mesh_step(model_apply, dense_opt, cfg, plan, eval_mode)
    ns, cap = rp.ws.n_mesh_shards, rp.ws.capacity
    L_pad, K = rp.L_pad, rp.K_pad
    rp_arrays = _resident_arrays(rp)
    per_device = rp.per_device

    def superstep_local(state, pos_block, arrs, pv_idx, pv_ro, pv_w):
        if per_device:  # multi-host: each device carries its host's arrays
            arrs = {k: v[0] for k, v in arrs.items()}

        def body(st, pos):
            batch = build_mesh_device_batch(
                arrs, cfg, pv_idx[pos, 0], L_pad, K, ns, cap
            )
            batch = {k: v[None] for k, v in batch.items()}
            batch["ins_weight"] = pv_w[pos]  # [1, b] local block
            batch["rank_offset"] = pv_ro[pos]  # [1, b, R]
            return local_step(st, batch)

        return _jax.lax.scan(body, state, pos_block)

    state_specs = mesh_state_specs(cfg, dense_opt, plan)
    per_step = mesh_metric_specs(cfg, plan, eval_mode)
    metric_specs = {
        k: (P(None, *s) if s else P()) for k, s in per_step.items()
    }
    rep = P()
    ax = plan.axis
    arr_specs = {k: (P(ax) if per_device else P()) for k in rp_arrays}

    def superstep(state, pos_block, arrs, pv_idx, pv_ro, pv_w):
        mapped = _mesh_shard_map(
            superstep_local,
            mesh=plan.mesh,
            in_specs=(
                state_specs,
                rep,  # batch positions: replicated
                arr_specs,  # replicated, or per-device host copies
                P(None, ax, None),  # pv_idx [n_b, n_dev, b]
                P(None, ax, None, None),  # pv_ro
                P(None, ax, None),  # pv_w
            ),
            out_specs=(state_specs, metric_specs),
            check_vma=False,
        )
        state, mstack = mapped(state, pos_block, arrs, pv_idx, pv_ro, pv_w)
        return state, per_batch_metrics(mstack)

    jitted = _jax.jit(superstep, donate_argnums=(0,))

    def call(state, pos_block):
        # multi-host arrays must be jit ARGUMENTS, not closure constants
        return jitted(state, pos_block, rp_arrays, feed.idx, feed.ro, feed.w)

    call.lower = lambda state, pos_block: jitted.lower(
        state, pos_block, rp_arrays, feed.idx, feed.ro, feed.w
    )
    return call


# ---- mesh (single-host) resident tier --------------------------------------


def _native_pad_stats(rp: ResidentPass, slices, cap: int, ns: int):
    """One GIL-released pbx_block_stats sweep over equal-length index
    slices -> (L[n], bmax[n]), or None when the native tier is absent or
    the slices are ragged (caller falls back to the per-block numpy
    sweep)."""
    from paddlebox_tpu.utils import native

    if not native.available() or not slices:
        return None
    if len({len(s) for s in slices}) != 1:
        return None
    blocks = np.stack([np.asarray(s, dtype=np.int64) for s in slices])
    return native.block_stats(
        rp._host_rows, rp.store.u64_base, rp._key_counts, blocks, cap, ns
    )


def ensure_sharded(rp: ResidentPass, batch_indices, n_devices: int) -> None:
    """Freeze/grow the mesh pads: per-DEVICE L_pad and the per-(device,
    shard) request bucket K_pad (exact scan, cached per index block — the
    resident analog of BatchPacker.freeze_shapes' lockstep branch).
    ``n_devices`` is the count THIS process packs for (local on a
    multi-host mesh); with a multi-rank transport on the ResidentPass the
    pads are allreduce-max'd so every host compiles the same program.
    Uncached device blocks sweep in ONE native call (pbx_block_stats) —
    pass prepare is one native counter sweep + one allreduce, the
    reference's equalization shape (data_set.cc:2069-2135)."""
    cap, ns = rp.ws.capacity, rp.ws.n_mesh_shards
    work = []  # (fp, slice) per device block, cache-order
    pending, seen = [], set()
    for idx in batch_indices:
        idx = np.asarray(idx)
        if len(idx) % n_devices:
            raise ValueError(
                f"batch of {len(idx)} records not divisible by "
                f"{n_devices} devices (same contract as the host packer)"
            )
        b = len(idx) // n_devices
        for d in range(n_devices):
            sl = idx[d * b : (d + 1) * b]
            fp = (d, sl.tobytes())
            work.append(fp)
            if fp not in rp._mesh_cache and fp not in seen:
                pending.append((fp, sl))
                seen.add(fp)
    if pending:
        stats = _native_pad_stats(rp, [s for _, s in pending], cap, ns)
        if stats is not None:
            for (fp, _), L, bm in zip(pending, stats[0], stats[1]):
                rp._mesh_cache[fp] = (int(L), int(bm))
        else:
            from paddlebox_tpu.data.record_store import _ragged_indices

            for fp, sl in pending:
                counts = rp._key_counts[sl]
                rows = rp._host_rows[
                    _ragged_indices(rp.store.u64_base[sl], counts)
                ]
                L = len(rows)
                if L:
                    uniq = np.unique(rows)
                    bmax = int(np.bincount(uniq // cap, minlength=ns).max())
                else:
                    bmax = 0
                rp._mesh_cache[fp] = (L, bmax)
    max_L, max_bucket = 1, 0
    for fp in work:
        cached = rp._mesh_cache[fp]
        max_L = max(max_L, cached[0])
        max_bucket = max(max_bucket, cached[1])
    L = _round_bucket(max_L, rp.bucket)
    K = _round_bucket(max_bucket + 1, rp.bucket)
    tp = rp.transport
    if tp is not None and tp.n_ranks > 1:
        # lockstep: every host enters these collectives the same number of
        # times (the stepper/prepare call sequence is uniform), tagged by a
        # per-ResidentPass counter
        rp._seq += 1
        L = tp.allreduce_max(L, f"res-L:{rp._seq}")
        K = tp.allreduce_max(K, f"res-K:{rp._seq}")
    rp.L_pad = max(rp.L_pad, L)
    rp.K_pad = max(rp.K_pad, K)


@jax.named_scope("build_batch")
def build_mesh_device_batch(
    rp_arrays: Dict[str, jnp.ndarray],
    cfg: TrainStepConfig,
    idx_dev: jnp.ndarray,  # [b] this device's record indices
    L_pad: int,
    K: int,
    ns: int,
    cap: int,
) -> Dict[str, jnp.ndarray]:
    """One device's mesh batch (req_ranks/inverse/segments/labels) built on
    device from the resident arrays — the _route_sharded host routine as
    static-shape XLA ops (sort groups rows by owner shard for free since
    global row ids are shard-major: row = shard*cap + rank)."""
    S, b = cfg.num_slots, cfg.batch_size
    rows_res, labels_res = rp_arrays["rows"], rp_arrays["labels"]
    off_b = _batch_offsets(rp_arrays, idx_dev)
    rows_flat, segments, valid = _ragged_rows(
        rows_res, off_b, S, b, L_pad, jnp.int32(ns * cap)
    )

    # route: sort by global row id (== by owner shard), first-occurrence
    # scan assigns each unique row its request-bucket slot j within its
    # shard; pads ride in bucket (shard 0, K-1), whose row is the reserved
    # padding row cap-1 via the req_ranks fill
    INF = jnp.int32(ns * cap)  # rows_flat already pads with this sentinel
    sorted_rows, perm = jax.lax.sort_key_val(
        rows_flat, jnp.arange(L_pad, dtype=jnp.int32)
    )
    real = sorted_rows < INF
    first = (
        jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), sorted_rows[1:] != sorted_rows[:-1]]
        )
        & real
    )
    uniq_seq = jnp.cumsum(first.astype(jnp.int32)) - 1  # global unique ordinal
    shard = jnp.where(real, sorted_rows // cap, 0)
    cnts = jax.ops.segment_sum(
        first.astype(jnp.int32), shard, num_segments=ns
    )  # uniques per shard
    shard_start = jnp.cumsum(cnts) - cnts  # exclusive
    j = jnp.clip(uniq_seq - shard_start[shard], 0, K - 2)
    bucket_sorted = jnp.where(real, shard * K + j, (K - 1))  # pads -> shard 0
    inverse = jnp.zeros((L_pad,), jnp.int32).at[perm].set(bucket_sorted)
    # request matrix: rank-within-shard at (shard, j) for each first
    # occurrence; everything else (incl. the K-1 pad slot) = cap-1 pad row
    flat_pos = jnp.where(first, shard * K + j, ns * K)  # non-first -> dropped
    req_ranks = (
        jnp.full((ns * K,), cap - 1, jnp.int32)
        .at[flat_pos]
        .set(jnp.where(real, sorted_rows % cap, cap - 1).astype(jnp.int32),
             mode="drop")
        .reshape(ns, K)
    )
    out = {
        "req_ranks": req_ranks,
        "inverse": inverse,
        "segments": segments,
        "labels": labels_res[idx_dev],
    }
    if "dense" in rp_arrays:
        out["dense"] = rp_arrays["dense"][idx_dev]
    return out


def make_resident_mesh_superstep(
    model_apply: Callable,
    dense_opt,
    cfg: TrainStepConfig,
    rp: ResidentPass,
    plan,
    eval_mode: bool = False,
) -> Callable:
    """``superstep(state, idx_block [K_scan, n_dev, b]) -> (state, metrics)``
    on a SINGLE-HOST mesh: resident arrays replicated across local devices,
    each device builds its own route buckets, then the shared per-device
    mesh step body runs (make_local_mesh_step — identical numerics to the
    host-packed path)."""
    import jax as _jax

    from paddlebox_tpu.parallel.mesh import shard_map as _mesh_shard_map
    from jax.sharding import PartitionSpec as P

    from paddlebox_tpu.train.sharded_step import (
        make_local_mesh_step,
        mesh_metric_specs,
        mesh_state_specs,
    )

    if _jax.process_count() > 1 and not rp.per_device:
        raise RuntimeError(
            "multi-host resident feed needs per-device pass arrays — build "
            "the ResidentPass with plan= and a multi-rank transport="
        )
    local_step = make_local_mesh_step(model_apply, dense_opt, cfg, plan, eval_mode)
    ns, cap = rp.ws.n_mesh_shards, rp.ws.capacity
    L_pad, K = rp.L_pad, rp.K_pad

    rp_arrays = _resident_arrays(rp)
    per_device = rp.per_device

    def superstep_local(state, idx_block, arrs):
        if per_device:  # each device carries its host's copy: strip [1,...]
            arrs = {k: v[0] for k, v in arrs.items()}

        def body(st, idx):  # idx [1, b] (this device's slice)
            batch = build_mesh_device_batch(
                arrs, cfg, idx[0], L_pad, K, ns, cap
            )
            batch = {k: v[None] for k, v in batch.items()}
            return local_step(st, batch)

        return _jax.lax.scan(body, state, idx_block)

    state_specs = mesh_state_specs(cfg, dense_opt, plan)
    # per-step metric specs shift one dim right under the scan stacking:
    # preds/labels come out [K_scan, b] per device and assemble
    # [K_scan, n_dev*b] — P(axis) on dim 0 would interleave devices into
    # the scan axis and hand consumers only device 0's slice
    per_step = mesh_metric_specs(cfg, plan, eval_mode)
    metric_specs = {
        k: (P(None, *s) if s else P()) for k, s in per_step.items()
    }

    arr_specs = {
        k: (P(plan.axis) if per_device else P()) for k in rp_arrays
    }

    def superstep(state, idx_block, arrs):
        mapped = _mesh_shard_map(
            superstep_local,
            mesh=plan.mesh,
            in_specs=(
                state_specs,
                P(None, plan.axis),  # scan axis whole, device axis split
                arr_specs,  # replicated, or per-device host copies
            ),
            out_specs=(state_specs, metric_specs),
            check_vma=False,
        )
        state, mstack = mapped(state, idx_block, arrs)
        return state, per_batch_metrics(mstack)

    jitted = _jax.jit(superstep, donate_argnums=(0,))

    def call(state, idx_block):
        # multi-host arrays span non-addressable devices: they must enter
        # the jit as ARGUMENTS, not closure constants
        return jitted(state, idx_block, rp_arrays)

    call.lower = lambda state, idx_block: jitted.lower(state, idx_block, rp_arrays)
    return call


def _resident_arrays(rp: ResidentPass) -> Dict[str, jnp.ndarray]:
    """The resident arrays a mesh superstep threads through shard_map —
    only the representation that was actually uploaded (off matrix, or
    base+counts), plus optional dense features."""
    arrs = {"rows": rp.rows, "labels": rp.labels}
    if rp.off is not None:
        arrs["off"] = rp.off
    else:
        arrs["base"] = rp.base
        arrs["counts"] = rp.counts
    if rp.dense is not None:
        arrs["dense"] = rp.dense
    return arrs
