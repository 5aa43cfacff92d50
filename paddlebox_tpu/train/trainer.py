"""Pass-loop trainer: the BoxPSTrainer/BoxPSWorker + Executor analog.

The reference drives training through Executor::RunFromDataset spawning one
BoxPSWorker thread per GPU (boxps_trainer.cc:186-200); here one CTRTrainer
owns the jitted step (single-device or mesh — the mesh step already contains
every device's work) and walks a BoxPSDataset pass by pass:

    trainer = CTRTrainer(model, cfg, plan=...)
    dataset.load_into_memory(); dataset.begin_pass()
    metrics = trainer.train_pass(dataset)
    # single-process: hand the DEVICE table over — the boundary then goes
    # delta-only (table/carrier.py); multi-host uses trained_table()
    dataset.end_pass(trainer.trained_table_device(), need_save_delta=...)

Dense params/optimizer state persist across passes on device; the sparse
working-set table is rebuilt per pass (pass-scoped HBM staging parity).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

from paddlebox_tpu.data.dataset import BoxPSDataset
from paddlebox_tpu.fleet.zero import Zero1Optimizer
from paddlebox_tpu.data.device_pack import BatchPacker, pack_batch, pack_batch_sharded
from paddlebox_tpu.data.pipeline import prefetch
from paddlebox_tpu.metrics.auc import auc_compute, auc_init
from paddlebox_tpu.metrics.registry import MetricRegistry
from paddlebox_tpu.obs.program_scopes import (
    REGISTRY as PROGRAMS,
    memory_of,
    table_layout_of,
)
from paddlebox_tpu.parallel.mesh import (
    MeshPlan,
    local_slice,
    put_replicated,
    put_sharded,
)
from paddlebox_tpu.train.sharded_step import (
    init_sharded_train_state,
    kstep_sync_params,
    make_sharded_train_step,
)
from paddlebox_tpu.train.resident_step import (
    ResidentPass,
    make_resident_superstep,
)
from paddlebox_tpu.train.table_format import (
    aval_of as _aval_of,
    jit_state_step,
    put_table,
)
from paddlebox_tpu.train.train_step import (
    TrainState,
    TrainStepConfig,
    jit_train_step,
    make_train_step,
)
from paddlebox_tpu.utils.dump import DumpWorkerPool, dump_fields, dump_param
from paddlebox_tpu.utils.faultinject import fire as _fault_fire
from paddlebox_tpu.utils.fs import atomic_write
from paddlebox_tpu.utils.trace import PROFILER
from paddlebox_tpu import config

config.define_flag(
    "max_inflight_steps",
    4,
    "cap on dispatched-but-unfinished device steps; 0 = unbounded. Keeps "
    "the async dispatch queue shallow: enough depth to hide host->device "
    "round-trip latency behind compute, shallow enough that transfers and "
    "executions don't pile up on the transport",
)


class CTRTrainer:
    def __init__(
        self,
        model: Any,  # object with .init(rng) / .apply(params, slot_feats, dense)
        cfg: TrainStepConfig,
        dense_opt: Optional[optax.GradientTransformation] = None,
        plan: Optional[MeshPlan] = None,
        dense_slot: Optional[str] = None,
        dense_dim: int = 0,
        pack_bucket: Optional[int] = None,
        metric_registry: Optional["MetricRegistry"] = None,
        async_dense: Optional["AsyncDenseTable"] = None,
        dump_pool: Optional["DumpWorkerPool"] = None,
        dump_fields_list: Sequence[str] = ("preds", "labels"),
        dump_mode: int = 0,  # 0 all, 1 sample-by-ins-id-hash, 2 every Nth batch
        dump_interval: int = 1,
        dump_params_at_end: bool = False,
        box: Optional[Any] = None,  # BoxWrapper whose test_mode gates eval
    ):
        self.model = model
        if getattr(model, "sequence_feed", False):
            # the model object says what it needs; the step builders read it
            # from the config, whatever wraps model.apply
            if cfg.sequence_len not in (0, model.seq_len):
                raise ValueError(
                    f"cfg.sequence_len {cfg.sequence_len} against the model's "
                    f"seq_len {model.seq_len}"
                )
            cfg = dataclasses.replace(cfg, sequence_len=model.seq_len)
        self.cfg = cfg
        self.dense_opt = dense_opt or optax.adam(1e-3)
        self.plan = plan
        self.async_dense = async_dense
        if isinstance(self.dense_opt, Zero1Optimizer) and plan is None:
            raise ValueError(
                "Zero1Optimizer (sharding strategy) needs a mesh plan — its "
                "optimizer state lives sharded across devices"
            )
        if cfg.dense_sync_mode == "async":
            if async_dense is None:
                raise ValueError(
                    "dense_sync_mode='async' needs an AsyncDenseTable (else "
                    "dense params would silently never update)"
                )
            if plan is not None and jax.process_count() > 1:
                # each process would push globally-reduced grads into its
                # own host table: consistent only under bit-identical update
                # rules AND lossless comms — not a guarantee worth making
                raise NotImplementedError(
                    "async dense mode spans one process (single-device or "
                    "single-host mesh); multi-host meshes use 'step'/'kstep'"
                )
        self.dense_slot = dense_slot
        self.dense_dim = dense_dim
        self.pack_bucket = pack_bucket
        self.metric_registry = metric_registry
        # per-batch field/param debug dumps (DeviceWorker::DumpField/DumpParam
        # parity, device_worker.cc:98-133; modes per device_worker.h:218-219)
        self.dump_pool = dump_pool
        self.dump_fields_list = tuple(dump_fields_list)
        self.dump_mode = dump_mode
        self.dump_interval = dump_interval
        self.dump_params_at_end = dump_params_at_end
        self.params: Any = None
        self.opt_state: Any = None
        self._state: Optional[TrainState] = None
        self._table_src: Any = None  # a pass table on its way up (_table_up)
        self._table_fmt: Any = None  # the format a superstep put it up in
        self._table_steps: Dict = {}  # per-batch steps that keep such a format
        self._dense_in_place = False  # see hand_over_dense
        self._dense_with_pass = False  # handed over, and not (yet) returned
        # eval/infer mode (SetTestMode box_wrapper.cc:623 +
        # infer_from_dataset executor.py:1520): either set directly on the
        # trainer or inherited from the owning BoxWrapper each pass
        self.box = box
        self.test_mode = False
        self._eval_step_cache = None
        if plan is None:
            self._step = jit_train_step(make_train_step(model.apply, self.dense_opt, cfg))
        else:
            self._step = make_sharded_train_step(model.apply, self.dense_opt, cfg, plan)

    # ---- eval mode -------------------------------------------------------

    def set_test_mode(self, on: bool = True) -> None:
        """SetTestMode parity: the next train_pass runs forward+metrics only
        (no sparse push, no dense update) until cleared."""
        self.test_mode = on

    @property
    def _eval_active(self) -> bool:
        return self.test_mode or bool(self.box is not None and self.box.test_mode)

    def _eval_step(self):
        if self._eval_step_cache is None:
            if self.plan is None:
                self._eval_step_cache = jit_train_step(
                    make_train_step(
                        self.model.apply, self.dense_opt, self.cfg, eval_mode=True
                    )
                )
            else:
                self._eval_step_cache = make_sharded_train_step(
                    self.model.apply, self.dense_opt, self.cfg, self.plan,
                    eval_mode=True,
                )
        return self._eval_step_cache

    # ---- dense param lifecycle ------------------------------------------

    def init_params(self, rng=None) -> None:
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        self._dense_with_pass = False
        self.params = self.model.init(rng)
        if isinstance(self.dense_opt, Zero1Optimizer):
            # chunked state is built (and placed sharded) by
            # init_sharded_train_state at pass start
            self.opt_state = None
        else:
            self.opt_state = self.dense_opt.init(self.params)

    def hand_over_dense(self, params: Any = None, opt_state: Any = None) -> None:
        """Train the dense state in place: from this call on no pass makes a
        second copy of it.

        By default a pass trains device copies of ``params`` / ``opt_state``
        (the step donates its state), so a reader of ``trainer.params`` — a
        mid-pass ``save_dense``, a follower, a rollback — sees the values the
        pass opened with. A dense state that cannot lie twice on the chip (a
        language model's parameters and moments are most of its memory) is
        handed over instead: every pass takes the trainer's own buffers.
        While a pass runs, ``params`` and ``opt_state`` are None and
        ``save_dense`` raises; ``train_pass`` re-points them at what the
        steps returned when it ends — after a failed pass too, if the buffers
        survived it. If they did not (an XLA error after donation) the state
        is gone: ``init_params()``, then ``load_dense`` a checkpoint.

        ``params`` given replace the trainer's (``opt_state`` fresh from the
        optimizer unless given too); the caller keeps no use of them."""
        if self.plan is not None:
            raise NotImplementedError(
                "the mesh path shards and copies the dense state itself "
                "(init_sharded_train_state)"
            )
        if params is not None:
            self.params = params
            self.opt_state = opt_state if opt_state is not None else self.dense_opt.init(params)
            self._dense_with_pass = False
        self._dense_in_place = True

    def save_dense(self, path: str) -> None:
        """Dense checkpoint (worker-scope param dump parity,
        boxps_trainer.cc:123-131). Written tmp-then-rename so a crash
        mid-write can't corrupt the checkpoint a cursor already points to."""
        if self._dense_in_place and self.params is None:
            # flattening (None, None) would replace a good checkpoint with
            # an npz of no leaves
            raise RuntimeError(
                "the dense state is handed over (hand_over_dense) and a pass "
                "holds it: save after train_pass returns. If a pass failed "
                "and took it along, init_params() and load_dense a checkpoint"
            )
        path = path if path.endswith(".npz") else path + ".npz"
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        leaves, treedef = jax.tree.flatten((self.params, self.opt_state))
        with atomic_write(path, "wb") as f:
            np.savez_compressed(
                f,
                treedef=str(treedef),
                **{f"leaf_{i}": np.asarray(x) for i, x in enumerate(leaves)},
            )

    def load_dense(self, path: str) -> None:
        if self.params is None:
            raise RuntimeError("init_params first (defines the tree structure)")
        if self.opt_state is None and isinstance(self.dense_opt, Zero1Optimizer):
            # rebuild the chunked-state structure so the checkpoint's zero
            # moment leaves have somewhere to land (fresh-process resume)
            self.opt_state = self.dense_opt.init_stacked(self.params)
        path = path if path.endswith(".npz") else path + ".npz"
        data = np.load(path, allow_pickle=False)
        leaves, treedef = jax.tree.flatten((self.params, self.opt_state))
        n_saved = sum(1 for k in data.files if k.startswith("leaf_"))
        if n_saved != len(leaves):
            raise ValueError(
                f"checkpoint holds {n_saved} leaves but the current "
                f"(params, opt_state) tree has {len(leaves)} — optimizer "
                "state mismatch (e.g. ZeRO chunked state not yet built: "
                "restore it with the same opt_state structure it was saved "
                "with, or load before switching optimizers)"
            )
        loaded = [jnp.asarray(data[f"leaf_{i}"]) for i in range(len(leaves))]
        for a, b in zip(leaves, loaded):
            if a.shape != b.shape:
                raise ValueError(f"dense checkpoint shape mismatch {a.shape} vs {b.shape}")
        self.params, self.opt_state = jax.tree.unflatten(treedef, loaded)

    # ---- pass loop -------------------------------------------------------

    def _make_state(self, dev_table: np.ndarray, ws_key: Optional[int] = None) -> TrainState:
        # within one pass (same working set), later train_pass calls — the
        # update phase after join, extra epochs, eval — must see the rows
        # the earlier calls trained, exactly as the reference's device table
        # persists between phases (BeginPass..EndPass, box_wrapper.cc:
        # 615-651). Rebuild only when the working set changes.
        if (
            self._state is not None
            and ws_key is not None
            and getattr(self, "_state_ws", None) is ws_key
        ):
            if self._dense_in_place:  # the cached state's buffers are the trainer's
                self.params = self.opt_state = None
                self._dense_with_pass = True
            return self._state
        self._state_ws = ws_key
        if self.params is None:
            if self._dense_with_pass:
                raise RuntimeError(
                    "the dense state was handed to a pass that failed and took "
                    "it along (hand_over_dense keeps no copy): init_params(), "
                    "then load_dense a checkpoint, before training on"
                )
            self.init_params()
        if self.plan is None:
            # device COPIES of params/opt_state: the step donates its state,
            # so handing self.params's own buffers over would delete them —
            # a mid-pass save_dense or an aborted pass would then read dead
            # arrays (init_sharded_train_state makes the same copies on
            # the mesh path). After hand_over_dense the pass takes the
            # buffers themselves: params / opt_state are None until
            # train_pass re-points them at what the steps returned
            params, opt_state = self.params, self.opt_state
            if self._dense_in_place:
                self.params = self.opt_state = None
                self._dense_with_pass = True
            else:
                params = jax.tree.map(jnp.copy, params)
                opt_state = jax.tree.map(jnp.copy, opt_state)
            auc = auc_init(self.cfg.auc_buckets)
            state = TrainState(
                table=None,
                params=params,
                opt_state=opt_state,
                auc=auc,
                step=jnp.zeros((), jnp.int32),
            )
            del params, opt_state
            # one placement for every leaf. A spliced pass table arrives
            # COMMITTED to its device (born under out_shardings) beside
            # uncommitted fresh leaves; the step's outputs are then all
            # committed, so the second dispatch would see a new argument
            # signature and compile the whole scan program a second time
            device = next(iter(
                (dev_table if isinstance(dev_table, jax.Array) else auc.pos).devices()
            ))
            state = jax.device_put(state, device)
            # the table itself goes up on its way into the first program
            # that takes it, in that program's format (_table_up): until
            # then its leaf says only what it will be
            self._table_src = dev_table
            self._table_fmt = None
            return state._replace(
                table=jax.ShapeDtypeStruct(
                    (dev_table.size // dev_table.shape[-1], dev_table.shape[-1]),
                    dev_table.dtype,
                    sharding=jax.sharding.SingleDeviceSharding(device),
                )
            )
        return init_sharded_train_state(
            self.plan,
            dev_table,
            self.params,
            self.dense_opt,
            self.cfg.auc_buckets,
            opt_state=self.opt_state,
            local_dense=self.cfg.dense_sync_mode == "kstep",
        )

    def _table_up(self, state: TrainState, fmt=None) -> TrainState:
        """The pass table, where it is not up yet, goes up in ``fmt``: the
        format the compiled superstep about to take it carries it in, so
        that no dispatch of the pass copies it (None, for the per-batch
        step: the default layout). One format for the pass's life: every
        later program is built for the format the table has."""
        if isinstance(state.table, jax.Array):
            return state
        with PROFILER.record_event("state.upload", "pass"):
            table = put_table(self._table_src, next(iter(state.step.devices())), fmt)
        self._table_src, self._table_fmt = None, fmt
        return state._replace(table=table)

    def _table_step(self, eval_mode: bool):
        """The one-chip per-batch step for this pass's table: the plain jit
        where the table lies in the default layout, else one that hands the
        table back in the format a superstep put it up in."""
        fmt = self._table_fmt
        if fmt is None:
            return self._eval_step() if eval_mode else self._step
        step = self._table_steps.get((eval_mode, fmt))
        if step is None:
            step = self._table_steps[(eval_mode, fmt)] = jit_state_step(
                make_train_step(
                    self.model.apply, self.dense_opt, self.cfg, eval_mode=eval_mode
                ),
                fmt_out=fmt,
            )
        return step

    @property
    def _n_pack_devices(self) -> int:
        """Devices THIS process packs batches for: all of them single-host,
        the local block of the global mesh multi-host."""
        return self.plan.n_devices // jax.process_count()

    def _host_np(self, x) -> np.ndarray:
        """Device array -> host numpy, gathering non-addressable shards
        across processes when the mesh spans hosts."""
        if getattr(x, "is_fully_addressable", True):
            return np.asarray(x)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))

    def _pack_and_put(self, batch, ws):
        if self.plan is None:
            db = pack_batch(
                batch,
                ws,
                self._schema,
                dense_slot=self.dense_slot,
                dense_dim=self.dense_dim,
                bucket=self.pack_bucket,
            )
            return {k: jnp.asarray(v) for k, v in db.as_dict().items()}
        # sticky pad floors per working set: K/L only ever grow, so the
        # sharded step keeps ONE compiled program across a pass's batches
        # (the slow/pv analog of BatchPacker's frozen shapes)
        if getattr(self, "_pads_ws", None) is not ws:
            self._pads_ws = ws
            self._pads = [-1, 0]  # [k_floor (-1 = headroom), l_floor]
        db = pack_batch_sharded(
            batch,
            ws,
            self._schema,
            self._n_pack_devices,
            dense_slot=self.dense_slot,
            dense_dim=self.dense_dim,
            bucket=self.pack_bucket,
            k_floor=self._pads[0],
            l_floor=self._pads[1],
        )
        self._pads = [db.req_ranks.shape[2], db.inverse.shape[1]]
        return {k: put_sharded(self.plan, v) for k, v in db.as_dict().items()}

    def _feed_aux(
        self, feed, batch=None, ins_weight=None, cmatch=None, rank=None, ins_ids=None
    ):
        """(device feed, registry aux) tuple for the step loop."""
        aux = {}
        if batch is not None:
            cmatch, rank = batch.cmatch, batch.rank
            if ins_ids is None:
                ins_ids = batch.ins_ids
        if cmatch is not None:
            aux["cmatch"] = cmatch
        if rank is not None:
            aux["rank"] = rank
        if ins_weight is not None:
            aux["ins_weight"] = ins_weight
        if ins_ids is not None:
            aux["ins_ids"] = ins_ids
        return feed, aux

    def _pv_lockstep(self, dataset, n_dev: int) -> int:
        """Multi-host join phase: equalize batch counts and pad shapes.

        The pv analog of the fast path's transport-locksteped freeze_shapes
        (compute_thread_batch_nccl parity, data_set.cc:2069-2135): (a)
        allreduce-max the local pv batch count — short hosts emit all-ghost
        batches; (b) allreduce-max the per-device L and per-(device, shard)
        request-bucket K over every local batch INCLUDING the ghost tail, and
        seed the sticky pack floors, so every host compiles the same mesh
        program and no collective ever sees mismatched shapes.
        Returns the global batch count (min_batches for pv_batches)."""
        from paddlebox_tpu.data.device_pack import _round_bucket

        cached = getattr(self, "_pv_lockstep_cache", None)
        if (
            cached is not None
            and cached[0] is dataset.pvs
            and cached[1] is dataset.ws
        ):
            # repeat join-phase calls over the same pvs/ws (warmup epoch,
            # join eval) skip the host re-pack sweep AND the allreduces —
            # re-entering the collectives alone would desync any host that
            # took the cache hit
            min_b, k_glob, l_glob = cached[2]
            self._pads_ws = dataset.ws
            self._pads = [k_glob, l_glob]
            return min_b
        tp = dataset.transport
        if tp is None:
            raise RuntimeError(
                "multi-host join-phase (pv) training needs a dataset "
                "transport to lockstep batch counts and pad shapes across "
                "hosts (pass transport= to BoxPSDataset)"
            )
        min_b = dataset.num_pv_batches(n_devices=n_dev, global_count=True)
        ws = dataset.ws
        cap, ns = ws.capacity, ws.n_mesh_shards
        bucket = self.pack_bucket or config.get_flag("batch_bucket_rounding")
        b = dataset.batch_size // n_dev

        def block_stats(recs, ghost, n_real):
            """(L, shard-bucket max) of one device block incl. ghost pad —
            ghosts repeat an existing record, so they add keys but no new
            unique rows beyond the ghost's own."""
            keys_parts = [r.u64_values for r in recs]
            if n_real < b and ghost is not None:
                keys_parts.extend([ghost.u64_values] * (b - n_real))
            keys = (
                np.concatenate(keys_parts)
                if keys_parts
                else np.zeros(0, np.uint64)
            )
            if not len(keys):
                return 0, 0
            uniq = np.unique(ws.lookup(keys))
            return len(keys), int(np.bincount(uniq // cap, minlength=ns).max())

        from paddlebox_tpu.data.pv_instance import (
            _iter_pv_blocks,
            first_pv_record,
            flatten_pv_instances,
        )

        max_L, max_bucket = 1, 0
        fallback = first_pv_record(dataset.pvs)
        n_local = 0
        for blocks in _iter_pv_blocks(dataset.pvs, b, n_dev):
            n_local += 1
            groups = list(blocks) + [[]] * (n_dev - len(blocks))
            # emit()'s ghost for an all-empty group is the first ad WITHIN
            # this batch (_GHOST_FALLBACK) — mirror it exactly so L matches
            batch_ghost = next(
                (pv.ads[0] for g in groups for pv in g if pv.ads), fallback
            )
            for group in groups:
                recs = flatten_pv_instances(group)
                ghost = recs[-1] if recs else batch_ghost
                L, bmax = block_stats(recs, ghost, len(recs))
                max_L = max(max_L, L)
                max_bucket = max(max_bucket, bmax)
        if n_local < min_b and fallback is not None:
            # lockstep all-ghost batches: b copies of one record per device
            L, bmax = block_stats([], fallback, 0)
            max_L = max(max_L, L)
            max_bucket = max(max_bucket, bmax)
        k_glob = tp.allreduce_max(
            _round_bucket(max_bucket + 1, bucket), f"pv-K:{dataset.pass_id}"
        )
        l_glob = tp.allreduce_max(
            _round_bucket(max_L, bucket), f"pv-L:{dataset.pass_id}"
        )
        self._pads_ws = dataset.ws
        self._pads = [k_glob, l_glob]
        self._pv_lockstep_cache = (dataset.pvs, dataset.ws, (min_b, k_glob, l_glob))
        return min_b

    def _pv_plan_feed_iter(self, dataset, plan, n_batches):
        """Plan-driven join-phase feed: the pv analog of _fast_feed_iter.

        Batch composition comes from the PvPlan's index tensor, so packing
        runs through the native columnar packer (BatchPacker) instead of
        the per-record SlotBatch path, with the same prefetch overlap as
        the flat fast path. On a multi-host mesh, freeze_shapes'
        transport branch locksteps the pads — replacing the per-record
        _pv_lockstep sweep with vectorized store math."""
        store = dataset.store
        packer = self._get_packer(dataset)
        n_dev = 1 if self.plan is None else self._n_pack_devices
        b = dataset.batch_size // n_dev
        packer.freeze_shapes(
            plan.idx,
            n_devices=n_dev if self.plan is not None else 0,
            transport=dataset.transport,
        )
        has_meta = store.ins_id_off is not None
        want_ids = has_meta and self.dump_pool is not None
        n = plan.n_batches
        if n_batches is not None:
            n = min(n, n_batches)

        def prep(pos):
            idx = plan.idx[pos]
            ro = plan.rank_offset[pos]
            w = plan.ins_weight[pos]
            if self.plan is None:
                db = packer.pack(idx)
                feed = {k: jax.device_put(v) for k, v in db.as_dict().items()}
                feed["ins_weight"] = jnp.asarray(w)
                feed["rank_offset"] = jnp.asarray(ro)
            else:
                db = packer.pack_sharded(idx, n_dev)
                feed = {
                    k: put_sharded(self.plan, v) for k, v in db.as_dict().items()
                }
                feed["ins_weight"] = put_sharded(self.plan, w.reshape(n_dev, b))
                feed["rank_offset"] = put_sharded(
                    self.plan, ro.reshape(n_dev, b, ro.shape[-1])
                )
            ids = [store.ins_id(int(j)) for j in idx] if want_ids else None
            return idx, feed, w, ids

        for idx, feed, w, ids in prefetch(range(n), prep):
            yield self._feed_aux(
                feed,
                ins_weight=w,
                cmatch=store.cmatch[idx] if has_meta else None,
                rank=store.rank[idx] if has_meta else None,
                ins_ids=ids,
            )

    def _pv_feed_iter(self, dataset, n_batches):
        n_dev = 1 if self.plan is None else self._n_pack_devices
        multi = self.plan is not None and jax.process_count() > 1
        if dataset.store is not None:
            plan, _ = self._pv_locked_plan(dataset)
            if plan is not None:
                yield from self._pv_plan_feed_iter(dataset, plan, n_batches)
                return
        min_b = 0
        if multi:
            min_b = self._pv_lockstep(dataset, n_dev)

        def prepare(item):
            batch, ins_weight = item
            feed = self._pack_and_put(batch, dataset.ws)
            if self.plan is None:
                if ins_weight is not None:
                    feed["ins_weight"] = jnp.asarray(ins_weight)
                if batch.rank_offset is not None:
                    feed["rank_offset"] = jnp.asarray(batch.rank_offset)
            else:
                # device-blocked pv batch: per-device leading axis, rank
                # offsets already device-local (pv_instance.pack_pv_batches)
                b = batch.batch_size // n_dev
                feed["ins_weight"] = put_sharded(
                    self.plan, ins_weight.reshape(n_dev, b)
                )
                ro = batch.rank_offset
                feed["rank_offset"] = put_sharded(
                    self.plan, ro.reshape(n_dev, b, ro.shape[-1])
                )
            return self._feed_aux(feed, batch=batch, ins_weight=ins_weight)

        # ONE worker, shallow depth: batch i+1 builds+packs while i trains
        # (join-phase analog of the fast path's prefetch). A single worker
        # keeps the sticky pad floors race-free and the order deterministic.
        yield from prefetch(
            dataset.pv_batches(n_batches, n_devices=n_dev, min_batches=min_b),
            prepare,
            workers=1,
            depth=2,
        )

    def _slow_feed_iter(self, dataset, n_batches):
        for batch in dataset.batches(n_batches):
            yield self._feed_aux(
                self._pack_and_put(batch, dataset.ws), batch=batch
            )

    def _get_packer(self, dataset) -> BatchPacker:
        """One BatchPacker per (store, working set): keeps pad shapes — and
        thus the compiled device program — stable across train_pass calls
        within a pass (warmup + epochs share one XLA executable)."""
        cached = getattr(self, "_packer_cache", None)
        if (
            cached is not None
            and cached[0] is dataset.store
            and cached[1] is dataset.ws
        ):
            return cached[2]
        if cached is not None:
            cached[2].close()
        packer = BatchPacker(
            dataset.store,
            dataset.ws,
            self._schema,
            dense_slot=self.dense_slot,
            dense_dim=self.dense_dim,
            bucket=self.pack_bucket,
        )
        self._packer_cache = (dataset.store, dataset.ws, packer)
        return packer

    def _fast_feed_iter(self, dataset, n_batches):
        """Columnar fast path: native pack + device upload in background
        threads, overlapped with the device step (MiniBatchGpuPack async
        pipeline parity, data_feed.h:1418-1542)."""
        store = dataset.store
        packer = self._get_packer(dataset)
        # one compiled program for the whole pass: L_pad frozen from the
        # full batch partition (U_pad/K self-stabilize with headroom)
        packer.freeze_shapes(
            dataset.batch_indices(n_batches),
            n_devices=self._n_pack_devices if self.plan is not None else 0,
            transport=dataset.transport,
        )
        has_meta = store.ins_id_off is not None

        want_ids = has_meta and self.dump_pool is not None

        def prep(idx):
            if self.plan is None:
                db = packer.pack(idx)
                feed = {
                    k: jax.device_put(v) for k, v in db.as_dict().items()
                }
            else:
                db = packer.pack_sharded(idx, self._n_pack_devices)
                feed = {
                    k: put_sharded(self.plan, v) for k, v in db.as_dict().items()
                }
            # ins_id string extraction belongs in the overlapped worker, not
            # between device steps
            ids = [store.ins_id(int(j)) for j in idx] if want_ids else None
            return idx, feed, ids

        def prep_traced(idx):
            # worker-thread span: the chrome trace shows pack/upload
            # overlapping the device step (RecordEvent parity). device_put
            # returns before the H2D transfer lands, so when tracing we
            # block on the feed INSIDE the worker span — the wait stays off
            # the main thread, which is exactly the prefetch worker's job
            if not PROFILER.enabled:
                return prep(idx)
            with PROFILER.record_event("pack+upload", "pack"):
                out = prep(idx)
                jax.block_until_ready(out[1])
                return out

        for idx, feed, ids in prefetch(dataset.batch_indices(n_batches), prep_traced):
            yield self._feed_aux(
                feed,
                cmatch=store.cmatch[idx] if has_meta else None,
                rank=store.rank[idx] if has_meta else None,
                ins_ids=ids,
            )

    def _classic_stepper(
        self, iterator, holder, step_fn, is_async, profile, t_feed, t_disp, t_dev
    ):
        """Per-batch dispatch over a host-packed feed iterator.

        Yields (batch_index, metrics, aux). Keeps a shallow dispatch window
        (max_inflight_steps): deep enough to hide host->device round-trip
        latency behind compute, shallow enough that transfers and
        executions can't pile up on the transport."""
        from collections import deque

        max_inflight = config.get_flag("max_inflight_steps")
        inflight: deque = deque()
        holder["state"] = self._table_up(holder["state"])
        it = iter(iterator)
        i = 0
        while True:
            t_feed.start()
            try:
                with PROFILER.record_event("feed_wait", "pass"):
                    feed, aux = next(it)
            except StopIteration:
                return
            finally:
                t_feed.pause()  # idempotent
            if is_async:  # PullDense / PushDense worker loop (B6)
                fresh = self.async_dense.pull_dense()
                if self.plan is not None:
                    fresh = put_replicated(self.plan, fresh)
                else:
                    fresh = jax.device_put(fresh)
                holder["state"] = holder["state"]._replace(params=fresh)
            # chaos seam: a per-batch device failure (OOM, interconnect
            # reset, preempted core) surfaces here as a dispatch exception
            _fault_fire("step.device")
            t_disp.start()
            with PROFILER.record_event("train_step_dispatch", "pass"):
                holder["state"], m = step_fn(holder["state"], feed)
            t_disp.pause()
            if profile:
                t_dev.start()
                with PROFILER.record_event("device_step", "device"):
                    jax.block_until_ready(m["loss"])
                t_dev.pause()
            elif max_inflight:
                inflight.append(m["loss"])
                if len(inflight) > max_inflight:
                    t_dev.start()
                    jax.block_until_ready(inflight.popleft())
                    t_dev.pause()
            yield i, m, aux
            i += 1

    def _get_resident(self, dataset):
        """Pass-scoped ResidentPass cache (same lifetime as the packer:
        rebuilt when the store or working set changes)."""
        c = getattr(self, "_resident_cache", None)
        if c is not None and c[0] is dataset.store and c[1] is dataset.ws:
            return c[2]
        # a rebuild over the SAME store (pass retry, warmup->timed ws swap)
        # can keep the frozen pad-shape cache: the unique-row count of an
        # index block depends only on the store's keys (distinct keys map
        # to distinct rows in ANY pass working set), so re-deriving it per
        # rebuild just re-runs the pad sweep for identical answers
        prev_uniq = (
            dict(c[2]._uniq_cache)
            if c is not None and c[0] is dataset.store
            else None
        )
        # release the PREVIOUS pass's device arrays (and the jitted
        # supersteps whose closures pin them) BEFORE uploading the new
        # pass's set — otherwise both passes' resident arrays coexist in
        # HBM during prepare, doubling peak device memory
        c = None  # the local ref would keep the old arrays alive too
        self._resident_cache = None
        self._sstep_cache = {}
        self._sstep_recorded = set()
        self._pv_feed_cache = None  # old pass's pv stacks must release too
        rp = ResidentPass(
            dataset.store,
            dataset.ws,
            self._schema,
            dense_slot=self.dense_slot,
            dense_dim=self.dense_dim,
            bucket=self.pack_bucket,
            plan=self.plan,
            transport=dataset.transport,
        )
        if prev_uniq:
            rp._uniq_cache.update(prev_uniq)
        self._resident_cache = (dataset.store, dataset.ws, rp)
        return rp

    def _pv_locked_plan(self, dataset):
        """The pass's PvPlan with the multi-host ghost-batch count folded
        in — THE one source all pv consumers share (gate, prepare, feed),
        so they can never build differently-locksteped plans. The global
        batch-count allreduce runs once per (pvs, n_dev) and is cached;
        every host takes the cache hit at the same call, so collective
        call counts stay symmetric."""
        n_dev = self._n_pack_devices if self.plan is not None else 1
        multi = self.plan is not None and jax.process_count() > 1
        c = getattr(self, "_pv_minb_cache", None)
        if c is not None and c[0] is dataset.pvs and c[1] == n_dev:
            min_b = c[2]
        else:
            min_b = (
                dataset.num_pv_batches(n_devices=n_dev, global_count=True)
                if multi
                else 0
            )
            self._pv_minb_cache = (dataset.pvs, n_dev, min_b)
        return dataset.pv_plan(n_dev, min_batches=min_b), n_dev

    def _pv_resident_prepare(self, dataset):
        """(rp, plan, device feed) for the resident join phase: build the
        PvPlan, freeze the resident pads over ITS batches (ghost repeats
        count keys but add no uniques), and upload the plan's stacked
        idx/rank_offset/ins_weight once per pass."""
        from paddlebox_tpu.train.resident_step import (
            ResidentPvFeed,
            ensure_sharded,
        )

        rp = self._get_resident(dataset)
        plan, n_dev = self._pv_locked_plan(dataset)
        if self.plan is None:
            rp.ensure(plan.idx)
        else:
            ensure_sharded(rp, plan.idx, self._n_pack_devices)
        c = getattr(self, "_pv_feed_cache", None)
        if c is None or c[0] is not plan or c[1] is not rp:
            feed = ResidentPvFeed(plan, mesh_plan=self.plan)
            self._pv_feed_cache = (plan, rp, feed)
        return rp, plan, self._pv_feed_cache[2]

    def _resident_superstep(self, rp, eval_mode, pv_feed=None):
        # keyed cache (not a single slot): a per-pass train -> eval -> train
        # alternation must reuse both compiled scan programs, like the
        # classic path keeps _step and _eval_step_cache alive side by side
        cache = getattr(self, "_sstep_cache", None)
        if cache is None:
            cache = self._sstep_cache = {}
        key = (id(rp), id(pv_feed), eval_mode, rp.L_pad, rp.U_pad, rp.K_pad)
        ss = cache.get(key)
        if ss is None:
            PROGRAMS.watch("superstep")  # its build seconds, by jax's own events
            if pv_feed is not None:
                from paddlebox_tpu.train.resident_step import (
                    make_resident_pv_mesh_superstep,
                    make_resident_pv_superstep,
                )

                if self.plan is None:
                    ss = make_resident_pv_superstep(
                        self.model.apply, self.dense_opt, self.cfg, rp,
                        pv_feed, eval_mode=eval_mode,
                    )
                else:
                    ss = make_resident_pv_mesh_superstep(
                        self.model.apply, self.dense_opt, self.cfg, rp,
                        pv_feed, self.plan, eval_mode=eval_mode,
                    )
            elif self.plan is None:
                ss = make_resident_superstep(
                    self.model.apply, self.dense_opt, self.cfg, rp,
                    eval_mode=eval_mode,
                )
            else:
                from paddlebox_tpu.train.resident_step import (
                    make_resident_mesh_superstep,
                )

                ss = make_resident_mesh_superstep(
                    self.model.apply, self.dense_opt, self.cfg, rp,
                    self.plan, eval_mode=eval_mode,
                )
            cache[key] = ss
        return ss

    def _record_superstep(self, sstep, avals, eval_mode: bool) -> None:
        """Once a superstep program has run: its instruction -> scope map,
        the account of what the scopes leave out, the executable's own
        memory figures and the table's entry and result layout into the
        process's program registry (obs/program_scopes.py). ``sstep`` is the
        executable that ran (one chip), or a mesh superstep whose ``lower``
        with the call's own avals hands back the executable the call just
        built — nothing compiles a second time."""
        shape = "x".join(str(d) for d in avals[1].shape)
        name = f"superstep/{'eval' if eval_mode else 'train'}/{shape}"
        with PROFILER.record_event("superstep_scope_map", "pass"):
            compiled = sstep if self.plan is None else sstep.lower(*avals).compile()
            text = compiled.as_text()
            PROGRAMS.record(
                name, "superstep", text, memory=memory_of(compiled),
                table_layout=table_layout_of(text, avals[0].table),
            )

    def _resident_stepper(
        self, dataset, n_batches, holder, eval_mode, profile, t_feed, t_disp, t_dev,
        use_pv: bool = False,
    ):
        """Superstep dispatch: K batches per lax.scan call, index-only feed.

        Yields the same (batch_index, metrics, aux) stream as the classic
        stepper. The superstep program hands its K batches' metrics back
        itself, one dict of device arrays a batch (per_batch_metrics), so
        batch j's metrics are ``per_batch[j]``: nothing is sliced, launched
        or read on the host between two dispatches, and unconsumed fields
        never leave the device. After dispatching superstep c the loop
        waits for superstep c - 1 and only then hands out c's batches: the
        device always holds the next program while the host consumes.

        ``use_pv`` switches to the join-phase tier: batches come from the
        pass's PvPlan (already resident on device), so the per-chunk feed is
        a [K] vector of batch positions; rank_offset/ins_weight ride along
        from the resident stacks."""
        t_feed.start()
        pv_w = None
        with PROFILER.record_event("resident_prepare", "pass"):
            if use_pv:
                rp, plan, pv_feed = self._pv_resident_prepare(dataset)
                n = plan.n_batches
                if n_batches is not None:
                    n = min(n, n_batches)
                blocks = [plan.idx[i] for i in range(n)]
                pv_w = plan.ins_weight
                sstep = self._resident_superstep(rp, eval_mode, pv_feed=pv_feed)
            else:
                rp = self._get_resident(dataset)
                blocks = [
                    np.asarray(b, dtype=np.int32)
                    for b in dataset.batch_indices(n_batches)
                ]
                if self.plan is None:
                    rp.ensure(blocks)
                else:
                    from paddlebox_tpu.train.resident_step import ensure_sharded

                    ensure_sharded(rp, blocks, self._n_pack_devices)
                sstep = self._resident_superstep(rp, eval_mode)
        t_feed.pause()
        # profiling wants per-batch device attribution: drop to one batch
        # per dispatch (the same overlap-for-attribution trade the classic
        # path makes by blocking every step)
        K = 1 if profile else max(1, int(config.get_flag("resident_scan_batches")))
        store = dataset.store
        has_meta = store.ins_id_off is not None
        want_ids = has_meta and self.dump_pool is not None
        from collections import deque
        from concurrent.futures import ThreadPoolExecutor

        # ins_id string extraction belongs off the dispatch thread (same
        # rule as the prefetch worker in _fast_feed_iter): one background
        # worker resolves a chunk's ids while its superstep runs
        ids_ex = ThreadPoolExecutor(max_workers=1) if want_ids else None
        try:
            inflight: deque = deque()
            i = 0
            for c0 in range(0, len(blocks), K):
                chunk = blocks[c0 : c0 + K]
                ids_fut = (
                    ids_ex.submit(
                        lambda ch: [
                            [store.ins_id(int(r)) for r in idx] for idx in ch
                        ],
                        chunk,
                    )
                    if want_ids
                    else None
                )
                if use_pv:
                    # the batches live on device already — feed POSITIONS
                    # (a transfer: jnp.arange would be a program of its own)
                    idx_dev = jnp.asarray(
                        np.arange(c0, c0 + len(chunk), dtype=np.int32)
                    )
                elif self.plan is not None:
                    # [K, B_local] -> [K, n_local, b]: record r -> device
                    # r // b, the same ins // b mapping the sharded packer
                    # uses; the scan axis stays whole, devices split (on a
                    # multi-host mesh each process contributes its local
                    # devices' blocks of LOCAL store indices)
                    from paddlebox_tpu.parallel.mesh import put_axis1_blocks

                    idx_dev = put_axis1_blocks(
                        self.plan,
                        np.stack(chunk).reshape(
                            len(chunk), self._n_pack_devices, -1
                        ),
                    )
                else:
                    idx_dev = jnp.asarray(np.stack(chunk))
                _fault_fire("step.device")  # chaos seam (see classic stepper)
                avals = None
                if (id(sstep), idx_dev.shape) not in self._sstep_recorded:
                    # the state is donated: keep its shapes, not its arrays
                    avals = jax.tree.map(_aval_of, (holder["state"], idx_dev))
                t_disp.start()
                with PROFILER.record_event("superstep_dispatch", "pass"):
                    run = sstep
                    if self.plan is None:
                        # built ahead of its first call: the table goes up
                        # in the format this program carries it in
                        run, fmt = sstep.executable(holder["state"], idx_dev)
                        holder["state"] = self._table_up(holder["state"], fmt)
                    holder["state"], per_batch = run(holder["state"], idx_dev)
                t_disp.pause()
                if avals is not None:
                    self._sstep_recorded.add((id(sstep), idx_dev.shape))
                    self._record_superstep(run, avals, eval_mode)
                if profile:
                    t_dev.start()
                    with PROFILER.record_event("device_superstep", "device"):
                        jax.block_until_ready(per_batch[-1]["loss"])
                    t_dev.pause()
                else:
                    inflight.append(per_batch[-1]["loss"])
                    if len(inflight) > 1:  # double-buffer supersteps
                        t_dev.start()
                        with PROFILER.record_event("superstep_wait", "device"):
                            jax.block_until_ready(inflight.popleft())
                        t_dev.pause()
                chunk_ids = ids_fut.result() if ids_fut is not None else None
                # the consumer's work on each batch runs inside this span
                with PROFILER.record_event("superstep_consume", "pass"):
                    for j, (idx, m) in enumerate(zip(chunk, per_batch)):
                        aux = {}
                        if has_meta:
                            aux["cmatch"] = store.cmatch[idx]
                            aux["rank"] = store.rank[idx]
                        if pv_w is not None:
                            aux["ins_weight"] = pv_w[c0 + j]
                        if chunk_ids is not None:
                            aux["ins_ids"] = chunk_ids[j]
                        yield i, m, aux
                        i += 1
        finally:
            if ids_ex is not None:
                ids_ex.shutdown(wait=False)

    def _use_resident(self, dataset: BoxPSDataset, use_pv: bool, is_async: bool) -> bool:
        """One predicate for the resident-vs-packer path, shared by
        train_pass and prepare_pass so the warm-start hook can never
        pre-freeze a different feed path than training will take.

        Covers the single-device step, single-host meshes (resident arrays
        replicate across local devices), and multi-host meshes (each
        device carries its host's pass arrays, pads transport-locksteped)
        — for BOTH tiers: flat, and join-phase (use_pv) via the
        pass-deterministic PvPlan, whose feed is batch POSITIONS into
        resident idx/rank_offset/ins_weight stacks (ghost batches
        equalize multi-host counts). A model that takes rank_offset is
        only excluded from the FLAT tier (no rank matrix exists there to
        feed it)."""
        multi_host = self.plan is not None and jax.process_count() > 1
        ok = (
            bool(config.get_flag("enable_resident_feed"))
            and not is_async
            and dataset.store is not None
            and len(dataset.store.u64_values) < (1 << 31)
            and not (multi_host and dataset.transport is None)
        )
        if multi_host and dataset.transport is not None:
            # the per-host inputs (store size, store presence) can differ —
            # a split decision would send the hosts into DIFFERENT lockstep
            # collectives (packer freeze vs resident allreduces) and
            # deadlock. All hosts take the resident tier only unanimously.
            # Calls are uniform across hosts (prepare/train/eval sequence),
            # so the FIFO tag needs no per-call uniqueifier.
            ok = (
                dataset.transport.allreduce_max(0 if ok else 1, "res-gate")
                == 0
            )
        if not ok:
            # cheap gates first: a multi-host join phase must NOT build the
            # min_batches=0 plan here (its _pv_feed_iter needs the
            # min_batches=min_b variant — a different cache key, so this
            # one would be a wasted full pack sweep)
            return False
        if use_pv:
            # the plan (and with it every record's store index) must exist;
            # building it here is free for train_pass, which needs it next.
            # Multi-host: the plan carries the locksteped ghost-batch count
            # (store-backed hosts always have store indices — availability
            # is uniform across hosts)
            return self._pv_locked_plan(dataset)[0] is not None
        return not self.cfg.model_takes_rank_offset

    def prepare_pass(
        self, dataset: BoxPSDataset, n_batches: Optional[int] = None
    ) -> None:
        """Pre-freeze this pass's pad shapes for the given batch partition.

        Optional warm-start hook: calling this (or training a warmup slice
        covering the partition) before a timed/measured train_pass keeps
        shape growth — and the XLA recompile it triggers — out of the
        measured region. Covers both the resident path (L_pad/U_pad) and
        the columnar packer (freeze_shapes).

        Records its own wall time as ``last_prepare_s`` (bench sub-field:
        the pass-prepare sweep must stay off the critical path — one
        native counter sweep + one allreduce, data_set.cc:2069-2135)."""
        import time as _time

        t0 = _time.perf_counter()
        try:
            self._prepare_pass_inner(dataset, n_batches)
        finally:
            self.last_prepare_s = _time.perf_counter() - t0

    def _prepare_pass_inner(
        self, dataset: BoxPSDataset, n_batches: Optional[int] = None
    ) -> None:
        self._schema = dataset.schema
        if dataset.store is None or dataset.ws is None:
            return
        use_pv = dataset.pv_merged and dataset.current_phase == 1
        is_async = self.cfg.dense_sync_mode == "async" and not self._eval_active
        if use_pv:
            if self._use_resident(dataset, use_pv, is_async):
                self._pv_resident_prepare(dataset)
            # host-packed pv pads freeze at feed time (plan freeze_shapes
            # or, records-only, the _pv_lockstep sweep)
            return
        if self._use_resident(dataset, use_pv, is_async):
            rp = self._get_resident(dataset)
            blocks = (
                np.asarray(b, dtype=np.int32)
                for b in dataset.batch_indices(n_batches)
            )
            if self.plan is None:
                rp.ensure(blocks)
            else:
                from paddlebox_tpu.train.resident_step import ensure_sharded

                ensure_sharded(rp, blocks, self._n_pack_devices)
        else:
            self._get_packer(dataset).freeze_shapes(
                dataset.batch_indices(n_batches),
                n_devices=self._n_pack_devices if self.plan is not None else 0,
                transport=dataset.transport,
            )

    def train_pass(
        self,
        dataset: BoxPSDataset,
        n_batches: Optional[int] = None,
        on_batch: Optional[Callable[[int, Dict], None]] = None,
        profile: bool = False,
    ) -> Dict[str, float]:
        """Train every minibatch of the current pass; returns pass metrics.

        Call between dataset.begin_pass() and dataset.end_pass(...). Dense
        params/opt state carry over to the next pass; the trained sparse
        table is available via trained_table() for end_pass writeback.

        ``profile=True`` (TrainFilesWithProfiler parity, boxps_worker.cc:
        525-620) adds a per-stage wall-clock breakdown under
        ``out["profile"]``: feed_wait (pack+upload not hidden by overlap),
        step_dispatch (host->XLA handoff), device_step (synchronous device
        execution — profiling blocks per batch, so overlap is sacrificed
        for attribution), host_metrics (registry/dump/callbacks).
        """
        if dataset.device_table is None:
            raise RuntimeError("dataset.begin_pass() first")
        self._schema = dataset.schema
        # the ws OBJECT is the cache key (an id() could be recycled across
        # passes and silently serve the previous pass's state)
        with PROFILER.record_event("train_pass.open", "pass"):
            state = self._make_state(dataset.device_table, ws_key=dataset.ws)
            # AUC buckets accumulate in device state across train_pass calls
            # within one pass (warmup epochs, join/update phases, sequential
            # slot-shuffle evals); snapshot them so THIS call's metrics are a
            # bucket delta, not the running total. The read waits for the
            # previous call's last program
            auc_pos0 = self._host_np(state.auc.pos).copy()
            auc_neg0 = self._host_np(state.auc.neg).copy()
        losses = []
        self._pass_counters = []  # a sequence-feed model's stacked counters, per batch
        # join phase serves pv-merged batches with rank_offset + ghost
        # weights; update phase serves flat batches (EnablePvMerge branch,
        # data_feed.cc:2165-2198)
        use_pv = dataset.pv_merged and dataset.current_phase == 1
        eval_mode = self._eval_active
        is_async = self.cfg.dense_sync_mode == "async" and not eval_mode
        # resident fast path: pass data lives in device HBM, feeds are
        # index-only, K steps per dispatch (train/resident_step.py)
        use_resident = self._use_resident(dataset, use_pv, is_async)
        iterator = None
        if use_resident:
            step_fn = None
        elif use_pv:
            iterator = self._pv_feed_iter(dataset, n_batches)
            step_fn = self._table_step(eval_mode)
        elif dataset.store is not None:
            iterator = self._fast_feed_iter(dataset, n_batches)
            step_fn = self._table_step(eval_mode)
        else:
            iterator = self._slow_feed_iter(dataset, n_batches)
            step_fn = self._table_step(eval_mode)
        if self.plan is not None and jax.process_count() > 1:
            if dataset.store is None:
                raise RuntimeError(
                    "multi-host mesh training needs the columnar-store fast "
                    "path (its pad shapes are transport-locksteped); enable "
                    "the native parser so dataset.store is built"
                )
            tp = dataset.transport
            if tp is not None and tp.rank != jax.process_index():
                # row placement puts process i's block at shard i while the
                # working set assigns ownership by transport rank — if the
                # two disagree, every pull silently reads the wrong host's
                # slice
                raise RuntimeError(
                    f"transport rank {tp.rank} != jax process index "
                    f"{jax.process_index()} — order the transport endpoint "
                    "list by jax process id"
                )
            omap = getattr(dataset, "ownership", None)
            if tp is not None and omap is not None and not omap.is_live(tp.rank):
                # after an elastic shrink the ownership map is the source of
                # truth for which ranks may train; a rank outside the live
                # set would pull shard ranges nobody routes to it
                raise RuntimeError(
                    f"transport rank {tp.rank} is not in the live set of "
                    f"ownership epoch {omap.epoch} "
                    f"(live={list(omap.live_ranks)}) — this process was "
                    "voted out of the membership and must not train"
                )
        from paddlebox_tpu.utils.timer import Timer

        t_feed, t_disp, t_dev, t_host = Timer(), Timer(), Timer(), Timer()
        skip_flags: list = []

        # the stepper generators mutate holder["state"] as they dispatch;
        # the consumer loop below is shared between the classic per-batch
        # path and the resident scan path so host-side semantics (registry,
        # dumps, NaN containment, callbacks) can never diverge
        holder = {"state": state}
        if use_resident:
            stepper = self._resident_stepper(
                dataset, n_batches, holder, eval_mode, profile,
                t_feed, t_disp, t_dev, use_pv=use_pv,
            )
        else:
            stepper = self._classic_stepper(
                iterator, holder, step_fn, is_async, profile,
                t_feed, t_disp, t_dev,
            )

        try:
            for i, m, aux in stepper:
                self._consume_batch(
                    i, m, aux, dataset, is_async, on_batch, losses,
                    skip_flags, t_host,
                )
        except BaseException:
            # the cached pre-pass state was donated into this pass's steps;
            # re-point at the last returned state so a retry (or
            # revert+retrain) doesn't touch deleted buffers. If the FAILING
            # call itself consumed that state (XLA runtime error after
            # donation), drop the cache so the retry rebuilds from the
            # dataset's pass-open table instead of crashing on dead arrays
            st = holder["state"]
            alive = True
            try:
                alive = not st.table.is_deleted()
            except AttributeError:
                pass  # host-side array: always alive
            # a table that never went up (_table_up) is no state to cache:
            # the retry rebuilds from the dataset's pass-open table too
            self._state = st if alive and self._table_src is None else None
            self._table_src = None
            if self._dense_in_place and alive:  # handed over, and it survived
                self.params, self.opt_state = st.params, st.opt_state
                self._dense_with_pass = False
            raise
        # a call that ran no batch still leaves a table for writeback
        state = self._table_up(holder["state"])
        # persist dense side for the next pass; state.table stays for writeback
        if eval_mode:
            # values are bit-identical, but the OLD buffers were donated into
            # the eval step — re-point at the returned state (skipping the
            # kstep pass-end sync, whose pmean would perturb bits)
            if self.plan is not None and self.cfg.dense_sync_mode == "kstep":
                self.params = jax.tree.map(lambda x: x[0], state.params)
                self.opt_state = jax.tree.map(lambda x: x[0], state.opt_state)
            else:
                self.params = state.params
                self.opt_state = state.opt_state
        elif is_async:
            # the host table owns the dense params; snapshot its latest view
            self.params = jax.device_put(self.async_dense.pull_dense())
            self.opt_state = state.opt_state  # untouched in async mode
        elif self.plan is not None and self.cfg.dense_sync_mode == "kstep":
            # pass-end SyncParam (boxps_worker.cc:459-461), then store the
            # synced params un-stacked; momentum stays device-0's (the
            # reference likewise syncs only the fused param buffer)
            state = kstep_sync_params(state, self.plan)
            self.params = jax.tree.map(lambda x: x[0], state.params)
            self.opt_state = jax.tree.map(lambda x: x[0], state.opt_state)
        else:
            self.params = state.params
            self.opt_state = state.opt_state
        self._state = state
        self._dense_with_pass = False
        if self.dump_pool is not None and self.dump_params_at_end:
            # DumpParam parity (device_worker.cc:131-133): dense params once
            # at pass end, one line per leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(self.params)[0]:
                name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
                dump_param(self.dump_pool, name, np.asarray(leaf))
        from paddlebox_tpu.metrics.auc import AucState

        with PROFILER.record_event("train_pass.tail", "pass"):
            cum = AucState(
                pos=self._host_np(state.auc.pos), neg=self._host_np(state.auc.neg)
            )
            delta = AucState(pos=cum.pos - auc_pos0, neg=cum.neg - auc_neg0)
            out = auc_compute(delta)
            cum_out = auc_compute(cum)
            out["auc_cumulative"] = cum_out["auc"]
            # saturation is a property of the CUMULATIVE buckets — the delta is
            # small by construction and would always read unsaturated
            out["saturated"] = cum_out["saturated"]
            if losses and skip_flags:
                lv = jnp.stack(losses)
                bad = jnp.stack(skip_flags) > 0
                kept = jnp.maximum(jnp.sum(~bad), 1)
                out["loss"] = float(jnp.sum(jnp.where(bad, 0.0, lv)) / kept)
                out["nan_batches"] = float(jnp.sum(bad))
            else:
                out["loss"] = float(jnp.mean(jnp.stack(losses))) if losses else float("nan")
                out["nan_batches"] = 0.0
            out["batches"] = float(len(losses))
            if self._pass_counters:  # the model's own: means over the pass, one read
                means = np.asarray(jnp.mean(jnp.stack(self._pass_counters), axis=0))
                out.update(zip(self.model.counter_names, map(float, means)))
                self.model.record_counters(means)
                self._pass_counters = []
            if not eval_mode:
                # monitor parity: training-lifecycle counters (an eval pass
                # trains nothing, so it bumps nothing). ins_num counts REAL
                # instances (AUC-masked: no ghosts, no skipped batches);
                # samples_processed is device throughput incl. wraparound pads.
                from paddlebox_tpu.utils.monitor import STAT_ADD

                STAT_ADD("train_batches", len(losses))
                STAT_ADD("train_samples_processed", len(losses) * self.cfg.batch_size)
                STAT_ADD("train_ins_num", out.get("ins_num", 0))
                STAT_ADD("nan_skipped_batches", out["nan_batches"])
        if profile:
            out["profile"] = {
                "feed_wait_s": round(t_feed.elapsed_sec(), 4),
                "step_dispatch_s": round(t_disp.elapsed_sec(), 4),
                "device_step_s": round(t_dev.elapsed_sec(), 4),
                "host_metrics_s": round(t_host.elapsed_sec(), 4),
            }
        return out

    def _consume_batch(
        self, i, m, aux, dataset, is_async, on_batch, losses, skip_flags, t_host
    ) -> None:
        """Host-side per-batch consumers, shared by both steppers."""
        t_host.start()
        if "nan_skipped" in m:  # lazy device array: no per-batch sync
            skip_flags.append(m["nan_skipped"])
        # containment must extend to every host-side consumer: a skipped
        # batch's NaN preds/grads reach neither the async dense table
        # nor the registry/dumps. The int() sync only happens when such
        # a consumer exists (those paths already sync per batch).
        skipped_now = 0
        if "nan_skipped" in m and (
            is_async or self.metric_registry is not None or self.dump_pool is not None
        ):
            skipped_now = int(m["nan_skipped"])
        if is_async and not skipped_now:
            self.async_dense.push_dense(jax.tree.map(np.asarray, m["gparams"]))
        if self.metric_registry is not None and not skipped_now:
            # per-batch registry feed with phase + logkey-derived vars
            # (AddAucMonitor parity, boxps_worker.cc:408-418)
            outputs = dict(m)
            outputs.update(aux)
            self.metric_registry.add_all(outputs, phase=dataset.current_phase)
        if self.dump_pool is not None and not skipped_now:
            self._dump_batch(i, m, aux)
        if on_batch is not None:
            on_batch(i, m)
        losses.append(m["loss"])
        if "counters" in m:
            self._pass_counters.append(m["counters"])
        t_host.pause()

    def _dump_batch(self, step_i: int, m: Dict, aux: Dict) -> None:
        """Per-batch field dump (DeviceWorker::DumpField parity,
        device_worker.cc:98-133; sampling modes device_worker.h:218-219)."""
        if not self.dump_pool._started:
            self.dump_pool.start()
        fields = {}
        n_ins = None
        for name in self.dump_fields_list:
            if name not in m:
                continue
            arr = np.asarray(m[name])
            if arr.ndim == 0:
                continue  # scalars (loss, step) have no per-instance rows
            flat = arr.reshape(-1, *arr.shape[2:]) if arr.ndim > 1 else arr
            fields[name] = flat
            n_ins = len(flat) if n_ins is None else min(n_ins, len(flat))
        if not fields or not n_ins:
            return
        ins_ids = aux.get("ins_ids")
        if ins_ids is None or len(ins_ids) != n_ins:
            # no ins-id metadata parsed: fall back to batch-ordinal ids
            ins_ids = [f"b{step_i}:{j}" for j in range(n_ins)]
        dump_fields(
            self.dump_pool,
            ins_ids,
            {k: v[:n_ins] for k, v in fields.items()},
            step=step_i,
            dump_mode=self.dump_mode,
            dump_interval=self.dump_interval,
        )

    def trained_table(self) -> np.ndarray:
        """The pass's trained table for writeback: the full array
        single-host, THIS host's shard block on a multi-process mesh
        (exactly what DistributedWorkingSet.writeback consumes — trained
        rows never cross hosts, EndPass parity box_wrapper.cc:627)."""
        if self._state is None:
            raise RuntimeError("no trained pass")
        if self.plan is not None and jax.process_count() > 1:
            return local_slice(self.plan, self._state.table)
        return np.asarray(self._state.table)

    def handoff_table(self, dataset: BoxPSDataset) -> None:
        """Carry this trainer's trained table into ANOTHER trainer's
        train_pass over the same working set.

        The reference's join and update phases push into one live PS table
        (phase machinery box_wrapper.h:620-622; the dataset is trained twice
        per pass, test_paddlebox_datafeed.py:103-119). Here each CTRTrainer
        binds one step config, so a two-phase pass uses two trainers — the
        join trainer must hand its sparse updates to the update trainer
        explicitly, else phase 2 silently restarts from the pass-open table:

            join_tr.train_pass(ds); join_tr.handoff_table(ds)
            upd_tr.train_pass(ds);  ds.end_pass(upd_tr.trained_table())

        Single-process the handoff stays ON DEVICE (no D2H/H2D round trip
        between phases); only the multi-host path goes through host memory
        (its writeback layout is per-host anyway).
        """
        if self.plan is not None and jax.process_count() > 1:
            t = self.trained_table()
        else:
            if self._state is None:
                raise RuntimeError("no trained pass")
            t = self._state.table
        if t.ndim == 2:  # single-device flat layout -> ws shard layout
            t = t.reshape(-1, dataset.ws.capacity, t.shape[-1])
        dataset.device_table = t

    def trained_table_device(self):
        """The live trained DEVICE table (no transfer): hand this to
        ``end_pass`` to opt into the device-carried boundary
        (table/carrier.py) — the next pass's finalize then splices
        surviving rows on device and fetches only the departing slice.
        Multi-host: the global sharded array; end_pass builds a per-host
        MultiHostCarrier over its addressable shard blocks (the decision
        is locksteped over the transport), so every node keeps its HBM
        cache warm across the boundary (EndPass box_wrapper.cc:627-651)."""
        if self._state is None:
            raise RuntimeError("no trained pass")
        return self._state.table
