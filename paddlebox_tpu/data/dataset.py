"""Pass-scoped in-memory dataset: the BoxPSDataset / PadBoxSlotDataset analog.

Reference surface being rebuilt (SURVEY.md B7/B17):
- python driver `BoxPSDataset` (python/paddle/fluid/dataset.py:1081-1221):
  set_date / load_into_memory / preload_into_memory / wait_preload_done /
  begin_pass / end_pass(need_save_delta) / slots_shuffle;
- C++ `PadBoxSlotDataset` (framework/data_set.cc:1515-2192): threaded file
  read into SlotRecords, feasign collection into the pass working set
  (PSAgent::AddKeys, data_set.cc:1647), node-striped file lists ("dualbox",
  data_set.cc:1452-1464), record shuffle before train (PrepareTrain,
  data_set.cc:2155-2192), equalized minibatch counts across devices
  (compute_thread_batch_nccl, data_set.cc:2069-2135).

TPU-shaped differences: the "device working set" is one dense jax array
sharded over the mesh (built by PassWorkingSet.finalize) instead of closed
HBM caches, and record routing across hosts is pluggable (``router``) with
hash semantics identical to the reference (search_id % n, XXH-style ins_id
hash, random).
"""

from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np

from paddlebox_tpu import config
from paddlebox_tpu.data.parser import parse_line
from paddlebox_tpu.data.quarantine import (
    DataPoisonedError,
    QuarantineLog,
    resolve_quarantine_dir,
)
from paddlebox_tpu.data.pv_instance import (
    PvInstance,
    flatten_pv_instances,
    merge_pv_instances,
    pack_pv_batches,
)
from paddlebox_tpu.data.record_store import ColumnarRecords
from paddlebox_tpu.data.slot_record import SlotBatch, SlotRecord, build_batch
from paddlebox_tpu.data.slot_schema import SlotSchema
from paddlebox_tpu.table.sparse_table import HostSparseTable, PassWorkingSet
from paddlebox_tpu.utils.faultinject import fire
from paddlebox_tpu.utils.fs import fs_glob
from paddlebox_tpu.utils.line_reader import BufferedLineFileReader
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_OBSERVE, STAT_SET
from paddlebox_tpu.utils.trace import record_event

config.define_flag(
    "padbox_dataset_shuffle_thread_num", 8, "default dataset reader thread count"
)
config.define_flag(
    "enable_carried_table",
    1,
    "keep the trained pass table in device HBM across the pass boundary "
    "and splice surviving rows into the next pass's table device-to-device "
    "(D2H only the departing keys, H2D only the new ones); 0 = classic "
    "full writeback + full re-upload",
)
config.define_flag(
    "carried_eager_flush",
    0,
    "after the carried-table splice, flush the carrier to the host store "
    "on a background thread (full-table D2H overlapping the next pass). "
    "Frees the extra HBM the lazy default pins for a whole pass — use "
    "when HBM, not transport bandwidth, is the constraint",
)
config.define_flag(
    "boundary_pipeline",
    1,
    "pipelined pass boundary: the load thread premerges the staged pass's "
    "key chunks (and, with boundary_prefetch_pull, prefetches host rows) "
    "while the current pass trains, so begin_pass finds the dedup/pull "
    "already done; 0 = classic serial boundary",
)
config.define_flag(
    "overlap_writeback",
    1,
    "kick the end-of-pass host writeback the moment the trained table "
    "lands (kick_writeback, called by the supervisor right after "
    "train_pass): the boundary worker joins the kick instead of writing "
    "back inline, so boundary.writeback_s records only the residual "
    "blocking tail and the hidden seconds flow into overlap_hidden_s. "
    "Safe under an armed guard (rollback covers partial writeback; "
    "revert_pass cancels the kick at a chunk boundary); 0 = classic "
    "writeback inside the boundary worker",
)
config.define_flag(
    "boundary_prefetch_pull",
    1,
    "with boundary_pipeline: the feed stage pull_or_creates host rows for "
    "staged keys NOT in the live pass (those rows cannot change before the "
    "boundary except by decay, which the consumer compensates bitwise). "
    "Auto-disabled when shrink_threshold != 0 or a mem_cap spill tier is "
    "active — either could invalidate prefetched rows",
)


def _ins_id_dest(ins_id: str, n_parts: int) -> int:
    # xxhash in the reference; any good string hash preserves semantics
    import hashlib

    return (
        int.from_bytes(hashlib.blake2b(ins_id.encode(), digest_size=8).digest(), "little")
        % n_parts
    )


def shuffle_route(records: Sequence[SlotRecord], n_parts: int, mode: str, seed: int) -> List[int]:
    """Destination part of each record (ShuffleData routing parity,
    data_set.cc:1772-1791): 'search_id' groups a query's ads on one node,
    'ins_id' spreads by instance hash, 'random' is uniform."""
    if mode == "search_id":
        return [r.search_id % n_parts for r in records]
    if mode == "ins_id":
        return [_ins_id_dest(r.ins_id, n_parts) for r in records]
    if mode == "random":
        rng = np.random.default_rng(seed)
        return list(rng.integers(0, n_parts, len(records)))
    raise ValueError(f"unknown shuffle mode {mode!r}")


def shuffle_route_store(
    store: ColumnarRecords, n_parts: int, mode: str, seed: int
) -> np.ndarray:
    """Vectorized shuffle_route over a columnar store -> int dest array."""
    n = len(store)
    if mode == "search_id":
        return (store.search_ids % np.uint64(n_parts)).astype(np.int64)
    if mode == "ins_id":
        return np.array(
            [_ins_id_dest(store.ins_id(i), n_parts) for i in range(n)], np.int64
        )
    if mode == "random":
        rng = np.random.default_rng(seed)
        return rng.integers(0, n_parts, n)
    raise ValueError(f"unknown shuffle mode {mode!r}")


class LocalShuffleRouter:
    """In-process stand-in for the closed ``boxps::PaddleShuffler`` RPC tier:
    exchanges record chunks between n logical nodes living in one process. A
    multi-host deployment plugs a host-RPC implementation with the same
    exchange()/collect() contract (parallel/transport.py TcpShuffleRouter,
    exercised by tests/test_multihost.py). A chunk is
    either a ``List[SlotRecord]`` or a ``ColumnarRecords``; the dataset
    normalizes on collect."""

    def __init__(self, n_nodes: int):
        self.n_nodes = n_nodes
        self._inboxes: List[list] = [[] for _ in range(n_nodes)]
        self._cond = threading.Condition()
        self._done = 0
        self._collected = 0

    def exchange(self, from_node: int, parts: list) -> None:
        """Deliver this node's outgoing chunks (one per destination); marks
        the node finished sending (the zero-length completion message of the
        reference's protocol, data_set.cc:1835-1866, collapses into this
        call). A node racing ahead into the next pass blocks here until
        every node collected the current one, so passes can never interleave
        in the inboxes."""
        with self._cond:
            self._cond.wait_for(lambda: self._done < self.n_nodes)
            for dst, chunk in enumerate(parts):
                if len(chunk):
                    self._inboxes[dst].append(chunk)
            self._done += 1
            self._cond.notify_all()

    def collect(self, node: int) -> list:
        """Blocks until every node has exchanged (ShuffleResultWaitGroup
        parity) so no late-arriving records are dropped. Returns the list
        of received chunks."""
        with self._cond:
            self._cond.wait_for(lambda: self._done >= self.n_nodes)
            out = self._inboxes[node]
            self._inboxes[node] = []
            self._collected += 1
            if self._collected >= self.n_nodes:  # re-arm for the next pass
                self._done = 0
                self._collected = 0
                self._cond.notify_all()  # wake exchangers blocked on the barrier
        return out


def _trained_to_host(arr, layout) -> np.ndarray:
    """Device trained table -> host ndarray, honoring the boundary wire
    format. Shared by the boundary worker's classic writeback and the
    overlapped kick_writeback thread, so both paths produce identical
    bytes."""
    if not isinstance(arr, np.ndarray) and not getattr(
        arr, "is_fully_addressable", True
    ):
        # multi-host global array: writeback wants this host's local
        # shard block only
        shards = sorted(
            arr.addressable_shards, key=lambda s: s.index[0].start or 0
        )
        arr = np.concatenate([np.asarray(s.data) for s in shards], axis=0)
    if not isinstance(arr, np.ndarray):
        from paddlebox_tpu.ops.wire_quant import fetch_rows

        shape = arr.shape
        arr = fetch_rows(
            arr.reshape(-1, shape[-1]), layout,
            str(config.get_flag("wire_dtype")),
        ).reshape(shape)
    return np.asarray(arr)


class _WritebackKick:
    """An in-flight overlapped writeback started by kick_writeback.

    The future resolves to the kick thread's total wall seconds (for the
    hidden-overlap accounting) or to the failure; ``cancel`` is checked by
    the chunked writeback at chunk boundaries (revert path)."""

    def __init__(self, ws):
        from concurrent.futures import Future

        self.ws = ws
        self.cancel = threading.Event()
        self.fut: "Future[float]" = Future()
        self.thread: Optional[threading.Thread] = None


@dataclass
class PassStats:
    """Per-load accounting, consistent across the native and Python tiers:

    ``lines``          every non-empty line seen (parsed + benign + bad)
    ``parsed``         lines that produced a record
    ``skipped_benign`` parser returned None legitimately (all-zero record,
                       '#' cache line) — the native tier's nstats["skipped"]
    ``bad_lines``      quarantined parse failures (0 unless data_quarantine)
    ``bad_files``      whole part files skipped (unreadable / truncated /
                       converter death)
    """

    files: int = 0
    lines: int = 0
    records: int = 0
    keys: int = 0
    parsed: int = 0
    skipped_benign: int = 0
    bad_lines: int = 0
    bad_files: int = 0
    bad_by_file: Dict[str, int] = field(default_factory=dict)
    dead_letter: Optional[str] = None


class BoxPSDataset:
    """One node's view of the pass data pipeline.

    Life cycle per pass (test_paddlebox_datafeed.py:103-119 sequence):
        set_date -> [pre]load_into_memory -> begin_pass
        -> batches()/train -> end_pass(need_save_delta)
    """

    def __init__(
        self,
        schema: SlotSchema,
        table: HostSparseTable,
        batch_size: int,
        n_mesh_shards: int = 1,
        read_threads: Optional[int] = None,
        rank: int = 0,
        nranks: int = 1,
        shuffle_mode: str = "none",  # none|local|search_id|ins_id|random
        router: Optional[LocalShuffleRouter] = None,
        transport=None,  # parallel.transport.TcpTransport for multi-host
        pipe_command: Optional[str] = None,
        line_parser: Optional[Callable[[str, SlotSchema], Optional[SlotRecord]]] = None,
        drop_remainder: bool = True,
        seed: int = 0,
        quarantine_dir: Optional[str] = None,
    ):
        self.schema = schema
        self.table = table
        self.batch_size = batch_size
        self.n_mesh_shards = n_mesh_shards
        self.read_threads = (
            read_threads
            if read_threads is not None
            else config.get_flag("padbox_dataset_shuffle_thread_num")
        )
        self.rank = rank
        self.nranks = nranks
        self.shuffle_mode = shuffle_mode
        self.router = router
        self.transport = transport
        self.pipe_command = pipe_command
        self.line_parser = line_parser or parse_line
        self.drop_remainder = drop_remainder
        self.seed = seed
        # where dead-letter files land (None -> data_quarantine_dir flag ->
        # tempdir fallback); the supervisor wires <checkpoint_root>/quarantine
        self.quarantine_dir = quarantine_dir
        self._dead_letter_seq = 0  # synchronized-by: load-thread exclusivity (one load/preload in flight)
        self._loading_qlog: Optional[QuarantineLog] = None  # synchronized-by: load-thread exclusivity

        self.date: Optional[str] = None
        self.pass_id = 0
        # bumped by every revert_pass: scopes the distributed working-set
        # exchange tags so a retried pass never consumes frames from the
        # aborted attempt (see TcpTransport.discard_epochs_below)
        self.pass_epoch = 0
        # explicit key-ownership map (parallel/membership.OwnershipMap),
        # installed/replaced by the elastic supervisor on membership or
        # placement changes; None = even split over all transport ranks
        self.ownership = None
        self.current_phase = 1  # 1 join, 0 update (data_set.h:291)
        self._filelist: List[str] = []
        # pass data lives EITHER columnar (store + shuffle order — the fast
        # path) or as a SlotRecord list (fallback parser / pv / eval paths);
        # the `records` property materializes a view list on demand.
        self.store: Optional[ColumnarRecords] = None
        self._order: Optional[np.ndarray] = None
        self._records: List[SlotRecord] = []
        self.ws: Optional[PassWorkingSet] = None
        self.device_table: Optional[np.ndarray] = None
        self.stats = PassStats()
        self._preload_thread: Optional[threading.Thread] = None
        self._preload_exc: Optional[BaseException] = None  # synchronized-by: preload join handoff (wait_preload_done)
        self._end_pass_fut = None  # pending end_pass_async worker
        self._in_pass = False
        # staged (store, order, records, ws, stats) loaded but not begun
        self._staged = None  # synchronized-by: preload join handoff (wait_preload_done)
        # staged boundary prefetch {src, keys, rows, epoch} built by the
        # feed stage alongside _staged; consumed (or dropped) by begin_pass.
        # Same synchronization discipline as _staged: written only by the
        # load path, read after wait_preload_done joins it.
        self._boundary_prefetch = None  # synchronized-by: preload join handoff (wait_preload_done)
        # stage time hidden behind training (reported via overlap_hidden_s);
        # accumulated on the load/preload thread, settled on the trainer
        # thread at wait_end_pass
        self._stage_lock = threading.Lock()
        self._stage_hidden_s = 0.0  # guarded-by: _stage_lock
        # serializes the live-pass slot swap (store/_order/_records/ws/
        # stats/_in_pass) between a finishing preload's publish and the
        # end_pass worker's failure re-open: main flips _in_pass False
        # BEFORE the worker runs, so without this lock a preload thread
        # that reads the flag can publish pass N+1 concurrently with a
        # failing worker restoring pass N — a torn mix of two passes.
        # RLock: the publish decision and _publish itself both take it.
        self._pass_lock = threading.RLock()
        self._loading_stats = self.stats  # synchronized-by: load-thread exclusivity (one load/preload in flight; wait_preload_done joins)

    # ---- record access ---------------------------------------------------

    @property
    def records(self) -> List[SlotRecord]:
        """Materialized SlotRecord view of the pass (compat paths: pv merge,
        AucRunner, direct inspection). Store-backed passes materialize
        lazily; the columnar fast path stays live."""
        if not self._records and self.store is not None and len(self.store):
            order = (
                self._order
                if self._order is not None
                else np.arange(len(self.store))
            )
            recs = []
            for i in order:
                r = self.store.record(int(i))
                # remember provenance so a reordering round-trip (pv merge ->
                # flatten) can stay columnar as a permutation of the store
                r._store_idx = int(i)
                recs.append(r)
            self._records = recs
        return self._records

    @records.setter
    def records(self, value) -> None:
        # assigning a list makes it the source of truth (pv flatten etc.);
        # the columnar store would be stale, so drop it
        self._records = list(value)
        self.store = None
        self._order = None

    # ---- pass config -----------------------------------------------------

    def set_date(self, date: str) -> None:
        """New day/pass id (BoxHelper::SetDate parity, box_wrapper.h:810)."""
        self.date = date
        self.pass_id += 1

    def set_filelist(self, files: Sequence[str]) -> None:
        """Full cluster file list; this node reads its rank-strided slice
        (dualbox striping, data_set.cc:1452-1464)."""
        expanded: List[str] = []
        for f in files:
            hits = fs_glob(f) if any(c in f for c in "*?[") else [f]
            expanded.extend(hits)
        self._filelist = expanded[self.rank :: self.nranks]

    def set_current_phase(self, phase: int) -> None:
        self.current_phase = phase

    # ---- pv merge (join phase) ------------------------------------------

    def preprocess_instance(
        self, max_rank: int = 3, valid_cmatch=(222, 223)
    ) -> int:
        """Group this pass's records into pv instances for join-phase
        training (PreprocessInstance parity, data_set.cc:1968-2009).
        Returns the pv count. Requires logkey parsing (search_id)."""
        if not self.schema.parse_logkey:
            raise RuntimeError(
                "preprocess_instance needs search_ids: build the SlotSchema "
                "with parse_logkey=True (else every record has search_id=0 "
                "and the whole pass merges into one pv)"
            )
        self.pvs: List[PvInstance] = merge_pv_instances(self.records)
        self._pv_max_rank = max_rank
        self._pv_valid_cmatch = tuple(valid_cmatch)
        self._pv_merged = True
        return len(self.pvs)

    @property
    def pv_merged(self) -> bool:
        """True between preprocess_instance and postprocess_instance."""
        return getattr(self, "_pv_merged", False)

    def postprocess_instance(self) -> None:
        """Restore the flat record view for the update phase
        (PostprocessInstance parity).

        When the pass is store-backed and every record still knows its
        store index, the pv-flattened order becomes a PERMUTATION of the
        columnar store — the update phase keeps the fast path (and, on a
        multi-host mesh, the transport-locksteped pads that require it)."""
        if not getattr(self, "_pv_merged", False):
            return
        flat = flatten_pv_instances(self.pvs)
        idx = [getattr(r, "_store_idx", None) for r in flat]
        if (
            self.store is not None
            and len(flat) == len(self.store)
            and all(i is not None for i in idx)
        ):
            self._records = flat
            self._order = np.asarray(idx, dtype=np.int64)
        else:
            self.records = flat  # setter: list becomes source of truth
        self.pvs = []
        self._pv_merged = False
        self._pv_plan_cache = None

    def pv_plan(self, n_devices: int = 1, min_batches: int = 0):
        """Cached index-level join-phase feed plan (see PvPlan).

        None when the pass isn't store-backed (records lack store indices);
        then consumers fall back to the record-level pv path. The cache is
        keyed by the pvs object identity plus the packing args — a repeat
        call over the same merged pass (warmup epoch, join eval, pad
        lockstep) costs nothing."""
        if not getattr(self, "_pv_merged", False):
            raise RuntimeError("preprocess_instance first")
        if self.store is None:
            return None
        key = (n_devices, min_batches)
        c = getattr(self, "_pv_plan_cache", None)
        if c is None or c[0] is not self.pvs:
            c = (self.pvs, {})
            self._pv_plan_cache = c
        if key not in c[1]:
            from paddlebox_tpu.data.pv_instance import build_pv_plan

            c[1][key] = build_pv_plan(
                self.pvs,
                self.batch_size,
                max_rank=self._pv_max_rank,
                valid_cmatch=self._pv_valid_cmatch,
                n_devices=n_devices,
                min_batches=min_batches,
            )
        return c[1][key]

    def num_pv_batches(self, n_devices: int = 1, global_count: bool = False) -> int:
        """Join-phase batch count; ``global_count`` allreduce-maxes it over
        the transport so every host runs the same number of mesh
        collectives (the pv analog of ``num_batches(global_count=True)``,
        compute_thread_batch_nccl parity data_set.cc:2069-2135)."""
        if not getattr(self, "_pv_merged", False):
            raise RuntimeError("preprocess_instance first")
        from paddlebox_tpu.data.pv_instance import count_pv_batches

        n = count_pv_batches(self.pvs, self.batch_size, n_devices=n_devices)
        if global_count and self.transport is not None and self.transport.n_ranks > 1:
            n = self.transport.allreduce_max(n, f"pv-count:{self.pass_id}")
        return n

    def pv_batches(
        self,
        n_batches: Optional[int] = None,
        n_devices: int = 1,
        min_batches: int = 0,
    ):
        """Join-phase batches: (SlotBatch with rank_offset, ins_weight).

        Whole pvs pack into ``batch_size`` instance slots, ghost-padded
        (see data/pv_instance.py). SlotBatch.rank_offset is set; ins_weight
        masks ghosts out of loss/metrics/show-clk. With ``n_devices > 1``
        the batch is device-blocked (no pv crosses a device, rank_offset
        rows device-local) for the mesh join step. ``min_batches`` appends
        all-ghost batches for multi-host lockstep (see pack_pv_batches).
        """
        if not getattr(self, "_pv_merged", False):
            raise RuntimeError("preprocess_instance first")
        packed = pack_pv_batches(
            self.pvs,
            self.batch_size,
            max_rank=self._pv_max_rank,
            valid_cmatch=self._pv_valid_cmatch,
            n_devices=n_devices,
            min_batches=min_batches,
        )
        if n_batches is not None:
            packed = itertools.islice(packed, n_batches)
        for records, rank_offset, weight in packed:
            sb = build_batch(records, self.schema)
            sb.rank_offset = rank_offset
            yield sb, weight

    # ---- load ------------------------------------------------------------

    def _native_eligible(self, path: str) -> bool:
        # native fast path applies when nothing needs the line-by-line
        # machinery (pipe converter, sampling, custom parser)
        return (
            self.pipe_command is None
            and self.line_parser is parse_line
            and config.get_flag("sample_rate") >= 1.0
            and config.get_flag("enable_native_parser")
            and not path.startswith(("hdfs:", "afs:"))  # fs dispatch tier
            and not path.endswith(".gz")
        )

    def _parse_lines(self, path: str, numbered_lines, qlog) -> list:
        """Parse (line_no, line) pairs with per-line quarantine; the one
        line-accounting path for the Python tier AND the native tier's
        corrupt-buffer fallback (so both report identically)."""
        out = []
        n_lines = n_parsed = n_benign = 0
        for line_no, line in numbered_lines:
            if not line:
                continue
            n_lines += 1
            try:
                rec = self.line_parser(line, self.schema)
            except Exception as e:  # noqa: BLE001 — quarantined + counted
                if qlog is None:  # strict mode: first bad line is fatal
                    raise
                qlog.quarantine_line(path, line_no, line, e)
                continue
            if rec is None:
                n_benign += 1
            else:
                n_parsed += 1
                out.append(rec)
        with self._stats_lock:
            st = self._loading_stats
            st.lines += n_lines
            st.parsed += n_parsed
            st.skipped_benign += n_benign
        return out

    def _read_one(self, path: str):
        """Read one part file -> ColumnarRecords chunk (native tier) or
        SlotRecord list (Python tier).

        File-level failures (unreadable, truncated gz, pipe-converter death,
        decode errors) quarantine the WHOLE file in data_quarantine mode —
        except FileNotFoundError: a missing input is a transient fault (late
        upstream drop) the fs/load-retry tier owns, and healing it by
        dropping the file would silently starve the pass."""
        qlog = self._loading_qlog
        try:
            fire("data.file_read")
            return self._read_one_inner(path, qlog)
        except FileNotFoundError:
            raise
        except Exception as e:  # noqa: BLE001 — quarantined + counted
            if qlog is None:
                raise
            qlog.quarantine_file(path, e)
            # empty columnar chunk when the pass could have gone columnar,
            # so one quarantined file never knocks the pass off the fast path
            if self._native_eligible(path):
                return ColumnarRecords.empty(
                    self.schema.num_sparse, self.schema.num_float
                )
            return []

    def _read_one_inner(self, path: str, qlog):
        if self._native_eligible(path):
            from paddlebox_tpu.utils import native

            if native.available():
                from paddlebox_tpu.utils.fs import fs_read_bytes_retry

                data = fs_read_bytes_retry(path)
                nstats: dict = {}
                try:
                    chunk = native.parse_buffer_columnar(
                        data, self.schema, nstats
                    )
                except ValueError:
                    if qlog is None:
                        raise
                    # the native parser rejects the whole buffer on its
                    # first corrupt line; re-parse per line so each bad
                    # line quarantines individually, and re-wrap columnar
                    # so the pass stays on the fast path
                    recs = self._parse_lines(
                        path,
                        enumerate(
                            data.decode("utf-8", errors="replace").splitlines(),
                            1,
                        ),
                        qlog,
                    )
                    return ColumnarRecords.from_records(recs, self.schema)
                with self._stats_lock:
                    st = self._loading_stats
                    skipped = nstats.get("skipped", 0)
                    st.lines += len(chunk) + skipped
                    st.parsed += len(chunk)
                    st.skipped_benign += skipped
                return chunk

        # per-file seed decorrelates sampling across part files (same-seeded
        # readers would keep/drop identical line indices)
        seed = hash((self.seed, self.pass_id, path)) & 0x7FFFFFFF
        begin_file = getattr(self.line_parser, "begin_file", None)
        if begin_file is not None:  # per-file parser state (e.g. cache lines)
            begin_file(path)
        reader = BufferedLineFileReader(path, converter=self.pipe_command, seed=seed)
        # lines_read is incremented before the reader yields, so it IS the
        # 1-based number of the line in flight
        return self._parse_lines(
            path, ((reader.lines_read, line) for line in reader), qlog
        )

    def load_into_memory(self) -> None:
        """Threaded read -> (optional shuffle) -> staged records + key set.

        Loads into a STAGING slot, not the live pass — so it can run while
        the previous pass is still training (double buffering; the reference
        survives two passes in RAM the same way, via the record object pool,
        data_feed.h:934). ``begin_pass`` consumes the staged data.
        """
        if self._staged is not None:
            raise RuntimeError("staged pass not yet consumed by begin_pass")
        if self._preload_thread is not None and threading.current_thread() is not self._preload_thread:
            raise RuntimeError("preload in flight; wait_preload_done first")
        self._stats_lock = threading.Lock()
        stats = PassStats(files=len(self._filelist))
        self._loading_stats = stats
        self._loading_qlog = (
            QuarantineLog() if config.get_flag("data_quarantine") else None
        )
        ws = self._new_working_set()
        parts: list = []
        try:
            if self._filelist:
                with ThreadPoolExecutor(max_workers=self.read_threads) as pool:
                    parts = list(pool.map(self._read_one, self._filelist))
            qlog, self._loading_qlog = self._loading_qlog, None
        except BaseException:
            self._loading_qlog = None
            raise
        if qlog is not None:
            self._settle_quarantine(stats, qlog)

        store, order, records = self._normalize_and_shuffle(parts)

        # MergeInsKeys parity (data_set.cc:1628-1683): every feasign of the
        # pass feeds the working set. Runs post-shuffle (ownership is final
        # only after routing).
        if store is not None:
            if len(store.u64_values):
                ws.add_keys(store.u64_values)
            stats.records = len(store)
        else:
            chunk = 4096
            for i in range(0, len(records), chunk):
                ws.add_keys(
                    np.concatenate([r.u64_values for r in records[i : i + chunk]])
                )
            stats.records = len(records)
        self._staged = (store, order, records, ws, stats)
        try:
            self._stage_boundary_prefetch(ws)
        except BaseException:
            # a failed feed stage must not wedge the retry loop: the next
            # load_into_memory would refuse over the leftover staged slot
            self.discard_staged()
            raise
        with self._pass_lock:
            # flag read and publish are one atomic step: an end_pass
            # worker's failure re-open must not interleave (it restores
            # pass N's slots and would tear a concurrent N+1 publish)
            if not self._in_pass:
                # no pass training right now: publish immediately so
                # memory_data_size()/stats match reference post-load
                # semantics (begin_pass still consumes the staged tuple)
                self._publish(self._staged)

    def _stage_boundary_prefetch(self, ws) -> None:
        """Stage 2 of the boundary feed pipeline: premerge the staged
        pass's key chunks and (gated) prefetch its host rows, all on the
        load/preload thread while the current pass trains.

        The premerge collapses ``ws._key_chunks`` so the later finalize
        re-merges a singleton list through merge_unique_keys' no-copy fast
        path; the prefetch pulls rows only for keys NOT in the live pass —
        the live pass's keys are the only host rows the boundary's
        writeback/splice can change, so everything prefetched stays valid
        modulo show/clk decay, which the consumer re-applies bitwise
        (:func:`sparse_table._rows_with_prefetch`)."""
        if not config.get_flag("boundary_pipeline"):
            return
        self._boundary_prefetch = None
        fire("boundary.premerge")
        with record_event("boundary.premerge", "boundary") as span:
            merged = ws.premerge(
                int(config.get_flag("boundary_merge_threads"))
            )
        premerge_s = span.seconds
        STAT_SET("boundary.premerge_s", premerge_s)
        STAT_OBSERVE("boundary.premerge_s", premerge_s)
        if self._in_pass:
            with self._stage_lock:
                self._stage_hidden_s += premerge_s

        live = self.ws
        table = self.table
        if (
            not config.get_flag("boundary_prefetch_pull")
            or not self._in_pass
            or not len(merged)
            or not isinstance(ws, PassWorkingSet)
            or not isinstance(live, PassWorkingSet)
            or not live._finalized
            or table.opt.shrink_threshold != 0
            or table.mem_cap_rows is not None
        ):
            return
        # exclude the live pass's keys: their host rows are not final
        # until its writeback/splice lands at the boundary
        exclude = live.sorted_keys
        if len(exclude):
            pos = np.minimum(
                np.searchsorted(exclude, merged), len(exclude) - 1
            )
            need = merged[exclude[pos] != merged]
        else:
            need = merged
        if not len(need):
            return
        # a departing-slice push from the PREVIOUS boundary may still be
        # in flight and can cover keys in `need` (departed two passes ago,
        # returning now): wait for it to land, WITHOUT consuming a failure
        # — that stays armed for the end_pass worker's join_push
        carrier = getattr(self, "_carrier", None)
        if carrier is not None and not carrier.flushed:
            carrier.wait_push()
        fire("boundary.stage_pull")
        with record_event("boundary.stage_pull", "boundary") as span:
            rows, epoch = table.prefetch_rows(need)
        pull_s = span.seconds
        STAT_SET("boundary.prefetch_pull_s", pull_s)
        STAT_OBSERVE("boundary.prefetch_pull_s", pull_s)
        with self._stage_lock:
            self._stage_hidden_s += pull_s
        self._boundary_prefetch = {
            "src": merged, "keys": need, "rows": rows, "epoch": epoch,
        }

    def discard_staged(self) -> None:
        """Drop a staged-but-unconsumed load and its boundary prefetch
        (supervisor cancel path: a staged pass N+1 must not survive a
        coordinated revert of pass N)."""
        self._staged = None
        self._boundary_prefetch = None

    # ---- quarantine / admission -----------------------------------------

    def _settle_quarantine(self, stats: PassStats, qlog: QuarantineLog) -> None:
        """Fold the load's quarantine log into its PassStats, write the
        dead-letter file when anything was quarantined, and publish the
        data.quarantine.* gauges."""
        qlog.settle(stats)
        if qlog.total:
            self._dead_letter_seq += 1
            name = (
                f"pass-{self.date or 'na'}-{self.pass_id:04d}"
                f"-r{self.rank}-{self._dead_letter_seq:03d}"
            )
            with record_event("data.quarantine.dead_letter", "data"):
                stats.dead_letter = qlog.write(
                    resolve_quarantine_dir(self.quarantine_dir),
                    name,
                    meta={
                        "date": self.date,
                        "pass_id": self.pass_id,
                        "rank": self.rank,
                        "files": stats.files,
                        "lines": stats.lines,
                    },
                )
            STAT_ADD("data.quarantine.dead_letter_files")
        STAT_SET("data.quarantine.bad_lines", stats.bad_lines)
        STAT_SET("data.quarantine.bad_files", stats.bad_files)
        if stats.bad_lines:
            STAT_ADD("data.quarantine.bad_lines_total", stats.bad_lines)
        if stats.bad_files:
            STAT_ADD("data.quarantine.bad_files_total", stats.bad_files)

    def admission_report(self) -> Dict:
        """Bounded-loss admission verdict for the pass about to begin.

        Computed over the STAGED load when one is pending (the pass
        ``begin_pass`` would consume), else the live stats. ``poisoned``
        is True when quarantine is on and either corrupt fraction exceeds
        its threshold — the caller (begin_pass, or the supervisor's
        poison-aware pre-check) decides fail/skip/degrade."""
        st = self._staged[4] if self._staged is not None else self.stats
        max_lf = float(config.get_flag("max_bad_line_fraction"))
        max_ff = float(config.get_flag("max_bad_file_fraction"))
        lf = st.bad_lines / max(1, st.lines)
        ff = st.bad_files / max(1, st.files)
        poisoned = bool(config.get_flag("data_quarantine")) and (
            lf > max_lf or ff > max_ff
        )
        parts = []
        if lf > max_lf:
            parts.append(
                f"{st.bad_lines}/{st.lines} lines quarantined "
                f"({lf:.5f} > max_bad_line_fraction {max_lf:.5f})"
            )
        if ff > max_ff:
            parts.append(
                f"{st.bad_files}/{st.files} part files quarantined "
                f"({ff:.5f} > max_bad_file_fraction {max_ff:.5f})"
            )
        detail = ""
        if poisoned:
            detail = "pass data poisoned: " + "; ".join(parts)
            if st.dead_letter:
                detail += f"; dead-letter: {st.dead_letter}"
        return {
            "poisoned": poisoned,
            "detail": detail,
            "line_fraction": lf,
            "file_fraction": ff,
            "bad_lines": st.bad_lines,
            "bad_files": st.bad_files,
            "lines": st.lines,
            "files": st.files,
            "dead_letter": st.dead_letter,
        }

    def check_admission(self) -> Dict:
        """Raise DataPoisonedError when the pending pass is over the
        bounded-loss thresholds; returns the report otherwise."""
        rep = self.admission_report()
        if rep["poisoned"]:
            raise DataPoisonedError(
                rep["detail"], report=rep, dead_letter=rep["dead_letter"]
            )
        return rep

    def drop_pass_data(self) -> None:
        """Abandon the loaded-but-unbegun pass data (supervisor
        on_poisoned_pass="skip_pass"): staged slot, published records, and
        the un-finalized working set all go; the table is untouched."""
        self.discard_staged()
        if not self._in_pass:
            self.store = None
            self._order = None
            self._records = []
            self.ws = None
            self.stats = PassStats()

    def _new_working_set(self):
        """Fresh (un-finalized) working set for this pass: multi-host
        key-exchange flavor when a transport spans ranks, else local.
        Shared by the load path and revert_pass so their retrains can never
        diverge."""
        if self.transport is not None and self.transport.n_ranks > 1:
            # multi-host: host-sharded table ownership + key exchange;
            # n_mesh_shards is the GLOBAL mesh shard count. ``ownership``
            # (an OwnershipMap, set by the elastic supervisor on membership
            # or placement changes) pins the key routing; None keeps the
            # default even split over all ranks.
            from paddlebox_tpu.table.dist_ws import DistributedWorkingSet

            return DistributedWorkingSet(
                self.transport,
                self.n_mesh_shards,
                pass_id=self.pass_id,
                epoch=self.pass_epoch,
                ownership=getattr(self, "ownership", None),
            )
        return PassWorkingSet(n_mesh_shards=self.n_mesh_shards)

    def _publish(self, staged) -> None:
        store, order, records, ws, stats = staged
        with self._pass_lock:
            self.store = store
            self._order = order
            self._records = records if records is not None else []
            self.ws = ws
            self.stats = stats
            # new data in memory: lockstep batch count must be renegotiated
            self._load_gen = getattr(self, "_load_gen", 0) + 1

    def _normalize_and_shuffle(self, parts: list):
        """File-part chunks -> (store, order, records): columnar when every
        part is columnar (native parse), SlotRecord list otherwise."""
        if parts and all(isinstance(p, ColumnarRecords) for p in parts):
            non_empty = [p for p in parts if len(p)]
            if non_empty:
                store = (
                    ColumnarRecords.concat(non_empty)
                    if len(non_empty) > 1
                    else non_empty[0]
                )
                return self._shuffle_store(store)
        records: List[SlotRecord] = []
        for p in parts:
            records.extend(p.records() if isinstance(p, ColumnarRecords) else p)
        return None, None, self._shuffle_records(records)

    def _shuffle_store(self, store: ColumnarRecords):
        """Columnar shuffle: routing moves arrays, local order is a
        permutation (no data movement at all)."""
        mode = self.shuffle_mode
        rng = np.random.default_rng(self.seed + self.pass_id)
        if mode == "none":
            return store, None, []
        if mode != "local" and self.router is not None:
            dests = shuffle_route_store(
                store, self.router.n_nodes, mode, self.seed + self.pass_id
            )
            parts = [
                store.select(np.nonzero(dests == d)[0])
                for d in range(self.router.n_nodes)
            ]
            self.router.exchange(self.rank, parts)
            chunks = self.router.collect(self.rank)
            cols = [c for c in chunks if isinstance(c, ColumnarRecords)]
            lists = [c for c in chunks if not isinstance(c, ColumnarRecords)]
            if lists:  # mixed transports: normalize to records
                records = [r for c in lists for r in c]
                for c in cols:
                    records.extend(c.records())
                order = rng.permutation(len(records))
                return None, None, [records[i] for i in order]
            store = (
                ColumnarRecords.concat(cols)
                if cols
                else ColumnarRecords.empty(store.n_sparse, store.n_float)
            )
        elif mode != "local" and self.nranks != 1:
            raise RuntimeError("global shuffle across ranks needs a router")
        return store, rng.permutation(len(store)), []


    def preload_into_memory(self) -> None:
        """Overlap next pass's IO with current training
        (PreLoadIntoMemory, data_set.cc:1576-1626)."""
        if self._preload_thread is not None:
            raise RuntimeError("preload already running")

        def run():
            try:
                self.load_into_memory()
            except BaseException as e:  # surfaced in wait_preload_done
                self._preload_exc = e

        self._preload_thread = threading.Thread(target=run, daemon=True)
        self._preload_thread.start()

    def wait_preload_done(self) -> None:
        if self._preload_thread is None:
            return
        self._preload_thread.join()
        self._preload_thread = None
        if self._preload_exc is not None:
            exc, self._preload_exc = self._preload_exc, None
            raise exc

    def _shuffle_records(self, records: List[SlotRecord]) -> List[SlotRecord]:
        mode = self.shuffle_mode
        if mode == "none":
            return records
        rng = np.random.default_rng(self.seed + self.pass_id)
        if mode == "local":
            order = rng.permutation(len(records))
            return [records[i] for i in order]
        # global modes route records between nodes, then local-shuffle
        if self.router is None:
            if self.nranks != 1:
                raise RuntimeError("global shuffle across ranks needs a router")
            order = rng.permutation(len(records))
            return [records[i] for i in order]
        dests = shuffle_route(records, self.router.n_nodes, mode, self.seed + self.pass_id)
        parts: List[List[SlotRecord]] = [[] for _ in range(self.router.n_nodes)]
        for r, d in zip(records, dests):
            parts[d].append(r)
        self.router.exchange(self.rank, parts)
        mine = [
            r
            for chunk in self.router.collect(self.rank)
            for r in (chunk.records() if isinstance(chunk, ColumnarRecords) else chunk)
        ]
        order = rng.permutation(len(mine))
        return [mine[i] for i in order]

    # ---- AucRunner slot-shuffle eval ------------------------------------

    def slots_shuffle(self, slots) -> dict:
        """Replace ``slots``' feasigns in the in-memory records with pooled
        candidates for feature-importance eval (BoxPSDataset.slots_shuffle
        parity, python dataset.py:1191-1210 -> BoxHelper::SlotsShuffle).

        The AucRunner is created lazily over all sparse slots on first use;
        pass ``slots=[]``/set() to restore the previous shuffle. Shuffled
        keys must still resolve in the pass working set — candidates come
        from this pass's own records, so they always do.
        """
        from paddlebox_tpu.metrics.auc_runner import AucRunner

        if not self.records:
            raise RuntimeError("slots_shuffle needs in-memory records")
        recs = self.records  # materializes the store view if needed
        runner = getattr(self, "_auc_runner", None)
        if runner is None or getattr(self, "_auc_runner_pass", None) != self.pass_id:
            cap = config.get_flag("auc_runner_pool_size")
            runner = AucRunner(
                self.schema,
                replaced_slots=[s.name for s in self.schema.used_sparse],
                capacity=cap,
                seed=self.seed + self.pass_id,
            )
            runner.observe(recs)
            self._auc_runner = runner
            self._auc_runner_pass = self.pass_id
        out = runner.slots_shuffle(recs, set(slots))
        if self.store is not None:
            # the runner rewrote record arrays; the columnar store is stale —
            # rebuild it (order baked in) so the fast path serves the
            # shuffled keys
            self.store = ColumnarRecords.from_records(recs, self.schema)
            self._order = None
            self.store.invalidate_rows()
        return out

    @property
    def auc_runner_phase(self) -> int:
        runner = getattr(self, "_auc_runner", None)
        return runner.phase if runner is not None else 1

    # ---- pass lifecycle --------------------------------------------------

    def _eager_drain(self) -> None:
        """Background carrier flush (carried_eager_flush). A failure here
        must be LOUD: drain_pending keeps the failed carrier registered so
        durability is preserved, and the exception is stored and re-raised
        at the next pass boundary instead of dying with the thread."""
        try:
            self.table.drain_pending()
        except Exception as e:  # noqa: BLE001 — surfaced at the boundary
            self._eager_flush_error = e

    def _raise_pending_flush_error(self) -> None:
        # join the in-flight drain first so the check is deterministic: an
        # unjoined thread could fail AFTER this boundary's check and the
        # error would surface a boundary late (or never, at process end)
        t = getattr(self, "_eager_thread", None)
        if t is not None and t.is_alive():
            t.join()
        self._eager_thread = None
        err = getattr(self, "_eager_flush_error", None)
        if err is not None:
            self._eager_flush_error = None
            raise RuntimeError(
                "background carrier flush failed — carried values remain "
                "owed and will be retried by the next drain_pending"
            ) from err

    def begin_pass(
        self,
        round_to: int = 512,
        enable_revert: bool = False,
        trainer=None,
        admit_poisoned: bool = False,
    ) -> np.ndarray:
        """Consume the staged load, finalize the working set, build the device
        table (BeginFeedPass+EndFeedPass+BeginPass collapse: on TPU the HBM
        staging IS the finalize, box_wrapper.cc:580-626).

        ``enable_revert=True`` arms a PassGuard (Confirm/Revert parity,
        fleet_wrapper.h:319-321): the pass keys' pre-train rows (and, with
        ``trainer``, the dense params/opt state) are snapshotted so
        ``revert_pass()`` can reject everything this pass publishes;
        ``end_pass`` confirms.

        Bounded-loss admission gate: a pass whose load quarantined more
        than ``max_bad_line_fraction`` / ``max_bad_file_fraction`` raises
        :class:`DataPoisonedError` BEFORE anything is finalized or armed —
        ``admit_poisoned=True`` overrides (the supervisor's
        ``on_poisoned_pass="degrade"`` path, which trains over the pass
        with the quarantined records dropped)."""
        # a pending async end_pass mutates the host table (writeback/decay/
        # spill); finalize must see its final state
        self.wait_end_pass()
        self._raise_pending_flush_error()
        if self._in_pass:
            # either end_pass was never called, or a FAILED end_pass
            # re-opened the pass; silently starting a new one would strand
            # its state (and discard any armed rollback snapshot)
            raise RuntimeError(
                "previous pass is still open — call end_pass (or, after a "
                "failed end_pass, retry it / revert_pass) before begin_pass"
            )
        if not admit_poisoned:
            # gate BEFORE consuming the staged slot: a rejected pass leaves
            # the staged data intact so the caller can still degrade
            # (begin_pass(admit_poisoned=True)) or drop_pass_data it
            self.check_admission()
        if self._staged is not None:
            self._publish(self._staged)
            self._staged = None
        prefetch, self._boundary_prefetch = self._boundary_prefetch, None
        if self.ws is None:
            raise RuntimeError("load_into_memory first")
        if enable_revert:
            # the rollback snapshot reads host rows — device-carried values
            # must land first or the snapshot (and a later revert) would
            # resurrect pre-carry state
            self.table.drain_pending()
        if not self.ws._finalized:
            carrier = getattr(self, "_carrier", None)
            if carrier is not None and carrier.flushed:
                carrier = None
            if carrier is not None:
                # PassWorkingSet takes a TableCarrier; the multi-host
                # DistributedWorkingSet takes a MultiHostCarrier (per-host
                # shard-block splice) — same kwarg, same delta boundary
                self.device_table = self.ws.finalize(
                    self.table, round_to=round_to, carrier=carrier,
                    prefetch=prefetch,
                )
                if config.get_flag("carried_eager_flush"):
                    self._eager_thread = threading.Thread(
                        target=self._eager_drain, daemon=False
                    )
                    self._eager_thread.start()
            else:
                self.device_table = self.ws.finalize(
                    self.table, round_to=round_to, prefetch=prefetch
                )
        self.stats.keys = self.ws.n_keys
        # monitor parity: the reference bumps STAT_total_feasign_num_in_mem
        # as passes stage into memory (box_wrapper.cc:1282)
        STAT_SET("total_feasign_num_in_mem", self.stats.keys)
        STAT_SET("total_records_in_mem", self.memory_data_size())
        self._in_pass = True
        self._guard = None
        if enable_revert:
            from paddlebox_tpu.train.rollback import PassGuard

            self._guard = PassGuard(self.table, trainer)
            self._guard.begin(self.ws.sorted_keys)
        return self.device_table

    def kick_writeback(self, trained_table) -> None:
        """Start the end-of-pass host writeback NOW, overlapped with
        whatever runs between training and ``end_pass`` (gate evaluation,
        verdict exchange, the next pass's staging): the boundary worker
        then JOINS this kick instead of writing back inline, so
        ``boundary.writeback_s`` records only the residual blocking tail
        and the hidden seconds flow into ``boundary.overlap_hidden_s``.

        Safe under an armed guard: rollback's PassGuard contract covers
        zero/partial/full writeback, so kicking before the verdict costs
        nothing — a rejected pass cancels the kick at a chunk boundary in
        ``revert_pass`` and the revert restores pre-pass rows either way.
        No-op when no pass is open, a kick is already pending, or the
        ``overlap_writeback`` flag is off."""
        if (
            trained_table is None
            or not self._in_pass
            or self.ws is None
            or not bool(config.get_flag("overlap_writeback"))
            or getattr(self, "_wb_kick", None) is not None
        ):
            return
        ws, table = self.ws, self.table
        kick = _WritebackKick(ws)

        def run_kick():
            try:
                with record_event("boundary.writeback_kick", "boundary") as span:
                    arr = _trained_to_host(trained_table, table.layout)
                    ws.writeback(arr, cancel=kick.cancel)
                kick.fut.set_result(span.seconds)
            except BaseException as e:
                kick.fut.set_exception(e)

        # non-daemon for the same reason as the end_pass worker: interpreter
        # exit joins an in-flight writeback instead of truncating it
        kick.thread = threading.Thread(target=run_kick, daemon=False)
        self._wb_kick = kick
        kick.thread.start()

    def _cancel_writeback_kick(self) -> None:
        """Stop a pending overlapped writeback at its next chunk boundary
        and join it — whatever landed is exactly what guard.revert()
        undoes. Swallows the cancellation (it is the requested outcome);
        real failures are counted, not raised: the revert that follows
        undoes their partial effects too."""
        kick = getattr(self, "_wb_kick", None)
        if kick is None:
            return
        from paddlebox_tpu.table.sparse_table import WritebackCancelled

        kick.cancel.set()
        try:
            kick.fut.result()
        except WritebackCancelled:
            STAT_ADD("data.revert_writeback_cancelled")
        except BaseException:
            STAT_ADD("data.revert_end_pass_errors")
        kick.thread.join()
        self._wb_kick = None

    def revert_pass(self) -> None:
        """Reject the current pass (Revert parity, fleet_wrapper.h:319-321,
        pslib __init__.py:673-690): every pass key's host row returns to its
        pre-pass value (undoing any partial/complete writeback), the dense
        side restores, and the in-memory data re-arms so ``begin_pass`` can
        retrain it from scratch."""
        self._cancel_writeback_kick()
        if self._end_pass_fut is not None:
            try:
                self.wait_end_pass()
            except Exception:
                # a failed publish is exactly what revert undoes — but it
                # is still an incident; revert erasing it would make the
                # retry loop's root cause invisible
                STAT_ADD("data.revert_end_pass_errors")
        guard = getattr(self, "_guard", None)
        if guard is None or not guard.armed:
            raise RuntimeError(
                "no armed rollback — begin_pass(enable_revert=True) first"
            )
        guard.revert()
        self._guard = None
        # cancel any staged next pass: join the feed stage first (it may
        # still be writing the staged slot), then drop it — a revert means
        # the retried pass re-derives everything downstream of it, and the
        # supervisor re-loads (or re-stages) pass N+1 afterwards
        if self._preload_thread is not None:
            try:
                self.wait_preload_done()
            except Exception:
                # a failed staged load is discarded with the stage; count
                # it so a flaky reader doesn't hide behind the revert
                STAT_ADD("data.revert_preload_errors")
        self.discard_staged()
        # new epoch for the retrain: the aborted attempt's in-flight
        # exchange frames (if any) must never reach the retried exchange
        self.pass_epoch += 1
        if self.transport is not None and hasattr(
            self.transport, "discard_epochs_below"
        ):
            self.transport.discard_epochs_below(self.pass_epoch)
        # fresh working set over the same in-memory records for the retrain
        ws = self._new_working_set()
        if self.store is not None:
            ws.add_keys(self.store.u64_values)
            self.store.invalidate_rows()
        else:
            for r in self._records:
                ws.add_keys(r.u64_values)
        self.ws = ws
        self.device_table = None
        self._in_pass = False
        self._auc_runner = None

    def end_pass(
        self,
        trained_table: Optional[np.ndarray] = None,
        need_save_delta: bool = False,
        delta_dir: Optional[str] = None,
        shrink: bool = True,
    ) -> dict:
        """Flush trained rows to the host store, decay/shrink, optional delta
        save (EndPass box_wrapper.cc:627 + SaveDelta :1316)."""
        self.end_pass_async(
            trained_table,
            need_save_delta=need_save_delta,
            delta_dir=delta_dir,
            shrink=shrink,
        )
        return self.wait_end_pass()

    def end_pass_async(
        self,
        trained_table: Optional[np.ndarray] = None,
        need_save_delta: bool = False,
        delta_dir: Optional[str] = None,
        shrink: bool = True,
    ) -> None:
        """EndPass in a background thread, overlapped with the next pass's
        ``set_date``/``load_into_memory``/``preload_into_memory``.

        The device->host pull of the trained table plus the host writeback,
        decay/shrink, delta save, and disk spill are the dominant
        between-pass cost; none of it touches what the next LOAD needs (the
        load only reads files and collects keys — the host table is first
        consulted again at ``begin_pass`` finalize, which joins this thread
        automatically). The same overlap the reference gets from BoxHelper's
        feed/end thread pair (box_wrapper.h:897-959). ``trained_table`` may
        be the live device array — the transfer happens on the worker.
        Results surface from ``wait_end_pass`` (or the next begin_pass)."""
        if not self._in_pass:
            raise RuntimeError("begin_pass first")
        self._raise_pending_flush_error()
        if need_save_delta and delta_dir is None:
            raise ValueError("need_save_delta requires delta_dir")
        ws, guard, table = self.ws, getattr(self, "_guard", None), self.table
        # consume a pending overlapped writeback for THIS working set: the
        # worker joins it instead of writing back inline. A kick for a
        # different ws (shouldn't happen — revert/begin clear it) is left
        # alone and the classic path runs.
        kick = getattr(self, "_wb_kick", None)
        if kick is not None and kick.ws is ws:
            self._wb_kick = None
        else:
            kick = None
        # device-carried boundary: retain the trained DEVICE table instead
        # of fetching it; the next finalize splices surviving rows
        # device-to-device and fetches only the departing slice (EndPass
        # HBM-cache-warm parity, box_wrapper.cc:627-651). Gated to the
        # single-device single-process path; a save/guard/delta in the way
        # flushes via table.drain_pending. An in-flight kick is already
        # writing the full table back, so carrying is off for this boundary.
        carrier = None
        carry_ok = (
            trained_table is not None
            and not isinstance(trained_table, np.ndarray)
            and getattr(trained_table, "ndim", 0) in (2, 3)
            and bool(config.get_flag("enable_carried_table"))
            and guard is None
            and kick is None
        )
        from paddlebox_tpu.table.dist_ws import DistributedWorkingSet
        from paddlebox_tpu.table.sparse_table import PassWorkingSet

        if isinstance(ws, PassWorkingSet) and carry_ok:
            import jax as _jax

            if (
                isinstance(trained_table, _jax.Array)
                and _jax.process_count() == 1
            ):
                from paddlebox_tpu.table.carrier import TableCarrier

                # decay is NOT pre-set: the worker's decay_and_shrink notes
                # it on every pending carrier under the maintenance lock,
                # so a concurrent drain can neither miss nor double it.
                # 3-D = single-host MESH table [ns, cap, W] (device-axis
                # sharded): rows stay in-shard across passes (key shard is
                # stable), so the splice's gathers/scatters are legal on
                # the sharded array — any reshard rides ICI, never the
                # host link
                carrier = TableCarrier(trained_table, ws, table.layout)
        elif isinstance(ws, DistributedWorkingSet):
            # multi-host: lockstep the carry decision over the transport
            # (like the resident gate) so every host takes the same
            # boundary. The allreduce runs UNCONDITIONALLY for a DWS pass
            # — a host that can't carry (flag off, guard armed, numpy
            # table) must still answer, or the hosts that can would hang.
            import jax as _jax

            self._carry_seq = getattr(self, "_carry_seq", 0) + 1
            local_ok = int(carry_ok and isinstance(trained_table, _jax.Array))
            agree = -ws.transport.allreduce_max(
                -local_ok, f"carry-gate:{self._carry_seq}"
            )
            if agree:
                from paddlebox_tpu.table.carrier import MultiHostCarrier

                # per-host carrier over this host's addressable shard
                # blocks; splice/departures/flush stay host-local because
                # key->shard->device pinning is pass-stable (writeback is
                # host-local for the same reason, dist_ws.py:20-22)
                carrier = MultiHostCarrier(
                    trained_table, ws.owned_shard_keys, table.layout,
                    ownership_epoch=ws.ownership.epoch,
                )
        if carrier is not None:
            table.add_pending_carrier(carrier)
            # the PREVIOUS boundary's carrier (if any) is superseded:
            # its carried keys live on in this carrier's table, its
            # departed keys were pushed at finalize
            prev = getattr(self, "_carrier", None)
            if prev is not None and not prev.flushed:
                prev.supersede()
            self._carrier = carrier
        # the pass state clears NOW so the next load starts immediately.
        # _guard intentionally STAYS set until the worker confirms, and a
        # worker FAILURE restores the cleared state — so a failed publish
        # (bad delta dir, full disk) leaves the pass open for a retried
        # end_pass, or revertible via revert_pass when a guard is armed;
        # begin_pass refuses to start a new pass over the unresolved one
        saved_state = (self.store, self._order, self._records)
        self._records = []
        self.store = None
        self._order = None
        self.ws = None
        self.device_table = None
        self._in_pass = False
        self._auc_runner = None  # pools reference this pass's records only

        prev_carrier = getattr(self, "_prev_boundary_carrier", None)
        self._prev_boundary_carrier = carrier

        def run():
            t_run = time.perf_counter()
            wb_s = 0.0
            try:
                fire("boundary.writeback")
                if prev_carrier is not None:
                    # the previous boundary's departing-slice push must land
                    # before this boundary's decay (a late push would
                    # overwrite decayed rows with un-decayed values)
                    prev_carrier.join_push()
                t_wb = time.perf_counter()
                if kick is not None:
                    # overlapped writeback: the kick thread has been pushing
                    # since the trained table landed — only the residual
                    # tail blocks this boundary, and the seconds the kick
                    # ran before this join were hidden behind the gate/
                    # verdict window (absorbed into overlap_hidden_s)
                    kick_secs = kick.fut.result()
                    kick.thread.join()
                    wb_s = time.perf_counter() - t_wb
                    hidden = max(0.0, kick_secs - wb_s)
                    with self._stage_lock:
                        self._stage_hidden_s += hidden
                    STAT_SET("boundary.writeback_hidden_s", hidden)
                    STAT_OBSERVE("boundary.writeback_hidden_s", hidden)
                    if prev_carrier is not None and not prev_carrier.flushed:
                        prev_carrier.supersede()
                elif trained_table is not None and carrier is None:
                    arr = _trained_to_host(trained_table, table.layout)
                    ws.writeback(arr)
                    if prev_carrier is not None and not prev_carrier.flushed:
                        # the full classic writeback covers everything a
                        # still-pending carrier owed (carried keys are this
                        # pass's rows; its departures just joined) — a later
                        # splice or drain of it would resurrect stale values
                        prev_carrier.supersede()
                    wb_s = time.perf_counter() - t_wb
                STAT_SET("boundary.writeback_s", wb_s)
                STAT_OBSERVE("boundary.writeback_s", wb_s)
                dropped = table.decay_and_shrink() if shrink else 0
                saved = table.save_delta(delta_dir) if need_save_delta else 0
                # enforce the host-RAM cap: evict cold rows to the disk tier
                # (LoadSSD2Mem inverse; next finalize promotes what it needs)
                if getattr(table, "mem_cap_rows", None) is not None:
                    table.maybe_spill()
                # per-pass table.tier.* gauges (occupancy, spill/promote flow)
                if hasattr(table, "publish_tier_stats"):
                    table.publish_tier_stats()
                # the pass is published: drop the rollback snapshot (Confirm)
                if guard is not None and guard.armed:
                    guard.confirm()
                if self._guard is guard:
                    self._guard = None
                return {
                    "dropped": dropped,
                    "delta_keys": saved,
                    "secs": time.perf_counter() - t_run,
                }
            except BaseException:
                # re-open the pass so the failure is recoverable; under
                # the pass lock so a preload thread publishing the next
                # pass can't interleave with the restore
                with self._pass_lock:
                    self.store, self._order, self._records = saved_state
                    self.ws = ws
                    self._in_pass = True
                raise

        from concurrent.futures import Future

        fut: Future = Future()

        def worker():
            try:
                with record_event("boundary.end_pass_worker", "boundary"):
                    fut.set_result(run())
            except BaseException as e:
                fut.set_exception(e)

        self._end_pass_fut = fut
        # non-daemon: interpreter exit JOINS an in-flight publish instead of
        # killing it mid-write (truncated delta files, lost writeback);
        # wait_end_pass joins the handle once the future settles
        self._end_pass_thread = threading.Thread(target=worker, daemon=False)
        self._end_pass_thread.start()

    def wait_end_pass(self) -> dict:
        """Join a pending end_pass_async; returns its result dict (or the
        last one again if already joined; {} if none ever ran).

        Also settles the boundary overlap accounting: worker seconds not
        spent blocking here ran behind training, and so did the feed
        stage's premerge/prefetch — their sum is ``boundary.overlap_hidden_s``."""
        fut = self._end_pass_fut
        if fut is not None:
            t0 = time.perf_counter()
            try:
                self._end_pass_result = fut.result()
            except BaseException:
                # never let a failed pass alias the previous pass's success
                self._end_pass_result = {}
                raise
            finally:
                self._end_pass_fut = None
                # the future settles inside the worker, so this join only
                # covers the record_event epilogue — but it retires the
                # handle instead of abandoning a zombie Thread object
                t = getattr(self, "_end_pass_thread", None)
                if t is not None:
                    t.join()
                    self._end_pass_thread = None
            blocked = time.perf_counter() - t0
            hidden = max(
                0.0, self._end_pass_result.get("secs", 0.0) - blocked
            )
            with self._stage_lock:
                stage_hidden, self._stage_hidden_s = self._stage_hidden_s, 0.0
            STAT_SET("boundary.overlap_hidden_s", hidden + stage_hidden)
            STAT_OBSERVE("boundary.overlap_hidden_s", hidden + stage_hidden)
        # surface an already-stored eager-flush failure HERE too: a run's
        # final pass has no next begin_pass to raise it, and exiting 0
        # with carried values still owed would hide the durability gap
        # (the failed carrier stays registered; drain_pending retries it).
        # Only a stored error raises — a still-running flush is joined at
        # the next boundary as before, preserving the overlap.
        err = getattr(self, "_eager_flush_error", None)
        if err is not None:
            self._eager_flush_error = None
            raise RuntimeError(
                "background carrier flush failed — carried values remain "
                "owed and will be retried by the next drain_pending"
            ) from err
        return getattr(self, "_end_pass_result", {})

    # ---- batch serving ---------------------------------------------------

    def memory_data_size(self) -> int:
        if self.store is not None:
            return len(self.store)
        return len(self._records)

    def num_batches(self, global_count: Optional[int] = None) -> int:
        """Minibatch count this pass. Lockstep across nodes: with a
        transport attached the local count is allreduce-max'd automatically
        (compute_thread_batch_nccl parity, data_set.cc:2069-2135) so every
        node runs the same count and mesh collectives never desync;
        ``global_count`` overrides with an externally agreed count."""
        if global_count is not None:
            return global_count
        n = self.memory_data_size()
        local = n // self.batch_size
        if not self.drop_remainder and n % self.batch_size:
            local += 1
        if self.transport is not None and self.transport.n_ranks > 1:
            # cache key must be identical on every rank (pass + load
            # generation, both advanced in lockstep) — keying on the LOCAL
            # count would let one rank skip the collective another enters
            key = (self.pass_id, getattr(self, "_load_gen", 0))
            cached = getattr(self, "_nb_lockstep", None)
            if cached is not None and cached[0] == key:
                return cached[1]
            agreed = self.transport.allreduce_max(
                local, f"nb:{key[0]}:{key[1]}"
            )
            self._nb_lockstep = (key, agreed)
            return agreed
        return local

    def batch_indices(self, n_batches: Optional[int] = None) -> Iterator[np.ndarray]:
        """Store-record indices of each minibatch (the fast-path analog of
        ``batches()``): the pre-partitioned ``batch_offsets_`` of the
        reference (PrepareTrain, data_set.cc:2155-2192) with the shuffle
        order applied as a permutation. Wraps around past the tail so every
        rank serves the same count (lockstep parity)."""
        n = self.num_batches() if n_batches is None else n_batches
        B = self.batch_size
        N = self.memory_data_size()
        if N == 0:
            if n > 0:
                raise RuntimeError(
                    f"asked for {n} batches but this node holds 0 records "
                    "(check file striping / shuffle routing)"
                )
            return
        for i in range(n):
            idx = np.arange(i * B, (i + 1) * B, dtype=np.int64) % N
            yield self._order[idx] if self._order is not None else idx

    def batches(self, n_batches: Optional[int] = None) -> Iterator[SlotBatch]:
        """Yield equal-size SlotBatches; wraps around if asked for more than
        the pass holds (tail re-split parity: devices stay in lockstep)."""
        n = self.num_batches() if n_batches is None else n_batches
        if self.memory_data_size() == 0:
            if n > 0:
                # yielding fewer batches than asked would desync mesh
                # collectives across ranks — fail loudly instead
                raise RuntimeError(
                    f"asked for {n} batches but this node holds 0 records "
                    "(check file striping / shuffle routing)"
                )
            return
        B = self.batch_size
        recs = self.records
        for i in range(n):
            batch = [recs[(i * B + j) % len(recs)] for j in range(B)]
            yield build_batch(batch, self.schema)
