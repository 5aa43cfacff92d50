"""Open sparse table: host tiered store + pass-scoped device working set.

The reference links a closed ``libbox_ps.so`` whose observable surface is
BeginFeedPass/EndFeedPass/BeginPass/EndPass/PullSparseGPU/PushSparseGPU/
SaveBase/SaveDelta (box_wrapper.cc:580-1331). This module implements that
surface openly, re-shaped for TPU:

- ``HostSparseTable``: the full 1e9..1e11-key store, sharded by key hash
  across ``n_shards``. Native-backed (csrc/host_table.cc): the RAM tier is
  a C++ open-addressing store, and when constructed with ``spill_dir`` /
  ``mem_cap_rows`` cold rows are evicted to per-shard disk files and
  promoted lazily with catch-up decay — the mem/SSD tiers of BoxPS
  (LoadSSD2Mem, box_wrapper.cc:1325).

- ``PassWorkingSet``: the HBM tier. During load, every feasign of the pass is
  fed in (PSAgent::AddKeys parity, data_set.cc:1647); ``finalize`` dedups,
  pulls rows from the host store, and lays them out as a dense
  ``[n_mesh_shards, capacity, width]`` fp32 array to be placed in device HBM
  sharded over the mesh. Keys map to (mesh_shard, row) by hash, so the
  device-side pull/push is a static-shape gather/scatter and the multi-chip
  routing is a fixed all_to_all — the TPU-native analog of
  PullSparseGPU/PushSparseGPU.

- lookup: batch keys -> dense row ids happens host-side at pack time
  (vectorized searchsorted over the pass's sorted key table), so no hash
  tables ever live on device.

Each mesh shard reserves its last row as the padding row (zero, never written
back): batch padding and dropped-grad scatter both target it.
"""

from __future__ import annotations

import functools
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from paddlebox_tpu import config
from paddlebox_tpu.table.optimizers import SparseOptimizerConfig
from paddlebox_tpu.table.value_layout import ValueLayout
from paddlebox_tpu.utils.faultinject import InjectedFault, fire as _fault_fire
from paddlebox_tpu.utils.fs import atomic_write
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_OBSERVE, STAT_SET
from paddlebox_tpu.utils.trace import record_event

config.define_flag(
    "boundary_merge_threads", 4,
    "threads for the chunked pass-boundary key merge; <=1 falls back to "
    "the serial np.unique(np.concatenate(...))",
)
config.define_flag(
    "spill_policy", "freq",
    "victim selection for the RAM->disk cap sweep (maybe_spill): 'freq' "
    "ranks rows by coldness — lowest decayed show first, oldest "
    "last-touched epoch breaking ties — honoring spill_pin_show / "
    "spill_admit_show and balancing the sweep across shards; 'fifo' is "
    "the legacy creation-order sweep (untouched rows first), kept as the "
    "A/B baseline",
)
config.define_flag(
    "spill_pin_show", 0.0,
    "freq policy pin threshold: rows whose decayed show is >= this are "
    "never spilled while any colder victim exists in their shard "
    "(0 disables pinning)",
)
config.define_flag(
    "writeback_threads", 4,
    "writer-pool size for the end-of-pass host-table writeback "
    "(PassWorkingSet.writeback -> pbx_table_push_mt): each worker owns a "
    "disjoint set of shards, bitwise-equal to the serial path at every "
    "value; <=1 is the legacy serial ablation (plain table.push)",
)
config.define_flag(
    "writeback_chunk_keys", 2_000_000,
    "keys per writeback chunk: the trained rows are gathered and pushed "
    "chunk by chunk so the next chunk's gather overlaps the in-flight "
    "push, and a revert can cancel between chunks (rollback's "
    "partial-writeback contract covers whatever landed)",
)
config.define_flag(
    "spill_admit_show", 0.0,
    "freq policy admission threshold: at sweep time every row whose "
    "decayed show is under this is written disk-first instead of holding "
    "a RAM slot until pure cap pressure evicts it — pair with "
    "cache_threshold(rate) to target a resident fraction (0 disables "
    "admission)",
)

_HASH_MULT = np.uint64(0x9E3779B97F4A7C15)

# below this many total keys the serial merge wins (thread dispatch costs
# more than the merge itself)
_MERGE_SERIAL_FLOOR = 262_144


def merge_unique_keys(
    chunks: Sequence[np.ndarray], threads: int = 1
) -> np.ndarray:
    """Sorted-unique union of sorted-unique uint64 chunks.

    Bitwise-identical to ``np.unique(np.concatenate(chunks))`` (the tests
    assert this), but large merges run over deterministic key ranges in a
    thread pool: pivots are quantiles of a sorted strided sample of the
    chunks, every chunk is sliced at those pivots with searchsorted, each
    range unions its slices independently, and the per-range results
    concatenate back in ascending range order.

    A single non-empty chunk is returned AS-IS (no copy): the boundary
    prefetch's validity check is an O(1) identity test against the array a
    premerge() stored, and this fast path is what preserves that identity
    through finalize's re-merge of the singleton chunk list.
    """
    chunks = [c for c in chunks if len(c)]
    if not chunks:
        return np.zeros(0, dtype=np.uint64)
    if len(chunks) == 1:
        return chunks[0]
    total = sum(len(c) for c in chunks)
    threads = int(threads)
    if threads <= 1 or total < _MERGE_SERIAL_FLOOR:
        return np.unique(np.concatenate(chunks))
    n_ranges = min(threads, 16)
    sample = np.sort(
        np.concatenate([c[:: max(1, len(c) // 64)] for c in chunks])
    )
    pivots = sample[(np.arange(1, n_ranges) * len(sample)) // n_ranges]
    bounds = [np.searchsorted(c, pivots, side="left") for c in chunks]

    def _one_range(r: int) -> np.ndarray:
        parts = []
        for ci, c in enumerate(chunks):
            lo = int(bounds[ci][r - 1]) if r else 0
            hi = int(bounds[ci][r]) if r < n_ranges - 1 else len(c)
            if hi > lo:
                parts.append(c[lo:hi])
        if not parts:
            return np.zeros(0, dtype=np.uint64)
        return np.unique(np.concatenate(parts))

    with ThreadPoolExecutor(
        max_workers=n_ranges, thread_name_prefix="key-merge"
    ) as ex:
        ranges = [r for r in ex.map(_one_range, range(n_ranges)) if len(r)]
    if not ranges:
        return np.zeros(0, dtype=np.uint64)
    return np.concatenate(ranges)


# below this many query keys the numpy body is the only body and the call
# the program it always was. On a v5e host a native call costs 0.2 ms
# before its first key: 1,024 keys take numpy 0.14-0.66 ms (a 20k-key set,
# a 47.6M-key one) and the native body 0.20-0.29; at 4,096 it is ahead
# against both (0.24 against 0.44 ms, 0.80 against 6.4; PERF.md, PR 41)
_LOOKUP_NATIVE_FLOOR = 4_096


def _lookup_rows_numpy(
    sorted_keys: np.ndarray, row_of_sorted: np.ndarray, keys: np.ndarray
) -> np.ndarray:
    """The oracle: three numpy passes (search, proof, row read)."""
    pos = np.searchsorted(sorted_keys, keys.astype(np.uint64))
    pos = np.minimum(pos, len(sorted_keys) - 1)
    if not np.all(sorted_keys[pos] == keys):
        missing = keys[sorted_keys[pos] != keys]
        raise KeyError(
            f"{len(missing)} batch keys not in pass working set (e.g. {missing[:5]})"
        )
    return row_of_sorted[pos].astype(np.int32)


def lookup_rows(
    sorted_keys: np.ndarray, row_of_sorted: np.ndarray, keys: np.ndarray
) -> np.ndarray:
    """Batch keys -> row ids (int32) of a finalized pass working set.

    ``sorted_keys`` (sorted unique uint64) and ``row_of_sorted`` (int64) are
    the working set's; every key must be among them, else ``KeyError`` names
    how many are not and the first five in query order. The one body of
    ``PassWorkingSet.lookup`` and ``DistributedWorkingSet.lookup``.

    Bitwise-identical to :func:`_lookup_rows_numpy` (the tests assert this),
    but a call of ``_LOOKUP_NATIVE_FLOOR`` keys or more, where the native
    library loaded, is one threaded native pass (``pbx_lookup_rows``,
    csrc/host_table.cc): the search, the proof and the row read at one
    position, many searches' cache misses in flight at once. Which body ran
    is counted: ``table.lookup.native_keys`` / ``table.lookup.numpy_keys``,
    and ``table.lookup.threads`` is the last native call's pool.
    """
    if len(sorted_keys) == 0:
        if len(keys):
            raise KeyError(
                f"{len(keys)} batch keys but the pass working set is empty"
            )
        return np.zeros(0, np.int32)
    if len(keys) >= _LOOKUP_NATIVE_FLOOR:
        from paddlebox_tpu.utils import native

        if native.available():
            rows, n_missing, first, threads = native.lookup_rows(
                sorted_keys, row_of_sorted, keys
            )
            if n_missing:
                raise KeyError(
                    f"{n_missing} batch keys not in pass working set "
                    f"(e.g. {keys[first]})"
                )
            STAT_ADD("table.lookup.native_keys", len(keys))
            STAT_SET("table.lookup.threads", threads)
            return rows
    rows = _lookup_rows_numpy(sorted_keys, row_of_sorted, keys)
    STAT_ADD("table.lookup.numpy_keys", len(keys))
    return rows


@functools.lru_cache(maxsize=8)
def _sharded_zeros_fn(rows: int, width: int, sharding):
    """Compiled born-sharded zeros builder, cached by (shape, sharding) —
    jit caches by function identity, so a fresh lambda per pass boundary
    would re-trace+compile the allocation every boundary."""
    import jax
    import jax.numpy as jnp

    return jax.jit(
        lambda: jnp.zeros((rows, width), dtype=jnp.float32),
        out_shardings=sharding,
    )


def _sharded_zeros(rows: int, width: int, sharding):
    return _sharded_zeros_fn(rows, width, sharding)()


def key_to_shard(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Mesh/host shard of each key: multiplicative hash then modulo.

    Feasigns are already hashes in production, but cheap mixing keeps
    adversarial/test keys balanced too.
    """
    with np.errstate(over="ignore"):
        mixed = keys.astype(np.uint64) * _HASH_MULT
    return (mixed >> np.uint64(33)).astype(np.int64) % n_shards


class SpillIOError(IOError):
    """Typed disk-tier failure from the spill entry points.

    The native store returns -1 (tier disabled) / -2 (IO failure) from
    ``spill_cold`` / ``compact_spill``; before this type those codes could
    flow upward as plain ints and read as "spilled -2 rows". Carries the
    failing op and raw code; every raise is counted under the
    ``table.spill_errors`` stat.
    """

    def __init__(self, op: str, rc: int, detail: str = ""):
        msg = f"spill tier {op} failed rc={rc}"
        if detail:
            msg = f"{msg} ({detail})"
        super().__init__(msg)
        self.op = op
        self.rc = rc


class WritebackCancelled(RuntimeError):
    """A chunked writeback was cancelled at a chunk boundary (revert path).

    Not an error: the chunks already pushed are exactly the partial
    writeback rollback's PassGuard contract covers ("safe after zero,
    partial, or full writeback"), so the canceller reverts and retries.
    Carries how far the writeback got for the revert log."""

    def __init__(self, done_keys: int, total_keys: int):
        super().__init__(
            f"writeback cancelled at chunk boundary "
            f"({done_keys}/{total_keys} keys pushed)"
        )
        self.done_keys = done_keys
        self.total_keys = total_keys


# flag value -> native policy code (csrc/host_table.cc kSpillFifo/kSpillFreq)
_SPILL_POLICY_CODES = {"fifo": 0, "freq": 1}


class _Shard:
    """One lock-protected hash shard of the host store."""

    __slots__ = ("index", "values", "lock", "touched", "width")

    def __init__(self, width: int):
        self.index: Dict[int, int] = {}
        self.values = np.zeros((0, width), dtype=np.float32)
        self.lock = threading.Lock()
        self.touched: set = set()
        self.width = width

    def _grow(self, need: int) -> None:
        cap = len(self.values)
        if need <= cap:
            return
        new_cap = max(1024, cap * 2, need)
        nv = np.zeros((new_cap, self.width), dtype=np.float32)
        nv[:cap] = self.values
        self.values = nv


class HostSparseTable:
    """Host sharded key -> fp32 row store: the mem + disk tiers of BoxPS.

    Backed by the native C++ store (csrc/host_table.cc) when the toolchain
    is available: batch pull/push run with the GIL released and thread
    across shards, and cold rows spill to per-shard disk files under
    ``spill_dir`` with lazy promotion + catch-up decay (``LoadSSD2Mem``
    parity, box_wrapper.cc:1325). Falls back to a pure-Python dict store
    (no spill) when g++ is unavailable or ``PBOX_NATIVE_TABLE=0``.

    ``mem_cap_rows`` bounds the RAM tier: ``maybe_spill()`` (called by the
    dataset at pass end) evicts cold rows to disk until under the cap.
    """

    def __init__(
        self,
        layout: ValueLayout,
        opt: SparseOptimizerConfig = SparseOptimizerConfig(),
        n_shards: Optional[int] = None,
        seed: int = 0,
        spill_dir: Optional[str] = None,
        mem_cap_rows: Optional[int] = None,
    ):
        if n_shards is None:
            # flag default (6 bits) keeps the historical 64-shard layout
            n_shards = 1 << config.get_flag("sparse_table_shard_bits")
        self.layout = layout
        self.opt = opt
        self.n_shards = n_shards
        self.mem_cap_rows = mem_cap_rows
        self._native = None
        if os.environ.get("PBOX_NATIVE_TABLE", "1") != "0":
            try:
                from paddlebox_tpu.utils import native as _native_mod

                if _native_mod.available():
                    lay = layout
                    n_emb = lay.embedx_dim + lay.expand_dim
                    init_cols = np.concatenate(
                        [
                            [lay.embed_w_col],
                            np.arange(lay.embedx_col, lay.embedx_col + n_emb),
                        ]
                    ).astype(np.int32)
                    if spill_dir:
                        os.makedirs(spill_dir, exist_ok=True)
                    self._native = _native_mod.NativeHostStore(
                        n_shards, lay.width, lay.SHOW, lay.CLK, seed,
                        init_cols, opt.initial_range, spill_dir,
                    )
            except Exception:
                # silent fallback to the Python store loses native batch
                # pull/push AND the disk tier — a box training 10x slower
                # with no signal is the worst failure mode this init has
                STAT_ADD("table.native_init_failures")
                self._native = None
        if self._native is None and spill_dir is not None:
            raise RuntimeError(
                "disk spill requires the native table store "
                "(g++ build failed or PBOX_NATIVE_TABLE=0)"
            )
        self._shards = (
            [] if self._native else [_Shard(layout.width) for _ in range(n_shards)]
        )
        self._rng = np.random.default_rng(seed)
        self._size = 0
        self._size_lock = threading.Lock()
        # device-carried pass tables owing this store a writeback (see
        # table/carrier.py); every durable read path drains them first.
        # _maintenance_lock orders carrier flushes against decay_and_shrink
        # so a carried row's show/clk decay is applied exactly once per
        # boundary no matter when a save drains.
        self._pending_carriers: List = []
        self._maintenance_lock = threading.Lock()
        # pass-boundary decay counter, stamped into every save's meta: a
        # key untouched since its last save still DECAYS at later
        # boundaries, so a resume must catch those rows up (load applies
        # rate**(file_epoch - table_epoch) to existing rows before each
        # delta lands) — else resumed counters run high and everything
        # show-gated (embedx unlock, shrink, cache thresholds) drifts
        self.decay_epochs = 0

    def add_pending_carrier(self, carrier) -> None:
        """Register a TableCarrier whose values the host store is owed."""
        with self._maintenance_lock:
            self._pending_carriers = [
                c for c in self._pending_carriers if not c.flushed
            ]
            self._pending_carriers.append(carrier)

    def drain_pending(self) -> int:
        """Flush every registered carrier (idempotent); returns keys written.

        Called by save/export paths so durable artifacts always include
        device-carried training. A flush that raises must NOT drop the
        failed (or the not-yet-reached) carriers from the registry —
        otherwise a later save_base/save_delta would silently write a
        checkpoint missing device-carried training."""
        with self._maintenance_lock:
            carriers, self._pending_carriers = self._pending_carriers, []
            n = 0
            try:
                while carriers:
                    c = carriers[0]
                    n += c.flush(self)
                    carriers.pop(0)
            finally:
                if carriers:  # failed + unflushed: keep them owed
                    self._pending_carriers = carriers + self._pending_carriers
        return n

    @property
    def native(self) -> bool:
        return self._native is not None

    @property
    def mem_rows(self) -> int:
        return self._native.mem_rows if self._native else self._size

    @property
    def disk_rows(self) -> int:
        return self._native.disk_rows if self._native else 0

    def spill_cold(self, max_mem_rows: int) -> int:
        """Evict cold rows to disk until RAM tier <= max_mem_rows.

        Victim selection follows the ``spill_policy`` flag: ``freq`` ranks
        by coldness (lowest decayed show, then oldest last-touched epoch)
        with the ``spill_pin_show`` / ``spill_admit_show`` thresholds
        active; ``fifo`` is the legacy creation-order sweep. Raises
        :class:`SpillIOError` (counted under ``table.spill_errors``) when
        the disk tier is disabled or a shard file write fails.
        """
        if self._native is None:
            raise RuntimeError("spill requires the native table store")
        policy = str(config.get_flag("spill_policy"))
        code = _SPILL_POLICY_CODES.get(policy)
        if code is None:
            raise ValueError(
                f"unknown spill_policy {policy!r} (expected 'freq' or 'fifo')"
            )
        try:
            _fault_fire("spill.io")
        except InjectedFault as e:
            STAT_ADD("table.spill_errors", 1)
            raise SpillIOError("spill_cold", -2, str(e)) from e
        # separate site for the double-buffered stage writer: an injected
        # failure here models the staged fwrite handoff dying mid-sweep
        # (native rc -2 from the flusher thread) without shifting spill.io
        # hit counts for plans armed against the sweep entry itself
        try:
            _fault_fire("spill.stage_flush")
        except InjectedFault as e:
            STAT_ADD("table.spill_errors", 1)
            raise SpillIOError("stage_flush", -2, str(e)) from e
        n = self._native.spill_cold(
            max_mem_rows,
            policy=code,
            pin_show=float(config.get_flag("spill_pin_show")),
            admit_show=float(config.get_flag("spill_admit_show")),
        )
        if n < 0:
            STAT_ADD("table.spill_errors", 1)
            raise SpillIOError(
                "spill_cold", n,
                "disk tier disabled (no spill_dir)" if n == -1
                else "shard spill-file write failed",
            )
        return n

    def maybe_spill(self) -> int:
        """Enforce ``mem_cap_rows`` if configured (pass-end hook)."""
        if self.mem_cap_rows is None or self._native is None:
            return 0
        return self.spill_cold(self.mem_cap_rows)

    def compact_spill(self) -> int:
        """Reclaim dead spill-file space (records superseded by promotes).

        spill_cold compacts a shard automatically once dead records
        outnumber live ones; this forces it everywhere — call at day
        boundaries. Returns live records kept; raises SpillIOError on a
        shard rewrite failure (the failed shard keeps its old file)."""
        if self._native is None:
            return 0
        n = self._native.compact_spill()
        if n == -1:  # tier disabled: nothing to reclaim
            return 0
        if n < 0:
            STAT_ADD("table.spill_errors", 1)
            raise SpillIOError("compact_spill", n, "shard rewrite failed")
        return n

    def spill_stats(self) -> tuple:
        """(live_records, dead_records, file_bytes) of the disk tier."""
        if self._native is None:
            return (0, 0, 0)
        return self._native.spill_stats()

    def tier_stats(self) -> dict:
        """Tiered-store occupancy + cumulative flow counters.

        Totals over all shards for each field of
        ``native.TIER_STAT_FIELDS`` (mem_rows, disk_rows, spilled_total,
        promoted_total, admitted_disk_first, lazy_shrunk, dead_records,
        spill_bytes), the per-shard maxima of the two occupancy columns
        (skew telltales), and the full per-shard vectors under
        ``"per_shard"``. The Python fallback reports mem occupancy only.
        """
        from paddlebox_tpu.utils.native import TIER_STAT_FIELDS

        if self._native is not None:
            per = self._native.tier_stats()
        else:
            per = np.zeros((self.n_shards, len(TIER_STAT_FIELDS)), np.int64)
            for i, sh in enumerate(self._shards):
                with sh.lock:
                    per[i, 0] = len(sh.index)
        out = {f: int(per[:, i].sum()) for i, f in enumerate(TIER_STAT_FIELDS)}
        out["mem_rows_max_shard"] = int(per[:, 0].max()) if len(per) else 0
        out["disk_rows_max_shard"] = int(per[:, 1].max()) if len(per) else 0
        out["per_shard"] = {
            f: per[:, i].tolist() for i, f in enumerate(TIER_STAT_FIELDS)
        }
        return out

    def publish_tier_stats(self) -> dict:
        """Export :meth:`tier_stats` totals as ``table.tier.*`` STAT gauges
        (per-shard vectors stay in the returned dict — stat names must be
        literals, so shard-indexed gauges are out by design)."""
        st = self.tier_stats()
        STAT_SET("table.tier.mem_rows", st["mem_rows"])
        STAT_SET("table.tier.disk_rows", st["disk_rows"])
        STAT_SET("table.tier.spilled_total", st["spilled_total"])
        STAT_SET("table.tier.promoted_total", st["promoted_total"])
        STAT_SET("table.tier.admitted_disk_first", st["admitted_disk_first"])
        STAT_SET("table.tier.lazy_shrunk", st["lazy_shrunk"])
        STAT_SET("table.tier.dead_records", st["dead_records"])
        STAT_SET("table.tier.spill_bytes", st["spill_bytes"])
        STAT_SET("table.tier.mem_rows_max_shard", st["mem_rows_max_shard"])
        STAT_SET("table.tier.disk_rows_max_shard", st["disk_rows_max_shard"])
        if self._native is not None:
            # where the writeback/spill IO time went: the gather-vs-fwrite
            # split of the double-buffered stage writers plus the push
            # pre-pass header reads (cumulative, from the native tier)
            io = self._native.io_stats()
            STAT_SET("table.writeback.spill_gather_s",
                     io["spill_gather_ns"] / 1e9)
            STAT_SET("table.writeback.spill_fwrite_s",
                     io["spill_fwrite_ns"] / 1e9)
            STAT_SET("table.writeback.prepass_read_s",
                     io["prepass_read_ns"] / 1e9)
            STAT_SET("table.writeback.stage_flushes", io["stage_flushes"])
            STAT_SET("table.writeback.stage_bytes", io["stage_bytes"])
        return st

    def __len__(self) -> int:
        if self._native is not None:
            return len(self._native)
        return self._size

    def keys(self) -> np.ndarray:
        """All keys currently stored (mem + disk tiers), unsorted.
        Keys-only exports on both backends: no value-matrix copies, no
        disk reads."""
        if self._native is not None:
            parts = [self._native.shard_keys(s) for s in range(self.n_shards)]
        else:
            parts = []
            for sh in self._shards:
                with sh.lock:
                    parts.append(
                        np.fromiter(
                            sh.index.keys(), dtype=np.uint64, count=len(sh.index)
                        )
                    )
        return np.concatenate(parts) if parts else np.zeros(0, np.uint64)

    def _init_rows(self, n: int) -> np.ndarray:
        lay = self.layout
        rows = np.zeros((n, lay.width), dtype=np.float32)
        r = self.opt.initial_range
        rows[:, lay.embed_w_col] = self._rng.uniform(-r, r, size=n)
        n_emb = lay.embedx_dim + lay.expand_dim  # expand block trails embedx
        rows[:, lay.embedx_col : lay.embedx_col + n_emb] = self._rng.uniform(
            -r, r, size=(n, n_emb)
        )
        return rows

    def pull_or_create(self, keys: np.ndarray) -> np.ndarray:
        """Rows for unique ``keys`` (creating missing ones). [n, width]."""
        if self._native is not None:
            return self._native.pull_or_create(keys)
        out = np.empty((len(keys), self.layout.width), dtype=np.float32)
        shard_ids = key_to_shard(keys, self.n_shards)
        created = 0
        for s in range(self.n_shards):
            sel = np.nonzero(shard_ids == s)[0]
            if len(sel) == 0:
                continue
            shard = self._shards[s]
            with shard.lock:
                idx = shard.index
                # pure-Python fallback path (native store unavailable):
                # .tolist() converts uint64->int in C so dict lookups stay
                # as cheap as the interpreter allows
                klist = keys[sel].tolist()
                get = idx.get
                rows = np.fromiter(
                    (get(k, -1) for k in klist), dtype=np.int64, count=len(klist)
                )
                miss = np.nonzero(rows < 0)[0]
                if len(miss):
                    base = len(idx)
                    shard._grow(base + len(miss))
                    init = self._init_rows(len(miss))
                    new_rows = base + np.arange(len(miss))
                    for mj, j in zip(new_rows, miss):
                        idx[klist[j]] = int(mj)
                    shard.values[new_rows] = init
                    rows[miss] = new_rows
                    created += len(miss)
                out[sel] = shard.values[rows]
        if created:
            with self._size_lock:
                self._size += created
        return out

    def shows_peek(self, keys: np.ndarray) -> np.ndarray:
        """Decayed show counts for ``keys`` without creating, promoting or
        touching anything. f32 [n]; keys on the disk tier or absent read 0.

        This is the hotness source of the adaptive ICI wire (a key is hot
        when its decayed show clears ``ici_hot_show``): a pure mem-tier
        peek, because spill policy only evicts cold rows — a hot key that
        somehow sits on disk just rides int8 until its next pull, which is
        the graceful-degrade contract anyway. Keeping the read side-effect
        free means the wire heuristic can never perturb tier state."""
        if self._native is not None:
            return self._native.shows_peek(keys)
        out = np.zeros(len(keys), dtype=np.float32)
        shard_ids = key_to_shard(keys, self.n_shards)
        show_col = self.layout.SHOW
        for s in range(self.n_shards):
            sel = np.nonzero(shard_ids == s)[0]
            if len(sel) == 0:
                continue
            shard = self._shards[s]
            with shard.lock:
                get = shard.index.get
                klist = keys[sel].tolist()
                rows = np.fromiter(
                    (get(k, -1) for k in klist), dtype=np.int64, count=len(klist)
                )
                hit = rows >= 0
                if hit.any():
                    out[sel[hit]] = shard.values[rows[hit], show_col]
        return out

    def prefetch_rows(self, keys: np.ndarray) -> Tuple[np.ndarray, int]:
        """Pull/create rows for a STAGED next pass; returns (rows, epoch).

        Held under the maintenance lock so the row snapshot and the decay
        epoch stamp agree — no concurrent ``decay_and_shrink`` (an
        overlapped end_pass worker's) or carrier drain can land between
        the pull and the stamp. The boundary consumer then compensates
        exactly ``decay_epochs - epoch`` decays onto the prefetched rows;
        rows created here have show=clk=0, so the extra decays are bitwise
        no-ops on them.
        """
        with self._maintenance_lock:
            return self.pull_or_create(keys), self.decay_epochs

    def push(self, keys: np.ndarray, rows: np.ndarray) -> None:
        """Write back full rows for existing keys (end-of-pass flush)."""
        if self._native is not None:
            self._native.push(keys, rows)
            return
        shard_ids = key_to_shard(keys, self.n_shards)
        created = 0
        for s in range(self.n_shards):
            sel = np.nonzero(shard_ids == s)[0]
            if len(sel) == 0:
                continue
            shard = self._shards[s]
            t_shard = time.perf_counter()
            with shard.lock:
                idx = shard.index
                klist = keys[sel].tolist()
                get = idx.get
                trows = np.fromiter(
                    (get(k, -1) for k in klist), dtype=np.int64, count=len(klist)
                )
                miss = np.nonzero(trows < 0)[0]
                if len(miss):
                    base = len(idx)
                    shard._grow(base + len(miss))
                    new_rows = base + np.arange(len(miss))
                    for mj, j in zip(new_rows, miss):
                        idx[klist[j]] = int(mj)
                    trows[miss] = new_rows
                    created += len(miss)
                shard.values[trows] = rows[sel]
                shard.touched.update(klist)
            # per-shard writeback time distribution: skew across shards
            # is the writeback wall the ROADMAP finalize item chases
            STAT_OBSERVE(
                "table.push_shard_s", time.perf_counter() - t_shard
            )
        if created:
            with self._size_lock:
                self._size += created

    def push_writeback(self, keys: np.ndarray, rows: np.ndarray,
                       threads: int) -> None:
        """One writer-pool chunk of the end-of-pass writeback.

        Routes through ``pbx_table_push_mt`` (bitwise-equal to ``push`` at
        every thread count) and feeds the per-shard wall seconds into the
        ``table.writeback.shard_s`` histogram. Fires the
        ``table.writeback_worker`` fault site; any failure — injected or a
        real worker rc — surfaces as the typed :class:`SpillIOError`,
        counted under ``table.spill_errors``.
        """
        try:
            _fault_fire("table.writeback_worker")
        except InjectedFault as e:
            STAT_ADD("table.spill_errors", 1)
            raise SpillIOError("writeback_worker", -2, str(e)) from e
        if self._native is None:
            self.push(keys, rows)
            return
        try:
            shard_s = self._native.push_mt(keys, rows, threads)
        except SpillIOError:
            raise
        except IOError as e:
            STAT_ADD("table.spill_errors", 1)
            raise SpillIOError("writeback_push", -2, str(e)) from e
        for v in shard_s:
            STAT_OBSERVE("table.writeback.shard_s", float(v))

    def decay_and_shrink(self) -> int:
        """Pass-boundary maintenance: decay show/clk, drop cold keys.

        Returns number of keys dropped. (pslib show_click_decay_rate + shrink
        threshold semantics; reference surfaces this as table shrink,
        fleet_wrapper.h:258-310.)

        Pending device-carried tables (whose rows this decay cannot reach)
        get the boundary's decay NOTED instead — they apply it at
        splice/flush time. Held under the maintenance lock so a concurrent
        drain either lands fully before (then its pushed rows decay here,
        classic push-then-decay order) or fully after (then the flush
        carries the noted decay) — never half."""
        with self._maintenance_lock:
            live = [c for c in self._pending_carriers if not c.flushed]
            for c in live:
                c.note_decay(self.opt.show_clk_decay)
            self._pending_carriers = live
            self.decay_epochs += 1
            return self._decay_and_shrink_locked()

    def _decay_and_shrink_locked(
        self, decay: Optional[float] = None, threshold: Optional[float] = None
    ) -> int:
        lay, opt = self.layout, self.opt
        decay = opt.show_clk_decay if decay is None else decay
        threshold = opt.shrink_threshold if threshold is None else threshold
        if self._native is not None:
            return self._native.decay_and_shrink(decay, threshold)
        dropped = 0
        for shard in self._shards:
            with shard.lock:
                n = len(shard.index)
                if n == 0:
                    continue
                vals = shard.values[:n]
                vals[:, lay.SHOW] *= decay
                vals[:, lay.CLK] *= decay
                keep = vals[:, lay.SHOW] >= threshold
                if keep.all():
                    continue
                keys_arr = np.empty(n, dtype=np.uint64)
                rows_arr = np.empty(n, dtype=np.int64)
                for i, (k, r) in enumerate(shard.index.items()):
                    keys_arr[i] = k
                    rows_arr[i] = r
                order = np.argsort(rows_arr)
                keys_arr, rows_arr = keys_arr[order], rows_arr[order]
                kept = keep[rows_arr]
                new_vals = vals[rows_arr[kept]]
                dropped += int((~kept).sum())
                shard.index = {int(k): i for i, k in enumerate(keys_arr[kept])}
                shard.values = np.zeros(
                    (max(1024, len(shard.index)), lay.width), dtype=np.float32
                )
                shard.values[: len(shard.index)] = new_vals
        with self._size_lock:
            self._size -= dropped
        return dropped

    # --- persistence: base + delta model publishing (SaveBase/SaveDelta parity,
    # box_wrapper.cc:1288-1331) ---

    def _snapshot_shard(self, s: int, only_touched: bool, clear_touched: bool = True):
        """Atomically snapshot (keys, values) of a shard and clear touched.

        The snapshot+clear happens under the shard lock so a concurrent
        push() either lands in this snapshot or stays marked touched for the
        next delta — no update can fall between and be lost.
        ``clear_touched=False`` gives a read-only peek (cache/whitelist/
        keys() exports).
        """
        if self._native is not None:
            return self._native.snapshot_shard(s, only_touched, clear_touched)
        shard = self._shards[s]
        with shard.lock:
            if only_touched:
                items = [(k, shard.index[k]) for k in shard.touched if k in shard.index]
            else:
                items = list(shard.index.items())
            keys = np.array([k for k, _ in items], dtype=np.uint64)
            vals = (
                shard.values[[r for _, r in items]]
                if items
                else np.zeros((0, self.layout.width), dtype=np.float32)
            )
            if clear_touched:
                shard.touched.clear()
        return keys, vals

    def save_base(self, path: str) -> None:
        self.drain_pending()
        os.makedirs(path, exist_ok=True)
        # the epoch stamp and the row snapshots must agree: hold the
        # maintenance lock across stamp + snapshots so an overlapped
        # end_pass_async worker's decay_and_shrink lands entirely before
        # or after this save. Compression/IO happens OUTSIDE the lock —
        # a minutes-long compressed write must not stall pass-boundary
        # maintenance (the transient snapshot copy is the price).
        with self._maintenance_lock:
            meta = {
                "n_shards": self.n_shards,
                "width": self.layout.width,
                "embedx_dim": self.layout.embedx_dim,
                "kind": "base",
                "decay_epoch": self.decay_epochs,
            }
            snaps = [
                self._snapshot_shard(s, only_touched=False)
                for s in range(self.n_shards)
            ]
        with atomic_write(os.path.join(path, "meta.json")) as f:
            json.dump(meta, f)
        for s, (keys, vals) in enumerate(snaps):
            np.savez_compressed(
                os.path.join(path, f"shard-{s:05d}.npz"),
                keys=keys, values=vals,
            )

    def save_delta(self, path: str, clear_touched: bool = True) -> int:
        """Write only keys touched since the last save; returns count.

        ``clear_touched=False`` keeps the touched set intact so the caller
        can defer the clear (via :meth:`clear_touched`) until the written
        delta is durable — a crashed-and-retried save then re-snapshots the
        same keys instead of publishing an empty delta.
        """
        self.drain_pending()
        os.makedirs(path, exist_ok=True)
        total = 0
        with self._maintenance_lock:  # stamp/snapshot atomicity (see save_base)
            epoch = self.decay_epochs
            snaps = [
                self._snapshot_shard(s, only_touched=True,
                                     clear_touched=clear_touched)
                for s in range(self.n_shards)
            ]
        for s, (keys, vals) in enumerate(snaps):
            total += len(keys)
            np.savez_compressed(
                os.path.join(path, f"shard-{s:05d}.npz"),
                keys=keys, values=vals,
            )
        with atomic_write(os.path.join(path, "meta.json")) as f:
            json.dump(
                {
                    "n_shards": self.n_shards,
                    "kind": "delta",
                    "decay_epoch": epoch,
                },
                f,
            )
        return total

    def clear_touched(self) -> None:
        """Drop the touched-keys set on every shard.

        Pairs with ``save_delta(..., clear_touched=False)``: the checkpoint
        layer snapshots without clearing, publishes durably, commits the
        cursor, and only THEN clears — so a crash anywhere inside the save
        leaves the touched set armed for the retry. Call only at a
        quiescent point (no concurrent pushes), or updates between the
        snapshot and this clear would drop out of the next delta.
        """
        if self._native is not None:
            self._native.clear_touched()
            return
        for shard in self._shards:
            with shard.lock:
                shard.touched.clear()

    def cache_threshold(self, cache_rate: float = 0.1) -> float:
        """Show-count threshold whose admitted fraction is CLOSEST to
        ``cache_rate`` (get_cache_threshold parity, pslib __init__.py:411).

        Computed over the exact show distribution, so heavy ties (many
        cold keys sharing tiny counts) can't silently blow the cache up to
        the whole table — the closest achievable fraction wins. The native
        store exports only the show column per shard; the Python fallback
        reads one column from its shard arrays."""
        if not 0.0 < cache_rate <= 1.0:
            raise ValueError(f"cache_rate must be in (0, 1], got {cache_rate}")
        shows = []
        for s in range(self.n_shards):
            if self._native is not None:
                col = self._native.shard_shows(s)
            else:
                shard = self._shards[s]
                with shard.lock:
                    col = shard.values[: len(shard.index), self.layout.SHOW].copy()
            if len(col):
                shows.append(col)
        if not shows:
            return 0.0
        allshow = np.concatenate(shows)
        uniq, counts = np.unique(allshow, return_counts=True)  # ascending
        admitted = np.cumsum(counts[::-1])[::-1] / len(allshow)  # frac >= uniq[i]
        return float(uniq[int(np.argmin(np.abs(admitted - cache_rate)))])

    def _filtered_save(self, path: str, mask_fn, meta: dict) -> int:
        """Shared filtered snapshot-to-dir writer (cache/whitelist saves).
        Stamp + snapshots are atomic under the maintenance lock (same
        discipline as save_base); filtering/compression run outside it."""
        self.drain_pending()
        os.makedirs(path, exist_ok=True)
        with self._maintenance_lock:
            meta = {**meta, "decay_epoch": self.decay_epochs}
            snaps = [
                self._snapshot_shard(s, only_touched=False, clear_touched=False)
                for s in range(self.n_shards)
            ]
        total = 0
        for s, (keys, vals) in enumerate(snaps):
            keep = mask_fn(keys, vals)
            keys, vals = keys[keep], vals[keep]
            total += len(keys)
            np.savez_compressed(
                os.path.join(path, f"shard-{s:05d}.npz"), keys=keys, values=vals
            )
        with atomic_write(os.path.join(path, "meta.json")) as f:
            json.dump({"n_shards": self.n_shards, **meta}, f)
        return total

    def save_cache(self, path: str, threshold: float) -> int:
        """Write the hot subset (show >= threshold) for serving
        (cache_shuffle/save_cache_model parity, pslib __init__.py:416).
        Like the reference (which brackets threshold+shuffle in worker
        barriers), quiesce pushes across threshold+save for an exact cut.
        Same dir format as base/delta; returns the feasign count."""
        return self._filtered_save(
            path,
            lambda keys, vals: vals[:, self.layout.SHOW] >= threshold,
            {"kind": "cache", "threshold": threshold},
        )

    def save_with_whitelist(self, path: str, whitelist: np.ndarray) -> int:
        """Write only the whitelisted keys that exist in the table
        (save_model_with_whitelist parity, pslib __init__.py:351-384)."""
        wl = np.unique(np.asarray(whitelist, dtype=np.uint64))
        return self._filtered_save(
            path, lambda keys, vals: np.isin(keys, wl), {"kind": "whitelist"}
        )

    def load(self, path: str) -> None:
        """Load a base dir, then optionally apply deltas via ``apply_delta``.

        Epoch catch-up: each file is stamped with the table's decay epoch
        at save time; when a file from a LATER epoch lands, the rows
        already in the table first receive the decays they lived through
        (``rate**(file_epoch - table_epoch)``) — exactly the history a key
        untouched since an earlier save experienced. Files without the
        stamp (older checkpoints) load as before."""
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        if meta["n_shards"] != self.n_shards:
            raise ValueError("shard count mismatch on load")
        file_epoch = int(meta.get("decay_epoch", self.decay_epochs))
        if meta.get("kind", "base") == "base":
            # a base load STARTS a lineage: epochs are only comparable
            # within one save lineage, so the table adopts the base's stamp
            # outright (catching 'up' across unrelated lineages would
            # crush or inflate counters arbitrarily)
            self.decay_epochs = file_epoch
        elif file_epoch > self.decay_epochs:
            if len(self):
                d = float(self.opt.show_clk_decay) ** (
                    file_epoch - self.decay_epochs
                )
                if d < 1.0:
                    # threshold 0: pure decay, no drops. (The native spill
                    # tier's per-record catch-up uses the last rate seen; a
                    # load into a table with live spill files is atypical.)
                    with self._maintenance_lock:
                        self._decay_and_shrink_locked(d, 0.0)
            self.decay_epochs = file_epoch
        for s in range(self.n_shards):
            data = np.load(os.path.join(path, f"shard-{s:05d}.npz"))
            keys, vals = data["keys"], data["values"]
            if len(keys):
                self.push(keys, vals)
            if self._native is None:
                self._shards[s].touched.clear()
        if self._native is not None:
            self._native.clear_touched()

    apply_delta = load  # a delta dir has the same format; push() upserts


def _rows_with_prefetch(
    table: HostSparseTable, keys: np.ndarray, prefetch
) -> np.ndarray:
    """Host rows for sorted unique ``keys``, serving staged-prefetch hits
    and pulling only the remainder.

    Prefetched rows receive the decays the host applied since the staged
    pull (``decay_epochs - epoch`` of them). Bitwise-equal to a fresh
    ``pull_or_create``: rows the prefetch CREATED have show=clk=0 so the
    catch-up decays are no-ops, and rows that already existed are — by the
    feed stage's exclusion of the live pass's keys — untouched by any
    writeback between the staged pull and now.
    """
    if prefetch is None:
        return table.pull_or_create(keys)
    pf_keys, pf_rows = prefetch["keys"], prefetch["rows"]
    lay = table.layout
    out = np.empty((len(keys), lay.width), dtype=np.float32)
    if len(pf_keys):
        pos = np.searchsorted(pf_keys, keys)
        pos = np.minimum(pos, len(pf_keys) - 1)
        hit = pf_keys[pos] == keys
    else:
        hit = np.zeros(len(keys), dtype=bool)
    if hit.any():
        rows = pf_rows[pos[hit]]  # fancy index: a fresh copy, safe to mutate
        d = table.decay_epochs - prefetch["epoch"]
        if d > 0:
            dec = np.float32(table.opt.show_clk_decay)
            for _ in range(d):
                rows[:, lay.SHOW] *= dec
                rows[:, lay.CLK] *= dec
        out[hit] = rows
    miss = ~hit
    if miss.any():
        out[miss] = table.pull_or_create(keys[miss])
    return out


class PassWorkingSet:
    """The HBM tier: dense pass-local table built from the pass's unique keys.

    Life cycle (BeginFeedPass .. EndPass parity):
      add_keys (during load, many threads) -> finalize() -> device array up
      -> train steps gather/scatter rows -> writeback(updated_array) -> host.
    """

    def __init__(self, n_mesh_shards: int = 1):
        self.n_mesh_shards = n_mesh_shards
        self._key_chunks: List[np.ndarray] = []
        self._lock = threading.Lock()
        self._finalized = False
        # set by finalize():
        self.sorted_keys: Optional[np.ndarray] = None  # uint64 [n]
        self.row_of_sorted: Optional[np.ndarray] = None  # int64 [n] global rows
        self.capacity = 0  # rows per mesh shard (incl. padding row)
        self.n_keys = 0
        # bool [n_mesh_shards*capacity] hotness bits for the adaptive ICI
        # wire (None = adaptive off/ablated: the packer keeps the uniform
        # slot order bitwise). Set by finalize() when the wire is engaged.
        self.hot_rows: Optional[np.ndarray] = None

    def add_keys(self, keys: np.ndarray) -> None:
        """Feed feasigns seen in loaded records (PSAgent::AddKeys parity)."""
        if self._finalized:
            raise RuntimeError("working set already finalized")
        if len(keys):
            with self._lock:
                self._key_chunks.append(np.unique(keys.astype(np.uint64)))

    def premerge(self, threads: int = 1) -> np.ndarray:
        """Collapse the accumulated key chunks to the merged array NOW.

        The boundary feed stage calls this while the PREVIOUS pass trains,
        so finalize() later re-merges a singleton chunk list through the
        no-copy fast path of :func:`merge_unique_keys` — the object
        returned here is the SAME object finalize sees, which is what lets
        a staged host prefetch validate itself with an O(1) identity test.
        ``add_keys`` after premerge still works (the merged array becomes
        one chunk among others) but voids that identity, so a stale
        prefetch is dropped rather than consumed.
        """
        if self._finalized:
            raise RuntimeError("working set already finalized")
        with self._lock:
            merged = merge_unique_keys(self._key_chunks, threads)
            self._key_chunks = [merged] if len(merged) else []
        return merged

    def finalize(
        self, table: HostSparseTable, round_to: int = 512, carrier=None,
        prefetch=None,
    ) -> np.ndarray:
        """Dedup keys, pull host rows, lay out [n_mesh_shards, cap, width].

        The returned array is what gets device_put with a mesh sharding on
        axis 0. Row (s, cap-1) of every shard is the reserved padding row.

        With ``carrier`` (the previous pass's TableCarrier), the boundary
        goes delta-only: keys present in both passes splice device-to-device
        from the carried trained table (one decay applied on device), keys
        that left the stream are fetched and pushed to the host store (D2H
        of the departing slice only), and only NEW keys pull host rows and
        upload. Returns a jax array in that case. The reference keeps its
        HBM cache warm across passes the same way (EndPass
        box_wrapper.cc:627-651).

        ``prefetch`` is the staged host-pull dict built by the dataset's
        boundary feed stage ({src, keys, rows, epoch}); it is consumed
        only if its ``src`` is the very array this finalize merges
        (identity check), else silently dropped."""
        with self._lock, record_event("boundary.dedup", "boundary") as span:
            all_keys = merge_unique_keys(
                self._key_chunks,
                int(config.get_flag("boundary_merge_threads")),
            )
            self._key_chunks = []
        STAT_SET("boundary.dedup_s", span.seconds)
        if prefetch is not None and prefetch.get("src") is not all_keys:
            prefetch = None  # keys landed after the staged premerge: stale
        self.n_keys = len(all_keys)
        ns = self.n_mesh_shards
        shard_ids = key_to_shard(all_keys, ns)
        counts = np.bincount(shard_ids, minlength=ns)
        # +1 reserves the padding row; round for stable compiled shapes
        cap = int(counts.max()) + 1 if len(all_keys) else 1
        cap = -(-cap // round_to) * round_to
        self.capacity = cap

        # stable order: group by shard, rank within shard — vectorized
        # (rank of key i = position of i within its shard's sorted group)
        order = np.argsort(shard_ids, kind="stable")
        rank_in_shard = np.empty(len(all_keys), dtype=np.int64)
        starts = np.repeat(np.cumsum(counts) - counts, counts)
        rank_in_shard[order] = np.arange(len(all_keys), dtype=np.int64) - starts
        global_rows = shard_ids * cap + rank_in_shard

        self.sorted_keys = all_keys  # np.unique output is sorted
        self.row_of_sorted = global_rows
        self._finalized = True
        self._table = table

        if carrier is not None and not carrier.flushed and carrier.ws.n_keys:
            # spliced boundary: resident keys' live shows sit on device, so
            # hotness reads the host mem tier instead (possibly one pass
            # stale — fine for a precision heuristic, and side-effect free)
            if self._ici_adaptive():
                self._set_hot_rows(global_rows, table.shows_peek(all_keys))
            return self._finalize_spliced(
                table, carrier, all_keys, global_rows, ns, cap, prefetch
            )
        with record_event("boundary.pull", "boundary") as span:
            rows = (
                _rows_with_prefetch(table, all_keys, prefetch)
                if len(all_keys)
                else np.zeros((0, table.layout.width), dtype=np.float32)
            )
        STAT_SET("boundary.pull_s", span.seconds)
        if self._ici_adaptive() and len(all_keys):
            # the classic pull already materialized every row: its decayed
            # show column is the exact, free hotness source
            self._set_hot_rows(global_rows, rows[:, table.layout.SHOW])
        with record_event("boundary.layout", "boundary"):
            dev = np.zeros((ns, cap, table.layout.width), dtype=np.float32)
            dev.reshape(ns * cap, -1)[global_rows] = rows
        return dev

    @staticmethod
    def _ici_adaptive() -> bool:
        from paddlebox_tpu.ops import wire_quant  # lazy: avoids import cycle

        return wire_quant.ici_adaptive_engaged()

    def _set_hot_rows(self, global_rows: np.ndarray, shows: np.ndarray) -> None:
        """Publish per-row hotness bits for the adaptive ICI wire."""
        thr = float(config.get_flag("ici_hot_show"))
        hot = np.zeros(self.n_mesh_shards * self.capacity, dtype=bool)
        hot[global_rows] = np.asarray(shows, dtype=np.float32) >= thr
        self.hot_rows = hot
        STAT_SET("wire.ici_hot_keys", int(hot.sum()))

    def _finalize_spliced(
        self, table, carrier, all_keys, global_rows, ns, cap, prefetch=None
    ):
        """Delta boundary: splice carried rows on device, push departures,
        upload only new keys. Returns the [ns, cap, width] jax array.

        The host pull of the new keys runs on a worker thread so it
        overlaps the device-side allocation + common splice; the two
        scatters hit disjoint row sets, so running the common splice first
        is bitwise-identical to the old new-then-common order."""
        import jax.numpy as jnp

        old_keys = carrier.ws.sorted_keys
        # both sides sorted: positions of the intersection in each
        pos_in_old = np.searchsorted(old_keys, all_keys)
        pos_in_old = np.minimum(pos_in_old, len(old_keys) - 1)
        common = old_keys[pos_in_old] == all_keys  # mask over all_keys
        common_old = pos_in_old[common]
        # departing = old keys NOT in the new set
        in_new = np.zeros(len(old_keys), dtype=bool)
        in_new[common_old] = True
        leave_pos = np.nonzero(~in_new)[0]
        if len(leave_pos):
            # departing slice: D2H + host push overlap the next pass
            # (joined before any decay or durable read)
            carrier.push_departures_async(
                table, old_keys[leave_pos], leave_pos
            )
        new_mask = ~common
        new_keys = all_keys[new_mask]
        W = table.layout.width

        # single-writer result cell; the join below is the only reader
        pull = {"rows": None, "err": None, "secs": 0.0}

        def _pull_new():
            try:
                with record_event("boundary.pull", "boundary") as span:
                    pull["rows"] = _rows_with_prefetch(
                        table, new_keys, prefetch
                    )
            except BaseException as e:  # joined + re-raised below
                pull["err"] = e
            pull["secs"] = span.seconds

        puller = None
        if len(new_keys):
            puller = threading.Thread(
                target=_pull_new, name="boundary-pull", daemon=True
            )
            puller.start()

        # allocate the destination BORN under the carried table's sharding
        # (jit + out_shardings): an eager zeros (even one fed to
        # device_put) would first materialize the full next-pass table
        # unsharded on the default device — an HBM spike of full-table
        # size at exactly the boundary the carrier exists to slim down.
        # On a single device this degenerates to a plain allocation.
        with record_event("boundary.splice", "boundary") as span:
            dev = _sharded_zeros(ns * cap, W, carrier.dev_flat.sharding)
            if common.any():
                dev = dev.at[jnp.asarray(global_rows[common])].set(
                    carrier.rows_for(common_old)
                )
        STAT_SET("boundary.splice_s", span.seconds)
        if puller is not None:
            puller.join()
            if pull["err"] is not None:
                raise pull["err"]
            STAT_SET("boundary.pull_s", pull["secs"])
            from paddlebox_tpu import config as _config
            from paddlebox_tpu.ops.wire_quant import send_rows

            up = send_rows(
                pull["rows"], table.layout, str(_config.get_flag("wire_dtype"))
            )
            dev = dev.at[jnp.asarray(global_rows[new_mask])].set(up)
        return dev.reshape(ns, cap, W)

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """Batch keys -> global row ids (int32). Keys must be in the pass."""
        return lookup_rows(self.sorted_keys, self.row_of_sorted, keys)

    @property
    def padding_row(self) -> int:
        """Global row id safe for batch padding (shard 0's reserved row)."""
        return self.capacity - 1

    def writeback(
        self,
        device_array: np.ndarray,
        cancel: Optional[threading.Event] = None,
    ) -> None:
        """Flush trained rows back to the host store (EndPass parity).

        With ``writeback_threads`` > 1 and the native store available, the
        push is chunked (``writeback_chunk_keys``) through the explicit
        writer pool: chunk k+1's row gather runs while chunk k's push is
        in flight on a single-slot pipeline, and ``cancel`` (checked at
        chunk boundaries) lets a revert stop mid-writeback — whatever
        landed is exactly the partial writeback rollback's PassGuard
        contract covers. ``writeback_threads <= 1`` is the legacy serial
        path, bit for bit. Either way the host table ends bitwise-equal:
        chunks split a sorted unique key batch, so per-shard batch order
        and every row write are identical to the one-shot push.

        Emits the ``table.writeback.*`` stat family: total push seconds,
        per-chunk gather/wait seconds, pool size, chunk count, and the
        seconds the pipeline hid (push busy time that overlapped gathers).
        """
        if self.n_keys == 0:
            return
        flat = np.asarray(device_array).reshape(-1, device_array.shape[-1])
        threads = int(config.get_flag("writeback_threads"))
        if threads <= 1 or not getattr(self._table, "native", False):
            self._table.push(self.sorted_keys, flat[self.row_of_sorted])
            return
        chunk = max(1, int(config.get_flag("writeback_chunk_keys")))
        n = len(self.sorted_keys)
        t_all = time.perf_counter()
        wait_s = 0.0
        busy_s = 0.0
        n_chunks = 0
        pending = None

        def _push_chunk(ck: np.ndarray, cr: np.ndarray) -> float:
            t0 = time.perf_counter()
            self._table.push_writeback(ck, cr, threads)
            return time.perf_counter() - t0

        with ThreadPoolExecutor(max_workers=1) as ex:
            for lo in range(0, n, chunk):
                if cancel is not None and cancel.is_set():
                    # the in-flight chunk (if any) completes on executor
                    # shutdown; nothing past it starts
                    raise WritebackCancelled(lo, n)
                hi = min(n, lo + chunk)
                t0 = time.perf_counter()
                cr = np.ascontiguousarray(flat[self.row_of_sorted[lo:hi]])
                gather_s = time.perf_counter() - t0
                STAT_OBSERVE("table.writeback.gather_s", gather_s)
                if pending is not None:
                    t0 = time.perf_counter()
                    busy_s += pending.result()
                    w = time.perf_counter() - t0
                    wait_s += w
                    STAT_OBSERVE("table.writeback.chunk_wait_s", w)
                pending = ex.submit(_push_chunk, self.sorted_keys[lo:hi], cr)
                n_chunks += 1
            t0 = time.perf_counter()
            busy_s += pending.result()
            w = time.perf_counter() - t0
            wait_s += w
            STAT_OBSERVE("table.writeback.chunk_wait_s", w)
        total_s = time.perf_counter() - t_all
        STAT_SET("table.writeback.threads", threads)
        STAT_SET("table.writeback.chunks", n_chunks)
        STAT_SET("table.writeback.wait_s", wait_s)
        STAT_SET("table.writeback.push_s", total_s)
        STAT_OBSERVE("table.writeback.push_s", total_s)
        # push busy time the single-slot pipeline hid behind row gathers
        STAT_SET("table.writeback.hidden_s", max(0.0, busy_s - wait_s))
