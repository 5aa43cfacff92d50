"""ctypes binding for the native C++ slot parser (csrc/slot_parser.cc).

The reference's data loader is C++ worker threads parsing sample text
(data_feed.cc:2951-3061); this module is that native tier here. The library
is built on demand with g++ (no pybind11 in the image — plain C ABI +
ctypes, per the runtime's binding policy) and cached under csrc/build/,
named by a hash of its sources: a copied checkout can carry a stale .so
with a fresh mtime, but never one whose name matches other sources.

``parse_buffer(data, schema)`` parses a whole file's bytes in one native
call and wraps the columnar result in per-record numpy VIEWS over two big
copies (one uint64, one float) — no per-line Python work at all. The
records satisfy the same contract as data/parser.py::parse_line, which
remains both the fallback and the semantics oracle (tests assert equality).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import List, Optional

import numpy as np

from paddlebox_tpu.data.slot_record import SlotRecord
from paddlebox_tpu.data.slot_schema import SlotSchema

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRCS = [
    os.path.join(_REPO, "csrc", "slot_parser.cc"),
    os.path.join(_REPO, "csrc", "batch_packer.cc"),
    os.path.join(_REPO, "csrc", "host_table.cc"),
]
_CXXFLAGS = ["-O3", "-shared", "-fPIC", "-std=c++17"]
_BUILD_DIR = os.path.join(_REPO, "csrc", "build")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_u64p = ctypes.POINTER(ctypes.c_uint64)
_u32p = ctypes.POINTER(ctypes.c_uint32)
_i64p = ctypes.POINTER(ctypes.c_int64)
_i32p = ctypes.POINTER(ctypes.c_int32)
_f32p = ctypes.POINTER(ctypes.c_float)

# spill victim-selection policies (mirror csrc/host_table.cc kSpill*)
SPILL_FIFO = 0  # legacy creation-order sweep, untouched rows first
SPILL_FREQ = 1  # coldness-ranked: admission/pin thresholds + (show, epoch)

# column layout of pbx_table_tier_stats (8 int64 slots per shard)
TIER_STAT_FIELDS = (
    "mem_rows", "disk_rows", "spilled_total", "promoted_total",
    "admitted_disk_first", "lazy_shrunk", "dead_records", "spill_bytes",
)

# layout of pbx_table_io_stats (5 cumulative int64 slots): where the
# writeback/spill IO time actually went — the gather-vs-fwrite split of the
# double-buffered spill writers plus the push pre-pass header reads
IO_STAT_FIELDS = (
    "spill_gather_ns", "spill_fwrite_ns", "prepass_read_ns",
    "stage_flushes", "stage_bytes",
)


def _lib_path() -> str:
    """csrc/build/libpbx_parser-<hash of sources + compile flags>.so"""
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return os.path.join(_BUILD_DIR, f"libpbx_parser-{h.hexdigest()[:16]}.so")


def _build(lib_path: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # compile to a tmp path, then atomic-rename: a concurrent builder or
    # loader sees either no file or the whole one, never a half-written .so
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++"] + _CXXFLAGS + ["-o", tmp] + _SRCS,
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, lib_path)
    except Exception:
        # every caller silently falls back to the pure-Python paths on
        # False — a 10x parse/pull slowdown nobody asked for must at
        # least leave a counter behind (lazy import: this module stays
        # importable before the package does)
        from paddlebox_tpu.utils.monitor import STAT_ADD

        STAT_ADD("native.build_failures")
        try:
            os.unlink(tmp)
        # pbox-lint: disable=EXC007 — tmp may never have been created
        except OSError:
            pass
        return False
    # libraries built from other sources are dead weight in every copy of
    # the checkout (an unlinked .so stays mapped where it is loaded)
    for old in glob.glob(os.path.join(_BUILD_DIR, "libpbx_parser-*.so")):
        if old != lib_path:
            os.unlink(old)
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        # PBOX_NATIVE_LIB points the whole native tier at a prebuilt .so
        # (tools/native_sanitize.py replays the test suite against an
        # ASan+UBSan-instrumented build this way); the override is never
        # rebuilt — the caller owns its lifecycle
        lib_path = os.environ.get("PBOX_NATIVE_LIB")
        if not lib_path:
            if not all(os.path.exists(s) for s in _SRCS):
                return None
            lib_path = _lib_path()
            if not os.path.exists(lib_path) and not _build(lib_path):
                return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError:
            # a .so that BUILT but won't load (ABI skew, torn file from a
            # pre-atomic-rename writer) is stranger than a missing
            # compiler — count it separately from build failures
            from paddlebox_tpu.utils.monitor import STAT_ADD

            STAT_ADD("native.load_failures")
            return None
        lib.pbx_parse_buffer.restype = ctypes.c_void_p
        lib.pbx_parse_buffer.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
            ctypes.c_char_p, ctypes.c_int,
        ]
        for name in ("pbx_num_records", "pbx_num_skipped", "pbx_num_u64",
                     "pbx_num_f", "pbx_ins_chars"):
            getattr(lib, name).restype = ctypes.c_int64
            getattr(lib, name).argtypes = [ctypes.c_void_p]
        for name, t in (
            ("pbx_u64_values", _u64p), ("pbx_u64_offsets", _u32p),
            ("pbx_u64_base", _i64p), ("pbx_f_values", _f32p),
            ("pbx_f_offsets", _u32p), ("pbx_f_base", _i64p),
            ("pbx_search_ids", _u64p), ("pbx_cmatch", _i32p),
            ("pbx_rank", _i32p), ("pbx_ins_id_off", _i64p),
            ("pbx_ins_id_chars_ptr", ctypes.c_char_p),
        ):
            getattr(lib, name).restype = t
            getattr(lib, name).argtypes = [ctypes.c_void_p]
        lib.pbx_free.restype = None
        lib.pbx_free.argtypes = [ctypes.c_void_p]
        lib.pbx_packer_create.restype = ctypes.c_void_p
        lib.pbx_packer_create.argtypes = [
            _i32p, _i64p, _u32p, ctypes.c_int64, ctypes.c_int, ctypes.c_int64,
        ]
        lib.pbx_pack_batch.restype = ctypes.c_int64
        lib.pbx_pack_batch.argtypes = [
            ctypes.c_void_p, _i64p, ctypes.c_int64, _i32p, _i32p, _i32p,
        ]
        lib.pbx_packer_free.restype = None
        lib.pbx_packer_free.argtypes = [ctypes.c_void_p]
        lib.pbx_gather_f32_slot.restype = None
        lib.pbx_gather_f32_slot.argtypes = [
            _f32p, _i64p, _u32p, ctypes.c_int, _i64p, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int, _f32p,
        ]
        lib.pbx_block_stats.restype = ctypes.c_int
        lib.pbx_block_stats.argtypes = [
            _i32p, ctypes.c_int64, _i64p, _i64p, ctypes.c_int64, _i64p,
            ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            _i64p, _i64p,
        ]
        # --- host table store (csrc/host_table.cc) ---
        lib.pbx_table_create.restype = ctypes.c_void_p
        lib.pbx_table_create.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, _i32p, ctypes.c_int, ctypes.c_float,
            ctypes.c_char_p,
        ]
        lib.pbx_table_free.restype = None
        lib.pbx_table_free.argtypes = [ctypes.c_void_p]
        for name in ("pbx_table_size", "pbx_table_mem_rows", "pbx_table_disk_rows"):
            getattr(lib, name).restype = ctypes.c_int64
            getattr(lib, name).argtypes = [ctypes.c_void_p]
        lib.pbx_table_pull_or_create.restype = ctypes.c_int
        lib.pbx_table_pull_or_create.argtypes = [
            ctypes.c_void_p, _u64p, ctypes.c_int64, _f32p,
        ]
        lib.pbx_table_push.restype = ctypes.c_int
        lib.pbx_table_push.argtypes = [
            ctypes.c_void_p, _u64p, _f32p, ctypes.c_int64,
        ]
        lib.pbx_table_push_mt.restype = ctypes.c_int
        lib.pbx_table_push_mt.argtypes = [
            ctypes.c_void_p, _u64p, _f32p, ctypes.c_int64,
            ctypes.c_int, _i64p,
        ]
        lib.pbx_lookup_rows.restype = ctypes.c_int64
        lib.pbx_lookup_rows.argtypes = [
            _u64p, _i64p, ctypes.c_int64, _u64p, ctypes.c_int64, _i32p,
            ctypes.c_int, _i64p, ctypes.POINTER(ctypes.c_int),
        ]
        lib.pbx_table_io_stats.restype = None
        lib.pbx_table_io_stats.argtypes = [ctypes.c_void_p, _i64p]
        lib.pbx_table_decay_shrink.restype = ctypes.c_int64
        lib.pbx_table_decay_shrink.argtypes = [
            ctypes.c_void_p, ctypes.c_float, ctypes.c_float,
        ]
        lib.pbx_table_spill_cold.restype = ctypes.c_int64
        lib.pbx_table_spill_cold.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pbx_table_spill_cold_ex.restype = ctypes.c_int64
        lib.pbx_table_spill_cold_ex.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
            ctypes.c_float, ctypes.c_float,
        ]
        lib.pbx_table_tier_stats.restype = ctypes.c_int64
        lib.pbx_table_tier_stats.argtypes = [ctypes.c_void_p, _i64p]
        lib.pbx_table_compact_spill.restype = ctypes.c_int64
        lib.pbx_table_compact_spill.argtypes = [ctypes.c_void_p]
        lib.pbx_table_spill_stats.restype = None
        lib.pbx_table_spill_stats.argtypes = [
            ctypes.c_void_p, _i64p, _i64p, _i64p,
        ]
        lib.pbx_table_clear_touched.restype = None
        lib.pbx_table_clear_touched.argtypes = [ctypes.c_void_p]
        lib.pbx_table_shard_shows.restype = ctypes.c_int64
        lib.pbx_table_shard_shows.argtypes = [
            ctypes.c_void_p, ctypes.c_int, _f32p, ctypes.c_int64,
        ]
        lib.pbx_table_shard_keys.restype = ctypes.c_int64
        lib.pbx_table_shard_keys.argtypes = [
            ctypes.c_void_p, ctypes.c_int, _u64p, ctypes.c_int64,
        ]
        lib.pbx_table_shows_peek.restype = ctypes.c_int
        lib.pbx_table_shows_peek.argtypes = [
            ctypes.c_void_p, _u64p, ctypes.c_int64, _f32p,
        ]
        lib.pbx_table_snapshot_count.restype = ctypes.c_int64
        lib.pbx_table_snapshot_count.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ]
        lib.pbx_table_snapshot.restype = ctypes.c_int64
        lib.pbx_table_snapshot.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            _u64p, _f32p,
        ]
        _lib = lib
        return _lib


def _as_ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def gather_f32_slot(
    f_values: np.ndarray,
    f_base: np.ndarray,
    f_offsets: np.ndarray,
    indices: np.ndarray,
    slot: int,
    dim: int,
) -> np.ndarray:
    """[n, dim] ragged float-slot gather (short rows zero-padded, long rows
    truncated) — native tier for ColumnarRecords.float_slot_matrix."""
    lib = _load()
    f_values = np.ascontiguousarray(f_values, dtype=np.float32)
    f_base = np.ascontiguousarray(f_base, dtype=np.int64)
    f_offsets = np.ascontiguousarray(f_offsets, dtype=np.uint32)
    indices = np.ascontiguousarray(indices, dtype=np.int64)
    out = np.empty((len(indices), dim), np.float32)
    lib.pbx_gather_f32_slot(
        _as_ptr(f_values, ctypes.c_float),
        _as_ptr(f_base, ctypes.c_int64),
        _as_ptr(f_offsets, ctypes.c_uint32),
        f_offsets.shape[1],
        _as_ptr(indices, ctypes.c_int64),
        len(indices),
        slot,
        dim,
        _as_ptr(out, ctypes.c_float),
    )
    return out


def block_stats(
    rows: np.ndarray,
    rec_base: np.ndarray,
    key_counts: np.ndarray,
    blocks: np.ndarray,  # int64 [n_blocks, b] record indices
    cap: int,
    ns: int,
) -> tuple:
    """Per-block (L, max unique rows per shard) over the resolved pass rows
    — the resident feed's pad-freeze sweep, one GIL-released call (the
    counter side of compute_thread_batch_nccl, data_set.cc:2069-2135)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native tier unavailable (g++ build failed?)")
    rows = np.ascontiguousarray(rows, dtype=np.int32)
    rec_base = np.ascontiguousarray(rec_base, dtype=np.int64)
    key_counts = np.ascontiguousarray(key_counts, dtype=np.int64)
    blocks = np.ascontiguousarray(blocks, dtype=np.int64)
    n_blocks, b = blocks.shape
    L_out = np.empty(n_blocks, np.int64)
    bmax_out = np.empty(n_blocks, np.int64)
    rc = lib.pbx_block_stats(
        _as_ptr(rows, ctypes.c_int32),
        len(rows),
        _as_ptr(rec_base, ctypes.c_int64),
        _as_ptr(key_counts, ctypes.c_int64),
        len(rec_base),
        _as_ptr(blocks, ctypes.c_int64),
        n_blocks, b, int(cap), int(ns), int(cap) * int(ns),
        _as_ptr(L_out, ctypes.c_int64),
        _as_ptr(bmax_out, ctypes.c_int64),
    )
    if rc != 0:
        raise ValueError(
            "block_stats: record index, row or key span out of range")
    return L_out, bmax_out


def lookup_rows(
    sorted_keys: np.ndarray,
    row_of_sorted: np.ndarray,
    keys: np.ndarray,
    threads: int = 0,
) -> tuple:
    """``int32 [m]`` rows of ``keys`` in a pass working set, by one threaded
    native search (``pbx_lookup_rows``): the native body of
    ``table/sparse_table.py::lookup_rows``, which holds it bit-equal to its
    numpy body. ``sorted_keys`` must be non-empty; queries that are not
    contiguous uint64 are converted as ``astype`` converts them, and only
    then (a pass's ``u64_values`` goes in as it lies). ``threads <= 0`` is
    the native side's own choice; the result is the same at every value.
    Returns ``(rows, n_missing, first_missing, threads_used)``:
    ``first_missing`` are the query indices of the first five keys that are
    not in ``sorted_keys``, in query order."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native tier unavailable (g++ build failed?)")
    sorted_keys = np.ascontiguousarray(sorted_keys, dtype=np.uint64)
    row_of_sorted = np.ascontiguousarray(row_of_sorted, dtype=np.int64)
    keys = np.ascontiguousarray(keys, dtype=np.uint64)
    out = np.empty(len(keys), np.int32)
    first = np.zeros(5, np.int64)
    used = ctypes.c_int(0)
    n_missing = int(lib.pbx_lookup_rows(
        _as_ptr(sorted_keys, ctypes.c_uint64),
        _as_ptr(row_of_sorted, ctypes.c_int64),
        len(sorted_keys),
        _as_ptr(keys, ctypes.c_uint64),
        len(keys),
        _as_ptr(out, ctypes.c_int32),
        int(threads),
        _as_ptr(first, ctypes.c_int64),
        ctypes.byref(used),
    ))
    return out, n_missing, first[: min(n_missing, 5)], int(used.value)


class NativePacker:
    """Per-thread handle over one pass's row-resolved columnar records.

    ``pack(indices)`` -> (uniq_rows[U], inverse[L], segments[L]) unpadded;
    the device_pack wrapper buckets/pads. The referenced arrays are pinned
    on the instance so the C++ side's borrowed pointers stay alive.
    """

    def __init__(self, rows: np.ndarray, rec_base: np.ndarray,
                 rec_off: np.ndarray, n_sparse: int, n_table_rows: int):
        lib = _load()
        if lib is None:
            raise RuntimeError("native packer unavailable (g++ build failed?)")
        self._lib = lib
        # keep contiguous copies alive for the borrowed C++ pointers
        self._rows = np.ascontiguousarray(rows, dtype=np.int32)
        self._base = np.ascontiguousarray(rec_base, dtype=np.int64)
        self._off = np.ascontiguousarray(rec_off, dtype=np.uint32)
        self._h = lib.pbx_packer_create(
            _as_ptr(self._rows, ctypes.c_int32),
            _as_ptr(self._base, ctypes.c_int64),
            _as_ptr(self._off, ctypes.c_uint32),
            len(self._base), n_sparse, int(n_table_rows),
        )

    def pack(self, indices: np.ndarray, n_keys: int):
        if not self._h:
            raise RuntimeError("NativePacker used after close()")
        indices = np.ascontiguousarray(indices, dtype=np.int64)
        uniq = np.empty(n_keys, np.int32)
        inv = np.empty(n_keys, np.int32)
        seg = np.empty(n_keys, np.int32)
        U = self._lib.pbx_pack_batch(
            self._h, _as_ptr(indices, ctypes.c_int64), len(indices),
            _as_ptr(uniq, ctypes.c_int32), _as_ptr(inv, ctypes.c_int32),
            _as_ptr(seg, ctypes.c_int32),
        )
        if U < 0:
            raise ValueError("native pack: record index or row out of range")
        return uniq[:U], inv, seg

    def close(self) -> None:
        if self._h:
            self._lib.pbx_packer_free(self._h)
            self._h = None

    def __del__(self):  # best-effort; close() is the real contract
        try:
            self.close()
        # pbox-lint: disable=EXC007 — finalizer; close() is the contract
        except Exception:
            pass


class NativeHostStore:
    """Handle over the C++ sharded key->row store (csrc/host_table.cc).

    The mem+disk host tiers of the sparse table: batch pull_or_create /
    push run natively with the GIL released and thread across shards;
    cold rows spill to per-shard disk files and promote lazily with
    catch-up show/clk decay (LoadSSD2Mem parity, box_wrapper.cc:1325).
    """

    def __init__(
        self,
        n_shards: int,
        width: int,
        show_col: int,
        clk_col: int,
        seed: int,
        init_cols: np.ndarray,
        init_range: float,
        spill_dir: Optional[str] = None,
    ):
        lib = _load()
        if lib is None:
            raise RuntimeError("native host table unavailable (g++ build failed?)")
        self._lib = lib
        self.width = width
        ic = np.ascontiguousarray(init_cols, dtype=np.int32)
        self._h = lib.pbx_table_create(
            n_shards, width, show_col, clk_col,
            ctypes.c_uint64(seed), _as_ptr(ic, ctypes.c_int32), len(ic),
            float(init_range),
            spill_dir.encode() if spill_dir else None,
        )
        self.n_shards = n_shards

    def __len__(self) -> int:
        return int(self._lib.pbx_table_size(self._h))

    @property
    def mem_rows(self) -> int:
        return int(self._lib.pbx_table_mem_rows(self._h))

    @property
    def disk_rows(self) -> int:
        return int(self._lib.pbx_table_disk_rows(self._h))

    def pull_or_create(self, keys: np.ndarray) -> np.ndarray:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        out = np.empty((len(keys), self.width), np.float32)
        rc = self._lib.pbx_table_pull_or_create(
            self._h, _as_ptr(keys, ctypes.c_uint64), len(keys),
            _as_ptr(out, ctypes.c_float),
        )
        if rc != 0:
            raise IOError(f"native table pull failed rc={rc} (spill IO error?)")
        return out

    def push(self, keys: np.ndarray, rows: np.ndarray) -> None:
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        rows = np.ascontiguousarray(rows, dtype=np.float32)
        rc = self._lib.pbx_table_push(
            self._h, _as_ptr(keys, ctypes.c_uint64),
            _as_ptr(rows, ctypes.c_float), len(keys),
        )
        if rc != 0:
            raise IOError(f"native table push failed rc={rc} (spill IO error?)")

    def push_mt(self, keys: np.ndarray, rows: np.ndarray,
                threads: int) -> np.ndarray:
        """Batch push through the explicit writer pool (bitwise-equal to
        ``push`` at every thread count; ``threads <= 0`` = auto heuristic,
        ``1`` = forced serial). Returns per-shard wall seconds (float64
        [n_shards]) — the ``table.writeback.shard_s`` histogram feed.
        Raises the raw IOError on a negative rc; the table layer maps it
        to the typed SpillIOError."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        rows = np.ascontiguousarray(rows, dtype=np.float32)
        shard_ns = np.zeros(self.n_shards, np.int64)
        rc = self._lib.pbx_table_push_mt(
            self._h, _as_ptr(keys, ctypes.c_uint64),
            _as_ptr(rows, ctypes.c_float), len(keys), int(threads),
            _as_ptr(shard_ns, ctypes.c_int64),
        )
        if rc != 0:
            raise IOError(f"native table push failed rc={rc} (spill IO error?)")
        return shard_ns.astype(np.float64) / 1e9

    def io_stats(self) -> dict:
        """Cumulative writeback/spill IO telemetry, keyed by
        IO_STAT_FIELDS — the gather-vs-fwrite split of the double-buffered
        spill writers plus push pre-pass header read time."""
        out = np.zeros(len(IO_STAT_FIELDS), np.int64)
        self._lib.pbx_table_io_stats(self._h, _as_ptr(out, ctypes.c_int64))
        return {k: int(v) for k, v in zip(IO_STAT_FIELDS, out)}

    def decay_and_shrink(self, decay: float, threshold: float) -> int:
        return int(self._lib.pbx_table_decay_shrink(self._h, decay, threshold))

    def compact_spill(self) -> int:
        """Rewrite shard spill files keeping only live records; returns the
        live count or the raw negative code (-1 tier disabled, -2 IO
        failure) for the table layer to map to SpillIOError. (spill_cold
        also compacts a shard opportunistically once dead records
        outnumber live.)"""
        return int(self._lib.pbx_table_compact_spill(self._h))

    def spill_stats(self) -> tuple:
        """(live_records, dead_records, file_bytes) of the disk tier."""
        live = ctypes.c_int64()
        dead = ctypes.c_int64()
        nbytes = ctypes.c_int64()
        self._lib.pbx_table_spill_stats(
            self._h, ctypes.byref(live), ctypes.byref(dead), ctypes.byref(nbytes)
        )
        return int(live.value), int(dead.value), int(nbytes.value)

    def spill_cold(
        self,
        max_mem_rows: int,
        policy: int = SPILL_FIFO,
        pin_show: float = 0.0,
        admit_show: float = 0.0,
    ) -> int:
        """Run one cap sweep; returns rows spilled, or the raw NEGATIVE
        native code (-1 tier disabled, -2 IO failure). The table layer maps
        codes to the typed SpillIOError — the raw int never escapes to a
        caller that could read it as "spilled -2 rows"."""
        return int(self._lib.pbx_table_spill_cold_ex(
            self._h, int(max_mem_rows), int(policy),
            float(pin_show), float(admit_show),
        ))

    def tier_stats(self) -> np.ndarray:
        """int64 [n_shards, len(TIER_STAT_FIELDS)] per-shard occupancy and
        cumulative spill/promote counters, rows ordered by shard id."""
        out = np.zeros((self.n_shards, len(TIER_STAT_FIELDS)), np.int64)
        if self.n_shards:
            self._lib.pbx_table_tier_stats(self._h, _as_ptr(out, ctypes.c_int64))
        return out

    def clear_touched(self) -> None:
        self._lib.pbx_table_clear_touched(self._h)

    def shard_shows(self, shard: int) -> np.ndarray:
        """SHOW column of one shard (mem + disk, catch-up decay applied) —
        a column-only export so threshold scans never materialize value
        matrices. The C side clamps to the buffer size, so a concurrent
        push between sizing and export cannot overrun."""
        n = int(self._lib.pbx_table_snapshot_count(self._h, shard, 0))
        out = np.empty(n, np.float32)
        if n:
            got = int(self._lib.pbx_table_shard_shows(
                self._h, shard, _as_ptr(out, ctypes.c_float), n
            ))
            if got < 0:
                raise IOError(f"native shard_shows failed rc={got}")
            out = out[:got]
        return out

    def shows_peek(self, keys: np.ndarray) -> np.ndarray:
        """Decayed shows for a key batch, mem tier only (disk/absent = 0);
        pure read — never creates, promotes or touches a row."""
        keys = np.ascontiguousarray(keys, dtype=np.uint64)
        out = np.zeros(len(keys), np.float32)
        if len(keys):
            rc = int(self._lib.pbx_table_shows_peek(
                self._h, _as_ptr(keys, ctypes.c_uint64), len(keys),
                _as_ptr(out, ctypes.c_float),
            ))
            if rc < 0:
                raise IOError(f"native shows_peek failed rc={rc}")
        return out

    def shard_keys(self, shard: int) -> np.ndarray:
        """Keys of one shard straight from the hash (no value copies, no
        disk reads); clamped to the sized buffer like shard_shows."""
        n = int(self._lib.pbx_table_snapshot_count(self._h, shard, 0))
        out = np.empty(n, np.uint64)
        if n:
            got = int(self._lib.pbx_table_shard_keys(
                self._h, shard, _as_ptr(out, ctypes.c_uint64), n
            ))
            out = out[:got]
        return out

    def snapshot_shard(self, shard: int, only_touched: bool, clear_touched: bool):
        n = int(self._lib.pbx_table_snapshot_count(self._h, shard, int(only_touched)))
        keys = np.empty(n, np.uint64)
        vals = np.empty((n, self.width), np.float32)
        if n:
            got = int(self._lib.pbx_table_snapshot(
                self._h, shard, int(only_touched), int(clear_touched),
                _as_ptr(keys, ctypes.c_uint64), _as_ptr(vals, ctypes.c_float),
            ))
            if got < 0:
                raise IOError(f"native table snapshot failed rc={got}")
            keys, vals = keys[:got], vals[:got]
        return keys, vals

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.pbx_table_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        # pbox-lint: disable=EXC007 — finalizer; close() is the contract
        except Exception:
            pass


def available() -> bool:
    return _load() is not None


def _copy(ptr, n, dtype):
    if n == 0:
        return np.zeros(0, dtype=dtype)
    return np.ctypeslib.as_array(ptr, shape=(n,)).astype(dtype, copy=True)


def parse_buffer_columnar(
    data: bytes, schema: SlotSchema, stats: Optional[dict] = None
):
    """Parse a whole file's bytes natively -> ColumnarRecords (one copy per
    array, zero per-record Python work). Raises ValueError with the native
    line diagnostic. ``stats["skipped"]`` receives the no-feasign count."""
    from paddlebox_tpu.data.record_store import ColumnarRecords

    lib = _load()
    if lib is None:
        raise RuntimeError("native parser unavailable (g++ build failed?)")
    S = len(schema.slots)
    kinds = (ctypes.c_uint8 * S)(*[1 if s.type == "float" else 0 for s in schema.slots])
    dense = (ctypes.c_uint8 * S)(*[1 if s.dense else 0 for s in schema.slots])
    used = (ctypes.c_uint8 * S)(*[1 if s.used else 0 for s in schema.slots])
    errbuf = ctypes.create_string_buffer(512)
    h = lib.pbx_parse_buffer(
        data, len(data), S, kinds, dense, used,
        1 if schema.parse_ins_id else 0,
        1 if schema.parse_logkey else 0,
        errbuf, len(errbuf),
    )
    if not h:
        raise ValueError(f"native slot parse failed: {errbuf.value.decode()}")
    try:
        n = lib.pbx_num_records(h)
        if stats is not None:
            stats["skipped"] = int(lib.pbx_num_skipped(h))
        n_u, n_f = lib.pbx_num_u64(h), lib.pbx_num_f(h)
        Su, Sf = schema.num_sparse, schema.num_float
        want_ids = schema.parse_ins_id or schema.parse_logkey
        ins_off = None
        chars = b""
        if want_ids and n:
            ins_off = _copy(lib.pbx_ins_id_off(h), n + 1, np.int64)
            chars = ctypes.string_at(lib.pbx_ins_id_chars_ptr(h), lib.pbx_ins_chars(h))
        return ColumnarRecords(
            _copy(lib.pbx_u64_values(h), n_u, np.uint64),
            _copy(lib.pbx_u64_offsets(h), n * (Su + 1), np.uint32).reshape(n, Su + 1),
            _copy(lib.pbx_u64_base(h), n, np.int64),
            _copy(lib.pbx_f_values(h), n_f, np.float32),
            _copy(lib.pbx_f_offsets(h), n * (Sf + 1), np.uint32).reshape(n, Sf + 1),
            _copy(lib.pbx_f_base(h), n, np.int64),
            search_ids=_copy(lib.pbx_search_ids(h), n, np.uint64),
            cmatch=_copy(lib.pbx_cmatch(h), n, np.int32),
            rank=_copy(lib.pbx_rank(h), n, np.int32),
            ins_id_off=ins_off,
            ins_id_chars=chars,
        )
    finally:
        lib.pbx_free(h)


def parse_buffer(
    data: bytes, schema: SlotSchema, stats: Optional[dict] = None
) -> List[SlotRecord]:
    """Compat wrapper: columnar parse, then materialize SlotRecord views."""
    return parse_buffer_columnar(data, schema, stats).records()


def parse_file(
    path: str, schema: SlotSchema, stats: Optional[dict] = None
) -> List[SlotRecord]:
    with open(path, "rb") as f:
        return parse_buffer(f.read(), schema, stats)


def parse_file_columnar(path: str, schema: SlotSchema, stats: Optional[dict] = None):
    with open(path, "rb") as f:
        return parse_buffer_columnar(f.read(), schema, stats)
