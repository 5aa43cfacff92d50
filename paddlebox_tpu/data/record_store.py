"""Columnar record store: the whole pass as a handful of flat arrays.

The reference keeps pass data as pooled ``SlotRecord`` objects
(``SlotObjPool``, data_feed.h:934-1050) because its per-record work happens
in C++ threads. Here the same columnar idea goes further: the pass IS the
arrays — ``u64_values``/``f_values`` flats plus per-record offset tables —
and every pass-wide operation (working-set key collection, key->row
resolution, label extraction, shuffling, batch packing) is one vectorized
or native call over them. No per-record Python objects exist on the hot
path; ``record(i)`` materializes a ``SlotRecord`` view only for the compat
paths (pv merge, AucRunner, cross-node routing).

Key→row resolution is pass-scoped: after ``PassWorkingSet.finalize`` the
mapping key->table row is frozen, so ``resolve_rows`` translates the whole
store ONCE (``ws.lookup`` over ``u64_values`` as it lies, no copy:
``table/sparse_table.py::lookup_rows``, one threaded native search where
the library loaded, numpy's searchsorted otherwise); batches then gather
int32 rows and never touch uint64 keys again (the host analog of the
reference's device CopyKeys + DedupKeysAndFillIdx,
box_wrapper_impl.h:25-162).
"""

from __future__ import annotations

import struct
from typing import List, Optional, Sequence

import numpy as np

from paddlebox_tpu.data.slot_record import SlotRecord
from paddlebox_tpu.data.slot_schema import SlotSchema


class ColumnarRecords:
    """Immutable columnar batch-of-all-records for one pass (one node)."""

    __slots__ = (
        "u64_values", "u64_offsets", "u64_base",
        "f_values", "f_offsets", "f_base",
        "search_ids", "cmatch", "rank",
        "ins_id_off", "ins_id_chars",
        "_rows", "_rows_ws_id",
    )

    def __init__(
        self,
        u64_values: np.ndarray,   # uint64 [total_u64]
        u64_offsets: np.ndarray,  # uint32 [n, n_sparse+1] record-local
        u64_base: np.ndarray,     # int64 [n]
        f_values: np.ndarray,     # float32 [total_f]
        f_offsets: np.ndarray,    # uint32 [n, n_float+1]
        f_base: np.ndarray,       # int64 [n]
        search_ids: Optional[np.ndarray] = None,  # uint64 [n]
        cmatch: Optional[np.ndarray] = None,      # int32 [n]
        rank: Optional[np.ndarray] = None,        # int32 [n]
        ins_id_off: Optional[np.ndarray] = None,  # int64 [n+1] byte offsets
        ins_id_chars: bytes = b"",
    ):
        self.u64_values = u64_values
        self.u64_offsets = u64_offsets
        self.u64_base = u64_base
        self.f_values = f_values
        self.f_offsets = f_offsets
        self.f_base = f_base
        n = len(u64_base)
        self.search_ids = search_ids if search_ids is not None else np.zeros(n, np.uint64)
        self.cmatch = cmatch if cmatch is not None else np.zeros(n, np.int32)
        self.rank = rank if rank is not None else np.zeros(n, np.int32)
        self.ins_id_off = ins_id_off
        self.ins_id_chars = ins_id_chars
        self._rows: Optional[np.ndarray] = None  # int32 [total_u64]
        self._rows_ws_id: Optional[int] = None

    # ---- basics ----------------------------------------------------------

    def __len__(self) -> int:
        return len(self.u64_base)

    @property
    def n_sparse(self) -> int:
        return self.u64_offsets.shape[1] - 1

    @property
    def n_float(self) -> int:
        return self.f_offsets.shape[1] - 1

    def key_counts(self) -> np.ndarray:
        """int64 [n]: total feasign count per record."""
        return self.u64_offsets[:, -1].astype(np.int64)

    def ins_id(self, i: int) -> str:
        if self.ins_id_off is None:
            return ""
        a, b = int(self.ins_id_off[i]), int(self.ins_id_off[i + 1])
        return self.ins_id_chars[a:b].decode(errors="replace")

    def record(self, i: int) -> SlotRecord:
        """Materialize one record as (view-backed) SlotRecord — compat path."""
        ub, fb = int(self.u64_base[i]), int(self.f_base[i])
        return SlotRecord(
            u64_values=self.u64_values[ub : ub + int(self.u64_offsets[i, -1])],
            u64_offsets=self.u64_offsets[i],
            f_values=self.f_values[fb : fb + int(self.f_offsets[i, -1])],
            f_offsets=self.f_offsets[i],
            ins_id=self.ins_id(i),
            search_id=int(self.search_ids[i]),
            cmatch=int(self.cmatch[i]),
            rank=int(self.rank[i]),
        )

    def records(self) -> List[SlotRecord]:
        return [self.record(i) for i in range(len(self))]

    # ---- construction ----------------------------------------------------

    @classmethod
    def empty(cls, n_sparse: int, n_float: int) -> "ColumnarRecords":
        return cls(
            np.zeros(0, np.uint64), np.zeros((0, n_sparse + 1), np.uint32),
            np.zeros(0, np.int64), np.zeros(0, np.float32),
            np.zeros((0, n_float + 1), np.uint32), np.zeros(0, np.int64),
            ins_id_off=np.zeros(1, np.int64),
        )

    @classmethod
    def from_records(
        cls, records: Sequence[SlotRecord], schema: SlotSchema
    ) -> "ColumnarRecords":
        """Vectorized concat of SlotRecords (fallback-parser / router path)."""
        n = len(records)
        Su, Sf = schema.num_sparse, schema.num_float
        if n == 0:
            return cls.empty(Su, Sf)
        u_off = np.stack([r.u64_offsets for r in records]).astype(np.uint32)
        f_off = np.stack([r.f_offsets for r in records]).astype(np.uint32)
        u_base = np.concatenate([[0], np.cumsum(u_off[:, -1])]).astype(np.int64)
        f_base = np.concatenate([[0], np.cumsum(f_off[:, -1])]).astype(np.int64)
        u_vals = (
            np.concatenate([r.u64_values for r in records])
            if u_base[-1]
            else np.zeros(0, np.uint64)
        )
        f_vals = (
            np.concatenate([r.f_values for r in records])
            if f_base[-1]
            else np.zeros(0, np.float32)
        )
        has_meta = schema.parse_ins_id or schema.parse_logkey
        ins_off = None
        chars = b""
        if has_meta:
            ids = [r.ins_id.encode() for r in records]
            lens = np.array([len(b) for b in ids], np.int64)
            ins_off = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
            chars = b"".join(ids)
        return cls(
            u_vals.astype(np.uint64), u_off, u_base[:-1],
            f_vals.astype(np.float32), f_off, f_base[:-1],
            search_ids=np.array([r.search_id for r in records], np.uint64),
            cmatch=np.array([r.cmatch for r in records], np.int32),
            rank=np.array([r.rank for r in records], np.int32),
            ins_id_off=ins_off, ins_id_chars=chars,
        )

    @classmethod
    def concat(cls, parts: Sequence["ColumnarRecords"]) -> "ColumnarRecords":
        parts = [p for p in parts if len(p)]
        if not parts:
            raise ValueError("concat of zero non-empty parts (use empty())")
        if len(parts) == 1:
            return parts[0]
        u_vals = np.concatenate([p.u64_values for p in parts])
        f_vals = np.concatenate([p.f_values for p in parts])
        u_off = np.concatenate([p.u64_offsets for p in parts])
        f_off = np.concatenate([p.f_offsets for p in parts])
        ub, fb, off_u, off_f = [], [], 0, 0
        for p in parts:
            ub.append(p.u64_base + off_u)
            fb.append(p.f_base + off_f)
            off_u += len(p.u64_values)
            off_f += len(p.f_values)
        have_ids = all(p.ins_id_off is not None for p in parts)
        ins_off = None
        chars = b""
        if have_ids:
            io, base = [np.zeros(1, np.int64)], 0
            pieces = []
            for p in parts:
                io.append(p.ins_id_off[1:] + base)
                base += p.ins_id_off[-1]
                pieces.append(p.ins_id_chars)
            chars = b"".join(pieces)
            ins_off = np.concatenate(io)
        return cls(
            u_vals, u_off, np.concatenate(ub), f_vals, f_off, np.concatenate(fb),
            search_ids=np.concatenate([p.search_ids for p in parts]),
            cmatch=np.concatenate([p.cmatch for p in parts]),
            rank=np.concatenate([p.rank for p in parts]),
            ins_id_off=ins_off, ins_id_chars=bytes(chars),
        )

    def select(self, indices: np.ndarray) -> "ColumnarRecords":
        """New store holding ``indices``' records (vectorized ragged gather).

        Used for physical shuffles and cross-node routing — the per-record
        list-append of the reference's ShuffleData (data_set.cc:1772-1791)
        becomes one gather per array.
        """
        indices = np.asarray(indices, dtype=np.int64)
        u_lens = self.u64_offsets[indices, -1].astype(np.int64)
        f_lens = self.f_offsets[indices, -1].astype(np.int64)
        u_idx = _ragged_indices(self.u64_base[indices], u_lens)
        f_idx = _ragged_indices(self.f_base[indices], f_lens)
        ins_off = None
        chars = b""
        if self.ins_id_off is not None:
            starts = self.ins_id_off[indices]
            lens = (self.ins_id_off[indices + 1] - starts).astype(np.int64)
            cidx = _ragged_indices(starts, lens)
            chars = np.frombuffer(self.ins_id_chars, np.uint8)[cidx].tobytes()
            ins_off = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
        return ColumnarRecords(
            self.u64_values[u_idx], self.u64_offsets[indices],
            np.concatenate([[0], np.cumsum(u_lens[:-1])]).astype(np.int64)
            if len(indices) else np.zeros(0, np.int64),
            self.f_values[f_idx], self.f_offsets[indices],
            np.concatenate([[0], np.cumsum(f_lens[:-1])]).astype(np.int64)
            if len(indices) else np.zeros(0, np.int64),
            search_ids=self.search_ids[indices],
            cmatch=self.cmatch[indices],
            rank=self.rank[indices],
            ins_id_off=ins_off, ins_id_chars=chars,
        )

    # ---- wire format (cross-process shuffle / working-set exchange) ------
    #
    # v2: one fixed header + raw column blocks in declared order. Column
    # dtypes are pinned by the class contract, so the header only needs
    # the shape scalars — no zip container, no per-array .npy headers, no
    # CRC duplication (the transport frame CRC already covers the bytes).
    # v1 (np.savez) payloads are still decoded: they start with the zip
    # local-file magic "PK\x03\x04", which can never collide with _WIRE_MAGIC.

    _WIRE_MAGIC = b"PBCR"
    _WIRE_VERSION = 2
    # magic, version, has_ins, n_sparse, n_float, n, n_u64, n_f, ins_chars
    _WIRE_HDR = struct.Struct("<4sBBHHQQQQ")

    def to_bytes(self) -> bytes:
        """Serialize for the host transport (compact v2: header + raw
        column blocks; versioned, self-describing, no pickle)."""
        has_ins = self.ins_id_off is not None
        parts = [
            self._WIRE_HDR.pack(
                self._WIRE_MAGIC, self._WIRE_VERSION, int(has_ins),
                self.n_sparse, self.n_float, len(self),
                len(self.u64_values), len(self.f_values),
                len(self.ins_id_chars) if has_ins else 0,
            ),
            np.ascontiguousarray(self.u64_values, np.uint64).tobytes(),
            np.ascontiguousarray(self.u64_offsets, np.uint32).tobytes(),
            np.ascontiguousarray(self.u64_base, np.int64).tobytes(),
            np.ascontiguousarray(self.f_values, np.float32).tobytes(),
            np.ascontiguousarray(self.f_offsets, np.uint32).tobytes(),
            np.ascontiguousarray(self.f_base, np.int64).tobytes(),
            np.ascontiguousarray(self.search_ids, np.uint64).tobytes(),
            np.ascontiguousarray(self.cmatch, np.int32).tobytes(),
            np.ascontiguousarray(self.rank, np.int32).tobytes(),
        ]
        if has_ins:
            parts.append(np.ascontiguousarray(self.ins_id_off, np.int64).tobytes())
            parts.append(bytes(self.ins_id_chars))
        return b"".join(parts)

    @classmethod
    def from_bytes(cls, data: bytes) -> "ColumnarRecords":
        if data[:4] == cls._WIRE_MAGIC:
            return cls._from_bytes_v2(data)
        if data[:4] == b"PK\x03\x04":  # legacy np.savez container
            import io

            z = np.load(io.BytesIO(data))
            ins_off = z["ins_id_off"] if "ins_id_off" in z.files else None
            chars = z["ins_id_chars"].tobytes() if "ins_id_chars" in z.files else b""
            return cls(
                z["u64_values"], z["u64_offsets"], z["u64_base"],
                z["f_values"], z["f_offsets"], z["f_base"],
                search_ids=z["search_ids"], cmatch=z["cmatch"], rank=z["rank"],
                ins_id_off=ins_off, ins_id_chars=chars,
            )
        raise ValueError(
            f"not a ColumnarRecords wire payload (magic {data[:4]!r})"
        )

    @classmethod
    def _from_bytes_v2(cls, data: bytes) -> "ColumnarRecords":
        hdr = cls._WIRE_HDR
        if len(data) < hdr.size:
            raise ValueError("ColumnarRecords v2 payload shorter than header")
        magic, ver, has_ins, n_sparse, n_float, n, n_u64, n_f, n_chars = (
            hdr.unpack_from(data)
        )
        if ver != cls._WIRE_VERSION:
            raise ValueError(f"ColumnarRecords wire version {ver} unsupported")
        # one writable buffer: slices below are views into it, matching the
        # fresh-array semantics of the npz path (slots_shuffle mutates
        # u64_values in place on the eval path)
        buf = bytearray(data)
        off = [hdr.size]

        def block(dtype, count):
            dt = np.dtype(dtype)
            end = off[0] + dt.itemsize * count
            if end > len(buf):
                raise ValueError(
                    "ColumnarRecords v2 payload truncated: header declares "
                    f"more column bytes than the {len(buf)}-byte buffer holds"
                )
            a = np.frombuffer(buf, dt, count=count, offset=off[0])
            off[0] = end
            return a

        u64_values = block(np.uint64, n_u64)
        u64_offsets = block(np.uint32, n * (n_sparse + 1)).reshape(n, n_sparse + 1)
        u64_base = block(np.int64, n)
        f_values = block(np.float32, n_f)
        f_offsets = block(np.uint32, n * (n_float + 1)).reshape(n, n_float + 1)
        f_base = block(np.int64, n)
        search_ids = block(np.uint64, n)
        cmatch = block(np.int32, n)
        rank = block(np.int32, n)
        ins_off = None
        chars = b""
        if has_ins:
            ins_off = block(np.int64, n + 1)
            chars = bytes(block(np.uint8, n_chars))
        if off[0] != len(buf):
            raise ValueError(
                f"ColumnarRecords v2 payload holds {len(buf) - off[0]} "
                "trailing bytes beyond the declared columns"
            )
        return cls(
            u64_values, u64_offsets, u64_base, f_values, f_offsets, f_base,
            search_ids=search_ids, cmatch=cmatch, rank=rank,
            ins_id_off=ins_off, ins_id_chars=chars,
        )

    # ---- pass-scoped precomputation -------------------------------------

    def resolve_rows(self, ws) -> np.ndarray:
        """int32 pass-local row per key, whole store at once (cached).

        One lookup per pass replaces a per-batch key search — the decisive
        host-side win over re-resolving every batch. The keys are resolved
        by the working set's ``lookup`` (``lookup_rows``: every key proven
        present, ``KeyError`` otherwise; at a pass's size the threaded
        native search ``pbx_lookup_rows``, counted in
        ``table.lookup.native_keys``).
        """
        if self._rows is not None and self._rows_ws_id == id(ws):
            return self._rows
        self._rows = (
            ws.lookup(self.u64_values)
            if len(self.u64_values)
            else np.zeros(0, np.int32)
        )
        self._rows_ws_id = id(ws)
        return self._rows

    def invalidate_rows(self) -> None:
        """Call after mutating keys in place (slots_shuffle eval path)."""
        self._rows = None
        self._rows_ws_id = None

    def float_slot_matrix(
        self, slot_idx: int, dim: int, indices: Optional[np.ndarray] = None
    ) -> np.ndarray:
        """[n, dim] dense view of a float slot (labels / dense features)."""
        if indices is None:
            indices = np.arange(len(self), dtype=np.int64)
        starts = self.f_base[indices] + self.f_offsets[indices, slot_idx].astype(np.int64)
        lens = (
            self.f_offsets[indices, slot_idx + 1] - self.f_offsets[indices, slot_idx]
        ).astype(np.int64)
        if np.all(lens == dim):
            idx = starts[:, None] + np.arange(dim, dtype=np.int64)[None, :]
            return self.f_values[idx].astype(np.float32, copy=False)
        from paddlebox_tpu.utils import native

        if native.available():
            return native.gather_f32_slot(
                self.f_values, self.f_base, self.f_offsets, indices, slot_idx, dim
            )
        out = np.zeros((len(indices), dim), np.float32)
        for i in range(len(indices)):
            c = min(int(lens[i]), dim)
            out[i, :c] = self.f_values[starts[i] : starts[i] + c]
        return out


def _ragged_indices(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Flat gather indices for variable-length runs [starts[i], +lens[i])."""
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    idx = np.ones(total, dtype=np.int64)
    nz = lens > 0
    # positions where a new run begins get start - (prev_start + prev_len) + 1
    run_starts = starts[nz]
    run_lens = lens[nz]
    run_ends = np.cumsum(run_lens)[:-1]
    idx[0] = run_starts[0]
    idx[run_ends] = run_starts[1:] - (run_starts[:-1] + run_lens[:-1]) + 1
    np.cumsum(idx, out=idx)
    return idx
