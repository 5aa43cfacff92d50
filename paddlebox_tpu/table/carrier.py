"""Device-carried pass table: keep trained rows in HBM across passes.

The classic pass boundary is symmetric and expensive on a bandwidth-limited
host<->TPU transport: EndPass fetches the WHOLE trained table to the host
(writeback), and the next finalize uploads the WHOLE new table back — yet in
CTR streams consecutive passes share most of their keys (the reference keeps
its HBM cache warm across passes for exactly this reason, EndPass
box_wrapper.cc:627-651). The carrier exploits the overlap:

- at ``end_pass`` the trained DEVICE array is retained (no D2H);
- at the next finalize, rows whose keys survive into the new working set are
  SPLICED device-to-device into the new pass table (with the boundary's
  show/clk decay applied on device), rows whose keys leave are fetched and
  pushed to the host store (D2H of only the departing slice), and only
  genuinely new keys pull host rows and upload (H2D of only the new slice);
- the host store lags by at most the carried rows; every save/export path
  drains pending carriers first (``HostSparseTable.drain_pending``), so
  anything durable still sees the trained values.

Semantic deltas vs the classic boundary, both bounded and documented:
- shrink: a carried key is exempt from the boundary's cold-key drop while it
  stays carried (it is by definition active in the next pass; the host row
  it would have been judged by is stale anyway). With shrink_threshold=0 the
  paths are bit-equivalent.
- durability: between boundary and flush, the host store holds pre-pass
  values for carried keys. ``flush`` (directly, or via drain_pending from
  any save) restores full host fidelity.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np


class TableCarrier:
    """One pass's trained device table, pending splice-or-flush.

    Built at ``end_pass`` (no transfer), consumed by the next finalize
    (splice) and/or ``flush`` (full writeback). The carrier stays alive
    after the splice so a mid-pass save can still flush everything the host
    is owed (= this table's values; the NEXT pass's training is on its own
    live array and is owed nothing until its own end_pass).
    """

    def __init__(self, dev_flat, ws, layout, decay: Optional[float] = None):
        # dev_flat: jax [rows, width] — the single-device trained table, or
        # a single-host mesh table [ns, cap, W] flattened (stays sharded;
        # global row ids = shard*cap + rank index it directly)
        if dev_flat.ndim == 3:
            dev_flat = dev_flat.reshape(-1, dev_flat.shape[-1])
        self.dev_flat = dev_flat
        self.ws = ws
        self.layout = layout
        # accumulated show/clk decay owed to carried rows: each host-side
        # decay_and_shrink that runs while this carrier is pending calls
        # note_decay (HostSparseTable.decay_and_shrink does it under the
        # maintenance lock, so a carrier can never miss or double-count a
        # boundary). An eval pass keeping a carrier alive across TWO
        # boundaries accumulates two decays, exactly like its host rows
        # would have.
        self._decay_accum = 1.0 if decay is None else float(decay)
        self._flushed = False
        # in-flight background departure push. The lock covers the handle
        # only (install/claim/peek); waiting on the future itself happens
        # outside it, so wait_push (boundary prefetch thread) and
        # join_push (end_pass worker) can block concurrently.
        self._push_lock = threading.Lock()
        self._push_fut = None  # guarded-by: _push_lock
        # ws-order positions already handed back to the host (departures):
        # flush must not re-push them — once a key departs, the host row is
        # live again (later passes may train it) and a re-push of this
        # carrier's older value would overwrite that
        self._departed: Optional[np.ndarray] = None

    @property
    def flushed(self) -> bool:
        return self._flushed

    def note_decay(self, rate: float) -> None:
        """Record one boundary's show/clk decay (applied at splice/flush)."""
        self._decay_accum *= float(rate)

    def supersede(self) -> None:
        """A newer full writeback (classic end_pass or a successor carrier)
        covers every value this carrier owed: join the in-flight departure
        push, release the HBM reference, and go inert."""
        self.join_push()
        self._flushed = True
        self.dev_flat = None

    def _decay_mult(self) -> Optional[np.ndarray]:
        if self._decay_accum == 1.0:
            return None
        lay = self.layout
        mult = np.ones(lay.width, dtype=np.float32)
        mult[lay.SHOW] = self._decay_accum
        mult[lay.CLK] = self._decay_accum
        return mult

    def rows_for(self, positions: np.ndarray):
        """Device rows (decayed) for ws-order key positions [k] — stays on
        device; the caller splices it into the next pass table."""
        import jax.numpy as jnp

        vals = self.dev_flat[self.ws.row_of_sorted[positions]]
        mult = self._decay_mult()
        if mult is not None:
            vals = vals * jnp.asarray(mult)[None, :]
        return vals

    def fetch_for(self, positions: np.ndarray) -> np.ndarray:
        """Host copy (decayed) of ws-order key positions — the departing
        slice's D2H. Honors the ``wire_dtype`` flag: bf16/int8 shrinks the
        bytes on the transport (Quant pull-value parity,
        box_wrapper.cc:419-437)."""
        from paddlebox_tpu import config
        from paddlebox_tpu.ops.wire_quant import fetch_rows

        return fetch_rows(
            self.rows_for(positions),
            self.layout,
            str(config.get_flag("wire_dtype")),
        )

    def push_departures_async(self, table, keys: np.ndarray, positions) -> None:
        """Push the departing slice on a background thread: the D2H
        overlaps the next pass's load/train instead of stalling the
        boundary. The device gather dispatches NOW (so it reads this
        table's values, not anything later); only the host fetch + push
        run on the worker. Joined by flush(), and by the next end_pass
        before host decay (a late push landing after a decay would
        un-decay those rows)."""
        from concurrent.futures import Future

        from paddlebox_tpu import config
        from paddlebox_tpu.ops.wire_quant import (
            fetch_rows_finish,
            fetch_rows_start,
        )

        mode = str(config.get_flag("wire_dtype"))
        # quantizing casts dispatch NOW (they must read this table's
        # values); only the blocking D2H + push run on the worker
        handle = fetch_rows_start(self.rows_for(positions), self.layout, mode)
        pos = np.asarray(positions)
        self._departed = (
            pos if self._departed is None else np.union1d(self._departed, pos)
        )
        fut: Future = Future()

        def work():
            try:
                table.push(keys, fetch_rows_finish(handle, self.layout))
                fut.set_result(len(keys))
            except BaseException as e:
                fut.set_exception(e)

        # non-daemon so interpreter exit joins an in-flight push; join_push
        # retires the handle once the future settles
        th = threading.Thread(target=work, daemon=False)
        th.start()
        with self._push_lock:
            self._push_fut = (fut, pos)
            self._push_thread = th

    def join_push(self) -> None:
        """Wait for an in-flight departure push (idempotent).

        A FAILED push un-departs its positions: the host never received
        those rows, so they must stay owed — a later flush() retry
        re-pushes them (drain_pending keeps this carrier registered on
        failure). Without this, the departed-exclusion in flush would
        silently drop exactly the rows whose push failed."""
        with self._push_lock:
            fut_pos, self._push_fut = self._push_fut, None
            th = getattr(self, "_push_thread", None)
            self._push_thread = None
        if fut_pos is not None:
            fut, pos = fut_pos
            try:
                fut.result()
            except BaseException:
                self._departed = (
                    np.setdiff1d(self._departed, pos)
                    if self._departed is not None
                    else None
                )
                raise
            finally:
                if th is not None:
                    th.join()

    def wait_push(self) -> None:
        """Block until any in-flight departure push lands, WITHOUT
        consuming the handle or its failure.

        The boundary prefetch must not read a departing key's pre-push
        host row, so it waits here first — but error handling (un-depart +
        raise) belongs to join_push on the end_pass path, so a failure is
        swallowed and stays armed. (A failed push fails the boundary
        there, and the supervisor's revert discards the staged prefetch.)
        """
        with self._push_lock:
            fut_pos = self._push_fut
        if fut_pos is not None:
            try:
                fut_pos[0].result()
            # deferred handling by design (docstring): the failure stays
            # armed in the future and join_push raises + un-departs it
            # pbox-lint: disable=EXC007
            except BaseException:
                pass

    def flush(self, table) -> int:
        """Push every carried key's (decayed) value to the host store.

        Idempotent; returns keys written. Called by drain_pending from any
        save/export path, by rollback arming, and at close/day boundaries."""
        self.join_push()
        if self._flushed or self.ws is None or self.ws.n_keys == 0:
            self._flushed = True
            self.dev_flat = None
            return 0
        pos = np.arange(self.ws.n_keys)
        if self._departed is not None:
            pos = np.setdiff1d(pos, self._departed, assume_unique=True)
        # chunked: one full-table gather + host copy at once would double
        # peak memory exactly at the save points where a snapshot copy is
        # already resident; fixed-size chunks bound the transient
        chunk = 2_000_000
        for lo in range(0, len(pos), chunk):
            p = pos[lo : lo + chunk]
            table.push(self.ws.sorted_keys[p], self.fetch_for(p))
        self._flushed = True
        self.dev_flat = None  # release the HBM reference
        return len(pos)


class _ShardView:
    """Key->row view over ONE device's shard block of a multi-host pass
    table: duck-types the ``ws`` surface TableCarrier reads (sorted_keys /
    row_of_sorted / n_keys). Rows are LOCAL to the device block
    (local_shard * cap + rank)."""

    def __init__(self, keys_per_shard, cap: int):
        ks, rows = [], []
        for j, k in enumerate(keys_per_shard):
            ks.append(k)
            rows.append(j * cap + np.arange(len(k), dtype=np.int64))
        keys = (
            np.concatenate(ks) if ks else np.zeros(0, np.uint64)
        )
        lrows = (
            np.concatenate(rows) if rows else np.zeros(0, np.int64)
        )
        order = np.argsort(keys)
        self.sorted_keys = keys[order]
        self.row_of_sorted = lrows[order]
        self.n_keys = len(keys)


class MultiHostCarrier:
    """Per-host device-carried pass table over a DistributedWorkingSet.

    The reference's EndPass keeps the HBM cache warm on EVERY node
    (box_wrapper.cc:627-651); here the same holds because ownership is
    structurally local: key -> mesh shard is a stable hash and shards pin
    to devices, so a key that survives into the next pass lands on the
    SAME device, and a key that departs is owed to THIS host's table slice
    (DistributedWorkingSet writeback is host-local by construction,
    dist_ws.py:20-22). The global trained table therefore decomposes into
    one independent TableCarrier per local device (its addressable shard
    block), each splicing / fetching / flushing purely locally — no
    cross-host traffic, no collective at the boundary.

    Registry-facing surface (flushed / note_decay / flush / supersede /
    join_push) delegates to the per-device carriers, so
    ``HostSparseTable.drain_pending`` and the decay bookkeeping treat this
    exactly like a single-host carrier.
    """

    def __init__(self, global_table, owned_shard_keys, layout,
                 ownership_epoch: int = 0):
        # global_table: jax [ns, cap, W] sharded on axis 0 over the mesh;
        # only this process's addressable shard blocks are touched.
        # owned_shard_keys: the ending pass's per-local-shard key lists
        # (DistributedWorkingSet.owned_shard_keys) — snapshotted into
        # per-device _ShardViews; the working set itself is NOT retained.
        # ownership_epoch pins the shard->host placement this snapshot was
        # taken under: a later finalize under a DIFFERENT epoch must not
        # splice these blocks (the ranges re-homed) — it flushes instead
        # (DistributedWorkingSet.finalize checks the pin).
        self.layout = layout
        self.ownership_epoch = int(ownership_epoch)
        self.sharding = global_table.sharding
        self.ns, self.cap, self.width = global_table.shape
        shards = sorted(
            global_table.addressable_shards,
            key=lambda s: s.index[0].start or 0,
        )
        if not shards:
            raise ValueError("no addressable shards on this process")
        self.shards_per_dev = shards[0].data.shape[0]
        self.devices = [s.data.devices().pop() for s in shards]
        # shard j of this host's owned_shard_keys belongs to device
        # j // shards_per_dev at block-local shard j % shards_per_dev
        self.parts = []
        spd = self.shards_per_dev
        for d, s in enumerate(shards):
            view = _ShardView(
                owned_shard_keys[d * spd : (d + 1) * spd], self.cap
            )
            dev_flat = s.data.reshape(spd * self.cap, self.width)
            self.parts.append(TableCarrier(dev_flat, view, layout))

    @property
    def flushed(self) -> bool:
        return all(c.flushed for c in self.parts)

    def note_decay(self, rate: float) -> None:
        for c in self.parts:
            c.note_decay(rate)

    def supersede(self) -> None:
        for c in self.parts:
            c.supersede()

    def join_push(self) -> None:
        # join ALL in-flight pushes even if one raises, then surface the
        # first failure (its positions are un-departed by TableCarrier)
        err = None
        for c in self.parts:
            try:
                c.join_push()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                err = err or e
        if err is not None:
            raise err

    def wait_push(self) -> None:
        for c in self.parts:
            c.wait_push()

    def flush(self, table) -> int:
        n = 0
        for c in self.parts:
            n += c.flush(table)
        return n
