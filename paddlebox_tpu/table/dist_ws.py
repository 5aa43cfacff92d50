"""Multi-host pass working set: host-sharded table ownership + key exchange.

The reference's pass open (`BeginFeedPass`, box_wrapper.cc:580) hands every
feasign of the pass to the closed boxps lib, which shards keys across MPI
nodes and stages each node's slice into its GPUs. This module is that tier
in the open: mesh shards partition keys (`key_to_shard(key, n_mesh)`), each
host OWNS the contiguous shard range of its local devices, and a two-round
host exchange builds the pass:

  round 1 (request):  every host all-to-alls the pass keys it saw to the
                      keys' owner hosts;
  round 2 (reply):    each owner dedups, assigns ranks (ascending key order
                      per shard — identical layout to the single-process
                      PassWorkingSet), pulls/creates rows in its LOCAL
                      HostSparseTable slice, and replies to each requester
                      with the global row ids of the keys it asked about.

Capacity is allreduce-max'd so every host compiles the same shapes
(lockstep parity, compute_thread_batch_nccl data_set.cc:2069-2135), and
writeback is purely local: a host's trained device slice lands in its own
host table — no cross-host traffic at pass end.

Both rounds encode through ``ops/host_codec.py``: request key streams are
delta+varint under the ``host_wire_codec`` flag (sorted unique uint64 →
~1-2 bytes/key; marker byte keeps raw/codec ranks interoperable), and row
replies always ride the narrow-int codec (width picked from the
``n_mesh_shards * capacity`` bound, overflow is a loud codec error).
``wire.ws_req_*`` / ``wire.ws_rep_*`` counters record raw-vs-encoded bytes
per round — the per-round ratios chaos_probe's distributed soak reports.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

from paddlebox_tpu import config
from paddlebox_tpu.ops import host_codec
from paddlebox_tpu.parallel.membership import OwnershipMap
from paddlebox_tpu.table.sparse_table import (
    HostSparseTable,
    key_to_shard,
    lookup_rows,
    merge_unique_keys,
)
from paddlebox_tpu.utils.monitor import STAT_ADD


class DistributedWorkingSet:
    """Pass working set across hosts; same pack-time surface as
    PassWorkingSet (n_mesh_shards / capacity / padding_row / lookup)."""

    def __init__(
        self, transport, n_mesh_shards: int, pass_id: int = 0, epoch: int = 0,
        ownership: Optional[OwnershipMap] = None,
    ):
        self.transport = transport
        self.n_mesh_shards = n_mesh_shards
        n_hosts = transport.n_ranks
        # ownership is an explicit versioned map (largest-remainder
        # contiguous ranges), not rank arithmetic: uneven splits are fine
        # and the live set may be smaller than the endpoint list after a
        # membership shrink. Default reproduces the historical even split.
        if ownership is None:
            ownership = OwnershipMap.even(n_mesh_shards, n_hosts)
        if ownership.n_mesh_shards != n_mesh_shards:
            raise ValueError(
                f"ownership map covers {ownership.n_mesh_shards} shards, "
                f"pass has {n_mesh_shards}"
            )
        if not ownership.is_live(transport.rank):
            raise ValueError(
                f"rank {transport.rank} is not live in {ownership!r}"
            )
        self.ownership = ownership
        lo, hi = ownership.range_of(transport.rank)
        self.shard_lo = lo
        self.shards_per_host = hi - lo  # THIS rank's owned count (uneven ok)
        self.pass_id = pass_id
        # pass-retry epoch: tags carry ``@e<epoch>`` so the transport can
        # discard a reverted attempt's frames instead of feeding them to
        # the retried exchange (see TcpTransport.discard_epochs_below)
        self.epoch = epoch
        self._key_chunks: List[np.ndarray] = []
        self._lock = threading.Lock()
        self._finalized = False
        # set by finalize():
        self.sorted_keys: Optional[np.ndarray] = None  # referenced keys
        self.row_of_sorted: Optional[np.ndarray] = None
        self.capacity = 0
        self.n_keys = 0  # locally referenced
        self.owned_shard_keys: Optional[List[np.ndarray]] = None
        # bool [n_mesh_shards*capacity] hotness bits for the adaptive ICI
        # wire (None = off/ablated); set by finalize via the gated ws-hot
        # round — owners read their local tier, requesters get one bit per
        # requested key
        self.hot_rows: Optional[np.ndarray] = None

    def add_keys(self, keys: np.ndarray) -> None:
        if self._finalized:
            raise RuntimeError("working set already finalized")
        if len(keys):
            with self._lock:
                self._key_chunks.append(np.unique(keys.astype(np.uint64)))

    def premerge(self, threads: int = 1) -> np.ndarray:
        """Collapse accumulated key chunks now (boundary feed stage); the
        later finalize re-merges the singleton list via the no-copy fast
        path (see PassWorkingSet.premerge)."""
        if self._finalized:
            raise RuntimeError("working set already finalized")
        with self._lock:
            merged = merge_unique_keys(self._key_chunks, threads)
            self._key_chunks = [merged] if len(merged) else []
        return merged

    def _owner_host(self, keys: np.ndarray) -> np.ndarray:
        return self.ownership.owner_of_shard(
            key_to_shard(keys, self.n_mesh_shards)
        )

    def finalize(
        self, table: HostSparseTable, round_to: int = 512, carrier=None,
        prefetch=None,
    ) -> np.ndarray:
        """Two-round exchange; returns THIS host's device slice
        ``[shards_per_host, capacity, width]`` (global row of key =
        global_shard * capacity + rank, exactly the single-process layout).

        With ``carrier`` (a MultiHostCarrier from the previous pass's
        end_pass), the boundary goes delta-only PER HOST: each local
        device splices its surviving shard rows device-locally, departures
        D2H only their slice into the local host table, and only new keys
        upload — then the per-device blocks reassemble into the global
        mesh array without any cross-host traffic (every node keeps its
        HBM cache warm, EndPass parity box_wrapper.cc:627-651). Returns a
        global jax.Array in that case.

        ``prefetch`` is accepted for interface parity with
        PassWorkingSet.finalize and ignored: the dataset's boundary feed
        stage never stages a host prefetch for a distributed pass (owned
        keys are only known after the exchange)."""
        t = self.transport
        with self._lock:
            referenced = merge_unique_keys(
                self._key_chunks,
                int(config.get_flag("boundary_merge_threads")),
            )
            self._key_chunks = []
        self.n_keys = len(referenced)

        # round 1: route referenced keys to their owner hosts. The keys per
        # destination are a masked slice of np.unique output — sorted — so
        # the delta+varint codec applies; the payload's marker byte keeps
        # the format self-describing (a codec-on rank and a raw-ablation
        # rank decode each other's frames identically)
        use_codec = bool(config.get_flag("host_wire_codec"))
        owners = self._owner_host(referenced)
        req_out = []
        for h in range(t.n_ranks):
            req_out.append(
                host_codec.encode_key_stream(referenced[owners == h], use_codec)
            )
        STAT_ADD("wire.ws_req_raw_bytes", int(len(referenced)) * 8)
        STAT_ADD("wire.ws_req_bytes", sum(len(b) for b in req_out))
        req_in = t.alltoall(req_out, f"ws-req:{self.pass_id}@e{self.epoch}")
        # ranks outside the ownership live set contribute b"" placeholder
        # slots (membership-aware alltoall), never decodable payloads
        live = set(self.ownership.live_ranks)
        req_keys = [
            host_codec.decode_key_stream(b) if h in live
            else np.zeros(0, np.uint64)
            for h, b in enumerate(req_in)
        ]

        # owner side: union, per-shard rank assignment (ascending key order)
        owned = (
            np.unique(np.concatenate([k for k in req_keys]))
            if any(len(k) for k in req_keys)
            else np.zeros(0, np.uint64)
        )
        shard_of = key_to_shard(owned, self.n_mesh_shards) - self.shard_lo
        counts = np.bincount(shard_of, minlength=self.shards_per_host)
        local_max = int(counts.max()) + 1 if len(owned) else 1
        cap = t.allreduce_max(local_max, f"ws-cap:{self.pass_id}@e{self.epoch}")
        cap = -(-cap // round_to) * round_to
        self.capacity = cap

        order = np.argsort(shard_of, kind="stable")  # keys sorted => rank order
        rank_in_shard = np.empty(len(owned), dtype=np.int64)
        starts = np.repeat(np.cumsum(counts) - counts, counts)
        rank_in_shard[order] = np.arange(len(owned), dtype=np.int64) - starts
        self.owned_shard_keys = np.split(
            owned[order], np.cumsum(counts)[:-1]
        )
        owned_rows = (
            (key_to_shard(owned, self.n_mesh_shards)) * cap + rank_in_shard
        )

        # build the local device slice: spliced from the carried device
        # table when one is live, else classic pull from the local host
        # table
        self.boundary_stats = None
        same_epoch = carrier is None or (
            getattr(carrier, "ownership_epoch", 0) == self.ownership.epoch
        )
        if carrier is not None and same_epoch and not carrier.flushed and len(owned):
            dev = self._finalize_spliced(table, carrier, cap)
        else:
            if carrier is not None:
                # no splice possible (empty pass, already flushed, or the
                # carrier's shard->host pinning predates this ownership
                # epoch): everything the carrier owes must land before the
                # classic pull reads host rows
                table.drain_pending()
            vals = (
                table.pull_or_create(owned)
                if len(owned)
                else np.zeros((0, table.layout.width), np.float32)
            )
            dev = np.zeros(
                (self.shards_per_host, cap, table.layout.width), np.float32
            )
            if len(owned):
                # guarded: reshape(0, -1) on a zero-width ownership range
                # cannot infer the trailing dim
                local_rows = shard_of * cap + rank_in_shard
                dev.reshape(self.shards_per_host * cap, -1)[local_rows] = vals

        # round 2: reply global rows for each requester's keys (their
        # order). Rows are shard*cap+rank, bounded by n_mesh_shards*cap —
        # the narrow-int codec downcasts to the width that bound needs
        # (uint16/uint32 in practice, never int64) and raises on overflow.
        # Always on, raw ablation included: the width byte self-describes.
        max_row = self.n_mesh_shards * cap - 1
        rep_out = []
        pos_all = np.searchsorted(owned, np.concatenate(req_keys)) if len(owned) else None
        off = 0
        for h in range(t.n_ranks):
            k = req_keys[h]
            if len(k):
                rep_out.append(
                    host_codec.encode_row_ids(
                        owned_rows[pos_all[off : off + len(k)]], max_row
                    )
                )
            else:
                rep_out.append(host_codec.encode_row_ids(np.zeros(0, np.int64), max_row))
            off += len(k)
        STAT_ADD(
            "wire.ws_rep_raw_bytes",
            8 * sum(len(k) for k in req_keys),
        )
        STAT_ADD("wire.ws_rep_bytes", sum(len(b) for b in rep_out))
        rep_in = t.alltoall(rep_out, f"ws-rep:{self.pass_id}@e{self.epoch}")

        # assemble local lookup over referenced keys; non-live slots carry
        # no keys (ownership routing never maps a shard to a dead rank)
        rows = np.empty(len(referenced), dtype=np.int64)
        for h in range(t.n_ranks):
            if h not in live:
                continue
            sel = owners == h
            got = host_codec.decode_row_ids(rep_in[h])
            rows[sel] = got

        # round 3 (gated): hotness bits for the adaptive ICI wire. Each
        # owner reads its LOCAL tier's decayed shows (shows_peek — pure,
        # never perturbs tier state) and replies one bit per requested key
        # in the requester's key order, packed 8 keys/byte. The round only
        # runs when the adaptive wire is engaged, so the ablation's host
        # exchange is byte-identical to the two-round historical one.
        from paddlebox_tpu.ops import wire_quant as _wq  # lazy: import cycle

        if _wq.ici_adaptive_engaged():
            thr = float(config.get_flag("ici_hot_show"))
            owned_hot = (
                (table.shows_peek(owned) >= thr)
                if len(owned)
                else np.zeros(0, bool)
            )
            hot_out = []
            off = 0
            for h in range(t.n_ranks):
                k = req_keys[h]
                bits = (
                    owned_hot[pos_all[off : off + len(k)]]
                    if len(k)
                    else np.zeros(0, bool)
                )
                hot_out.append(np.packbits(bits.astype(np.uint8)).tobytes())
                off += len(k)
            STAT_ADD("wire.ws_hot_bytes", sum(len(b) for b in hot_out))
            hot_in = t.alltoall(hot_out, f"ws-hot:{self.pass_id}@e{self.epoch}")
            hot = np.zeros(self.n_mesh_shards * cap, dtype=bool)
            for h in range(t.n_ranks):
                if h not in live:
                    continue
                sel = owners == h
                nk = int(sel.sum())
                if nk:
                    bits = np.unpackbits(
                        np.frombuffer(hot_in[h], np.uint8), count=nk
                    ).astype(bool)
                    hot[rows[sel]] = bits
            self.hot_rows = hot

        self.sorted_keys = referenced  # np.unique output: sorted
        self.row_of_sorted = rows
        self._finalized = True
        self._table = table
        return dev

    def _finalize_spliced(self, table: HostSparseTable, carrier, cap: int):
        """Per-device delta boundary over the carried shard blocks.

        Each local device splices keys surviving from the previous pass
        out of its own carried block (decay applied on device), pushes its
        departing slice to the LOCAL host table on a background thread,
        and uploads only its genuinely new keys — the multi-host analog of
        PassWorkingSet._finalize_spliced, with every step host-local by
        the stable key->shard->device pinning."""
        import jax
        import jax.numpy as jnp

        from paddlebox_tpu import config as _config
        from paddlebox_tpu.ops.wire_quant import send_rows

        W = table.layout.width
        spd = carrier.shards_per_dev
        stats = {"common": 0, "new": 0, "departed": 0}
        blocks = []
        for di, (dev, part) in enumerate(zip(carrier.devices, carrier.parts)):
            # this device's NEW keys + block-local rows
            ks, rows = [], []
            for j in range(spd):
                k = self.owned_shard_keys[di * spd + j]
                ks.append(k)
                rows.append(j * cap + np.arange(len(k), dtype=np.int64))
            new_keys = np.concatenate(ks) if ks else np.zeros(0, np.uint64)
            new_rows = np.concatenate(rows) if rows else np.zeros(0, np.int64)

            old_keys = part.ws.sorted_keys
            if len(old_keys):
                pos_in_old = np.searchsorted(old_keys, new_keys)
                pos_in_old = np.minimum(pos_in_old, len(old_keys) - 1)
                common = old_keys[pos_in_old] == new_keys
            else:
                pos_in_old = np.zeros(len(new_keys), np.int64)
                common = np.zeros(len(new_keys), bool)
            common_old = pos_in_old[common]
            in_new = np.zeros(len(old_keys), dtype=bool)
            in_new[common_old] = True
            leave_pos = np.nonzero(~in_new)[0]
            if len(leave_pos):
                part.push_departures_async(
                    table, old_keys[leave_pos], leave_pos
                )
            new_mask = ~common
            stats["common"] += int(common.sum())
            stats["new"] += int(new_mask.sum())
            stats["departed"] += len(leave_pos)

            with jax.default_device(dev):
                block = jnp.zeros((spd * cap, W), jnp.float32)
                if new_mask.any():
                    up = send_rows(
                        table.pull_or_create(new_keys[new_mask]),
                        table.layout,
                        str(_config.get_flag("wire_dtype")),
                    )
                    block = block.at[jnp.asarray(new_rows[new_mask])].set(up)
                if common.any():
                    block = block.at[jnp.asarray(new_rows[common])].set(
                        part.rows_for(common_old)
                    )
            blocks.append(block.reshape(spd, cap, W))
        self.boundary_stats = stats
        return jax.make_array_from_single_device_arrays(
            (self.n_mesh_shards, cap, W), carrier.sharding, blocks
        )

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """Batch keys -> GLOBAL row ids (int32); keys must be in the pass."""
        return lookup_rows(self.sorted_keys, self.row_of_sorted, keys)

    @property
    def padding_row(self) -> int:
        return self.capacity - 1

    @property
    def _finalized_ok(self) -> bool:
        return self._finalized

    def writeback(
        self,
        local_slice: np.ndarray,
        cancel: Optional[threading.Event] = None,
    ) -> None:
        """Flush THIS host's trained shard slice into its own host table —
        ownership == device placement, so nothing crosses hosts (EndPass
        parity, box_wrapper.cc:627). ``cancel`` (the overlapped-kick revert
        path) is checked between shard pushes: shards already pushed are
        covered by rollback's partial-writeback contract."""
        if self.owned_shard_keys is None or self.shards_per_host == 0:
            # a zero-width ownership range (uneven map, more ranks than
            # shards) trains nothing and owes the host table nothing
            return
        flat = np.asarray(local_slice).reshape(self.shards_per_host, self.capacity, -1)
        for s, keys in enumerate(self.owned_shard_keys):
            if cancel is not None and cancel.is_set():
                from paddlebox_tpu.table.sparse_table import WritebackCancelled

                raise WritebackCancelled(
                    sum(len(k) for k in self.owned_shard_keys[:s]),
                    sum(len(k) for k in self.owned_shard_keys),
                )
            if len(keys):
                self._table.push(keys, flat[s, : len(keys)])


def hot_shard_loads(table, ownership: OwnershipMap, rank: int) -> np.ndarray:
    """Hotness-weighted per-mesh-shard load of ``rank``'s owned range
    (float64, length ``hi - lo``) — the elastic planner's load vector.

    The same Parallax-style frequency prior the adaptive ICI wire reads:
    each owned key weighs its decayed show count (``shows_peek`` — pure,
    mem-tier only) plus a residency term from the tiered store's
    occupancy split (``tier_stats`` per-host-shard mem/disk rows): a key
    whose host shard is mostly disk-resident is cheaper to move and
    colder to serve, so it weighs half a mem-resident key. Migrating or
    carving by this vector moves *hot* load, not raw key counts — a
    joiner carved at its quantile cuts takes traffic, not tombstone mass.
    Deterministic from the local table state; callers allgather the
    per-rank slices into the global vector."""
    lo, hi = ownership.range_of(int(rank))
    if hi <= lo:
        return np.zeros(0, dtype=np.float64)
    keys = table.keys()
    mesh = key_to_shard(keys, ownership.n_mesh_shards)
    mine = (mesh >= lo) & (mesh < hi)
    keys, mesh = keys[mine], mesh[mine]
    if len(keys) == 0:
        return np.zeros(hi - lo, dtype=np.float64)
    st = table.tier_stats()
    mem = np.asarray(st["per_shard"]["mem_rows"], dtype=np.float64)
    disk = np.asarray(st["per_shard"]["disk_rows"], dtype=np.float64)
    frac_mem = np.where(mem + disk > 0, mem / np.maximum(mem + disk, 1.0), 1.0)
    host = key_to_shard(keys, table.n_shards)
    residency = 0.5 + 0.5 * frac_mem[host]
    w = residency + np.asarray(table.shows_peek(keys), dtype=np.float64)
    return np.bincount(mesh - lo, weights=w, minlength=hi - lo).astype(
        np.float64
    )
