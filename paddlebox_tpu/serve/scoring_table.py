"""Atomic-swap scoring table: the follower's serve-side model state.

The xbox/abacus serving fleet consumes the trainer's per-pass SaveDelta
stream and must never answer a request from a half-applied delta
(box_wrapper.cc publishes whole passes; the serving side swaps whole
models). This module gives the follower that all-or-nothing boundary:

- :class:`TableVersion` — one immutable published state (base + deltas
  1..delta_idx): sorted keys, a :class:`ReplicaCache` holding the rows,
  and the publish metadata (decay epoch, watermark timestamp) that the
  staleness metric is computed from.
- :class:`ScoringTable` — holds the currently served version behind a
  lock. :meth:`commit` builds the NEXT version completely off to the
  side and installs it with a single reference swap; scorers that
  grabbed the old version mid-request keep a complete consistent table.

The kill-mid-apply contract lives here: fault site ``serve.apply_delta``
fires after the next version is fully built but before the swap, so an
injected crash models a follower dying mid-apply — the served version
must remain the previous one, bit-for-bit (tests/test_serve.py pins it).

PR 19 adds the mesh-sharded hot tier (the PullSparseGPU analog for
serving): :class:`DeviceScoringTier` holds exact fp32 copies of the
version's hottest rows (decayed-show >= ``device_tier_hot_show``, the
same ``shows_peek`` signal the adaptive ICI wire uses), sharded over the
mesh with ``NamedSharding`` so each chip owns 1/N of them; lookups route
through the sharded-pull collective with ``serve_key_bucket``-bucketed
request shapes, and only tier misses fall back to the host
:meth:`TableVersion.lookup_rows`. The tier is built inside
:meth:`ScoringTable.commit` (fault site ``serve.tier_build`` sits at the
start of that build) and rides the version object itself, so tier and
host rows install under the SAME single reference swap — a crash
mid-tier-build can never surface a partial tier.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import numpy as np

from paddlebox_tpu import config
from paddlebox_tpu.table.replica_cache import ReplicaCache
from paddlebox_tpu.table.sparse_table import key_to_shard
from paddlebox_tpu.utils.faultinject import fire as _fault_fire
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_SET

try:
    import jax
# optional-dependency gate: without jax the tier degrades to host-only
# pbox-lint: disable=EXC007
except Exception:  # pragma: no cover
    jax = None


class DeviceScoringTier:
    """Device-resident hot-row tier of one TableVersion. Immutable after
    build (same contract as the version itself): per-shard sorted key
    arrays stay on the host for routing, the row blocks live on device
    sharded over the mesh axis, and lookups run the sharded-pull
    collective with shape-bucketed requests.
    """

    def __init__(self, plan, keys: np.ndarray, rows: np.ndarray):
        from paddlebox_tpu.data.device_pack import _round_bucket
        from paddlebox_tpu.parallel.mesh import put_sharded

        self.plan = plan
        self.n_shards = plan.n_devices
        self.width = int(rows.shape[1])
        keys = np.asarray(keys, dtype=np.uint64)
        owner = key_to_shard(keys, self.n_shards)
        counts = np.bincount(owner, minlength=self.n_shards)
        # +1 reserves a guaranteed zero padding row per shard; rounding to
        # serve_row_bucket bounds the distinct table shapes across commits
        cap = _round_bucket(
            int(counts.max()) + 1 if len(keys) else 1,
            int(config.get_flag("serve_row_bucket")),
        )
        block = np.zeros((self.n_shards, cap, self.width), dtype=np.float32)
        self._shard_keys: List[np.ndarray] = []
        for s in range(self.n_shards):
            sel = np.nonzero(owner == s)[0]
            sk = keys[sel]
            order = np.argsort(sk)
            self._shard_keys.append(sk[order])
            block[s, : len(sk)] = rows[sel][order]
        self.pad_rank = cap - 1
        self.table = put_sharded(plan, block)  # [n_shards, cap, width] on dp
        self.n_rows = int(len(keys))
        self._pull_cache: dict = {}  # K -> compiled collective, guarded-by GIL
        # per-tier hit/miss tallies for the health gossip (the STAT_ADD
        # counters are process-global; gossip wants per-rank numbers)
        self._stat_lock = threading.Lock()
        self.hits = 0  # guarded-by: _stat_lock
        self.misses = 0  # guarded-by: _stat_lock

    def mem_used_mb(self) -> float:
        cap = self.pad_rank + 1
        return self.n_shards * cap * self.width * 4 / 1024.0 / 1024.0

    def _pull_fn(self, K: int):
        fn = self._pull_cache.get(K)
        if fn is None:
            from jax.sharding import PartitionSpec as P

            from paddlebox_tpu.parallel.mesh import shard_map
            from paddlebox_tpu.parallel.sharded_pullpush import (
                sharded_serve_pull,
            )

            plan = self.plan
            axis = plan.axis

            def body(table_block, req_block):
                # per device: table_block [1, cap, W], req_block [1, n, K]
                return sharded_serve_pull(
                    table_block[0], req_block[0], axis_name=axis
                )[None]

            fn = jax.jit(
                shard_map(
                    body,
                    mesh=plan.mesh,
                    in_specs=(P(axis), P(axis)),
                    out_specs=P(axis),
                )
            )
            self._pull_cache[K] = fn
        return fn

    def lookup_rows(self, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Tier rows for uint64 ``keys``: (rows [n, width], hit bool [n]).

        Hit rows are bitwise the committed version's rows (the tier stores
        exact fp32 copies and the pull is a pure routed gather); miss rows
        are zero and the caller falls back to the host version.
        """
        from paddlebox_tpu.data.device_pack import route_serve_requests

        q = np.asarray(keys, dtype=np.uint64)
        m = len(q)
        out = np.zeros((m, self.width), dtype=np.float32)
        hit = np.zeros(m, dtype=bool)
        local = np.zeros(m, dtype=np.int64)
        if m and self.n_rows:
            owner = key_to_shard(q, self.n_shards)
            for s in range(self.n_shards):
                sel = np.nonzero(owner == s)[0]
                sk = self._shard_keys[s]
                if len(sel) == 0 or len(sk) == 0:
                    continue
                pos = np.searchsorted(sk, q[sel])
                pos = np.minimum(pos, len(sk) - 1)
                h = sk[pos] == q[sel]
                hit[sel] = h
                local[sel] = pos
            idx = np.nonzero(hit)[0]
            if len(idx):
                req, pos, K = route_serve_requests(
                    owner[idx],
                    local[idx],
                    self.n_shards,
                    int(config.get_flag("serve_key_bucket")),
                    self.pad_rank,
                )
                pulled = np.asarray(self._pull_fn(K)(self.table, req))
                out[idx] = pulled.reshape(-1, self.width)[pos]
        n_hit = int(np.count_nonzero(hit))
        with self._stat_lock:
            self.hits += n_hit
            self.misses += m - n_hit
        return out, hit


class TableVersion:
    """One immutable served state. Never mutated after construction —
    that immutability is what makes the ScoringTable swap atomic."""

    __slots__ = (
        "date",
        "delta_idx",
        "decay_epoch",
        "published_unix",
        "keys",
        "cache",
        "rows",
        "params",
        "opt_state",
        "device_tier",
        "first_served_unix",
    )

    def __init__(
        self,
        date: Optional[str],
        delta_idx: int,
        decay_epoch: int,
        published_unix: Optional[float],
        keys: np.ndarray,
        cache: ReplicaCache,
        params=None,
        opt_state=None,
        device_tier: Optional[DeviceScoringTier] = None,
    ):
        self.date = date
        self.delta_idx = delta_idx
        self.decay_epoch = decay_epoch
        self.published_unix = published_unix
        self.keys = keys  # uint64 [n], sorted
        self.cache = cache
        # the dense params this sparse state pairs with (the cursor pairs
        # them on the producer side; carrying them IN the version keeps the
        # pair atomic under the same swap — a crash between dense load and
        # commit can never serve new dense over old sparse)
        self.params = params
        self.opt_state = opt_state
        # the mesh-sharded hot tier (None = host-only serving); built by
        # commit() so it installs under the same atomic swap as the rows
        self.device_tier = device_tier
        # materialized once (versions are immutable) so lookups are a
        # searchsorted + fancy-index, not a per-request stack
        self.rows = cache.host_array()  # f32 [n, width]
        # stamped by the server the first time a request is answered from
        # this version; (first_served - published) IS the train-to-serve
        # staleness the soak reports. Single batcher thread writes it.
        self.first_served_unix: Optional[float] = None

    @property
    def n_rows(self) -> int:
        return len(self.keys)

    def lookup_rows(self, keys: np.ndarray) -> Tuple[np.ndarray, int]:
        """Rows for uint64 ``keys``; returns (rows [n, width], miss count).

        Missing keys get the zero row: a key the published model has never
        seen scores from a cold embedding, exactly like a fresh-created
        (pre-first-push) trainer row with zero counters would after the
        show/clk CVM transform zeroes out.
        """
        q = np.asarray(keys, dtype=np.uint64)
        out = np.zeros((len(q), self.cache.dim), dtype=np.float32)
        n_miss = len(q)
        if len(self.keys) and len(q):
            pos = np.searchsorted(self.keys, q)
            pos = np.minimum(pos, len(self.keys) - 1)
            hit = self.keys[pos] == q
            out[hit] = self.rows[pos[hit]]
            n_miss = int(np.count_nonzero(~hit))
        if n_miss:
            # the zero-row fallback is intentional but must never be
            # silent: an all-miss request usually means a key-hash or
            # lineage bug, and only the counter makes that visible
            STAT_ADD("serve.key_misses", n_miss)
        return out, n_miss

    def lookup_rows_tiered(
        self, keys: np.ndarray
    ) -> Tuple[np.ndarray, int, int]:
        """The serve-side miss-fallback ladder: device tier first, host
        rows for tier misses. Returns (rows [n, width], tier misses, key
        misses) — bitwise-equal rows to :meth:`lookup_rows` always,
        because the tier stores exact copies of the same rows.

        Counter split: ``serve.device_tier_misses`` counts keys the hot
        tier did not hold (answered from the host path), while the
        existing ``serve.key_misses`` keeps counting keys the published
        model has never seen at all (zero-row fallback) — tier misses
        are a capacity/hotness signal, key misses a lineage signal.
        """
        if self.device_tier is None:
            rows, n_key_miss = self.lookup_rows(keys)
            return rows, 0, n_key_miss
        q = np.asarray(keys, dtype=np.uint64)
        rows, hit = self.device_tier.lookup_rows(q)
        n_hit = int(np.count_nonzero(hit))
        n_tier_miss = len(q) - n_hit
        if n_hit:
            STAT_ADD("serve.device_tier_hits", n_hit)
        n_key_miss = 0
        if n_tier_miss:
            STAT_ADD("serve.device_tier_misses", n_tier_miss)
            cold = ~hit
            rows[cold], n_key_miss = self.lookup_rows(q[cold])
        return rows, n_tier_miss, n_key_miss


def _empty_version(width: int) -> TableVersion:
    return TableVersion(
        date=None,
        delta_idx=-1,
        decay_epoch=0,
        published_unix=None,
        keys=np.zeros(0, dtype=np.uint64),
        cache=ReplicaCache(width),
    )


# one mesh plan per process for serve tiers: versions come and go every
# commit, the device topology does not. None after a failed probe = no
# mesh available, the tier degrades to host-only for the process lifetime.
_tier_plan = None
_tier_plan_probed = False
_tier_plan_lock = threading.Lock()


def _serve_mesh_plan():
    global _tier_plan, _tier_plan_probed
    with _tier_plan_lock:
        if not _tier_plan_probed:
            _tier_plan_probed = True
            try:
                if jax is None:
                    raise RuntimeError("jax unavailable")
                from paddlebox_tpu.parallel.mesh import make_mesh

                _tier_plan = make_mesh()
            # degrade-clean gate: any backend/mesh failure means host-only
            # serving, never a serving outage
            # pbox-lint: disable=EXC007
            except Exception:
                _tier_plan = None
                STAT_ADD("serve.device_tier_unavailable")
        return _tier_plan


def build_device_tier(
    keys: np.ndarray, rows: np.ndarray, hotness: np.ndarray
) -> Optional[DeviceScoringTier]:
    """Select the hot rows and place them on the mesh; None when no mesh
    is available (host-only degrade). Runs inside the commit() build
    window — the ``serve.tier_build`` fault site fires at the start, so a
    mid-build crash aborts the whole commit before anything is visible.
    """
    plan = _serve_mesh_plan()
    if plan is None:
        return None
    _fault_fire("serve.tier_build")  # window: tier building, nothing visible
    hotness = np.asarray(hotness, dtype=np.float32)
    idx = np.nonzero(hotness >= float(config.get_flag("device_tier_hot_show")))[0]
    cap = int(config.get_flag("device_tier_capacity"))
    if len(idx) > cap:
        # hottest rows win; sort keeps the selection deterministic under
        # show ties so a healed retry rebuilds the identical tier
        keep = np.argsort(-hotness[idx], kind="stable")[:cap]
        idx = np.sort(idx[keep])
    tier = DeviceScoringTier(plan, keys[idx], rows[idx])
    STAT_SET("serve.device_tier_rows", tier.n_rows)
    STAT_SET("serve.device_tier_mem_mb", tier.mem_used_mb())
    STAT_ADD("serve.device_tier_builds")
    return tier


class ScoringTable:
    """The follower's served table: an atomically swappable TableVersion.

    Readers call :meth:`version` once per request and use that object for
    the whole request; writers call :meth:`commit` with the complete next
    state. There is no in-place mutation path on purpose.
    """

    def __init__(self, width: int):
        self.width = width
        self._lock = threading.Lock()
        self._version: TableVersion = _empty_version(width)  # guarded-by: _lock
        self._history: List[int] = []  # guarded-by: _lock  (committed delta idxs)

    def version(self) -> TableVersion:
        with self._lock:
            return self._version

    def committed_indices(self) -> List[int]:
        """Delta indices in commit order (monotonicity probe for tests)."""
        with self._lock:
            return list(self._history)

    def commit(
        self,
        keys: np.ndarray,
        rows: np.ndarray,
        *,
        date: str,
        delta_idx: int,
        decay_epoch: int,
        published_unix: Optional[float] = None,
        params=None,
        opt_state=None,
        hotness: Optional[np.ndarray] = None,
    ) -> TableVersion:
        """Build and install the next version, all-or-nothing.

        ``keys`` must be sorted uint64 with ``rows`` aligned ([n, width]).
        ``hotness`` (decayed shows aligned with ``keys``, the follower's
        ``shows_peek``) opts this version into the device scoring tier;
        None keeps the host-only path bitwise (the ablation default).
        Everything expensive (cache build, row materialization, the device
        tier) happens BEFORE the swap; the swap itself is one reference
        assignment under the lock. A crash anywhere before it (the
        ``serve.tier_build`` and ``serve.apply_delta`` fault sites sit in
        that window) leaves the previous version served.
        """
        cache = ReplicaCache(self.width)
        if len(rows):
            cache.add_batch(rows)
        tier = None
        if hotness is not None and len(keys):
            tier = build_device_tier(
                np.asarray(keys, dtype=np.uint64),
                np.asarray(rows, dtype=np.float32),
                hotness,
            )
        nxt = TableVersion(
            date=date,
            delta_idx=delta_idx,
            decay_epoch=decay_epoch,
            published_unix=published_unix,
            keys=np.asarray(keys, dtype=np.uint64),
            cache=cache,
            params=params,
            opt_state=opt_state,
            device_tier=tier,
        )
        _fault_fire("serve.apply_delta")  # window: built, not yet visible
        with self._lock:
            self._version = nxt
            self._history.append(delta_idx)
        cache.publish_serve_stats()
        STAT_SET("serve.version_delta_idx", delta_idx)
        STAT_ADD("serve.version_commits")
        return nxt
