"""PassSupervisor: the self-healing pass/day loop.

The repo has had the recovery *pieces* for a while — PassGuard
confirm/revert (train/rollback.py, FleetWrapper::Confirm/Revert parity),
retry-until-open on flaky inputs (utils/fs.py, data_feed.cc:2738-2740
parity), NaN-batch containment in the device step, and day-level
base+delta resume (train/checkpoint.py). What production actually needs is
the layer that COMPOSES them: a multi-day CTR run survives a bad pass
because something notices, reverts, retries, and — when retries don't help
— falls back to the last durable state and re-enters. That layer is
``PassSupervisor``.

One supervised pass runs:

    load (fs retries inside) -> begin_pass(enable_revert) [guard armed]
      -> prepare_pass -> train_pass -> health gates -> end_pass [confirm]
      -> optional checkpoint publish (base/delta, manifest-verified)

Any exception or gate rejection reverts the pass (bit-exact: retraining
after revert equals a never-interrupted run, pinned by
tests/test_rollback.py) and retries under bounded exponential backoff.
When ``max_retries`` is exhausted the supervisor escalates once: it
restores the last durable checkpoint state via ``CheckpointManager.
resume()`` (manifest-verified, torn-snapshot fallback) and re-enters with
a fresh retry budget. Every action lands in a structured incident log —
``self.incidents``, process-wide counters in utils/monitor, and instant
events in the utils/trace timeline.

Health gates (the "pass is poisoned" detectors the reference applies by
operator convention):

- NaN gate: the ratio of NaN-skipped batches (the step's containment
  counter) must stay under ``nan_ratio_max`` — a pass that skims over too
  many poisoned batches is itself poisoned.
- AUC floor: the pass AUC must not fall more than ``auc_floor_margin``
  below the trailing mean of the last ``auc_window`` CONFIRMED passes
  (only consulted after ``auc_min_history`` confirmations, so a cold
  start can't self-reject).

Poison awareness: corruption is NOT a transient fault. A load that
quarantined data beyond the admission thresholds (data/quarantine.py)
surfaces as ``DataPoisonedError`` — deterministic, because retrying the
same filelist replays the same corruption — so the supervisor resolves it
BEFORE the retry loop, without burning a single backoff retry, under the
``on_poisoned_pass`` policy: ``fail`` (raise, with a ``data_poisoned``
incident naming the dead-letter file), ``skip_pass`` (drop the pass's
data, keep the day), or ``degrade`` (train the pass with the quarantined
records dropped; the loss fraction lands in the incident and the pass
metrics). In coordinated runs the corrupt-fraction verdict rides the
same allgather as the pass/load verdicts, so every rank admits or
rejects in lockstep.

Distributed coordination (``transport=`` + :class:`EpochCoordinator`):
when the supervisor drives one rank of a multi-host run, a pass must
commit or revert GLOBALLY — one rank confirming a pass its peer reverted
leaves the host tables permanently diverged. So before ``end_pass`` every
rank publishes a verdict (my gates passed / my attempt raised) on a
control tag scoped by the current pass epoch; any NO — including a peer
that simply stopped answering, which times out the exchange — turns into
a :class:`CoordinatedAbort` on the healthy ranks, and every rank walks
the same revert path, bumps the same pass epoch (stale frames of the
aborted attempt are discarded by tag), and retries in lockstep. The
retried pass then runs over exactly the data + table state a clean run
would see, so its result is bitwise-equal to a never-faulted run
(tests/test_chaos_dist.py). Load failures coordinate the same way before
anything is armed. Escalation stays lockstep for free: verdicts are
global, every rank exhausts the same retry budget on the same attempt.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from paddlebox_tpu import config
from paddlebox_tpu.data.quarantine import DataPoisonedError
from paddlebox_tpu.obs.flight_recorder import FLIGHT_RECORDER
from paddlebox_tpu.obs.metrics_writer import MetricsWriter
from paddlebox_tpu.parallel import membership as _membership
from paddlebox_tpu.parallel.transport import PeerDeadError
from paddlebox_tpu.train.checkpoint import MembershipEpochError, rank_root
from paddlebox_tpu.utils.faultinject import InjectedFault
from paddlebox_tpu.utils.faultinject import fire as _fault_fire
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_OBSERVE, STAT_SET
from paddlebox_tpu.utils.trace import PROFILER

# incident kinds that end a pass (or the day) rather than healing in
# place: each one flushes the flight recorder into an incident bundle
_FATAL_INCIDENT_KINDS = ("data_poisoned", "peer_abort", "gave_up")

# PBTX control tags of the elastic join protocol (grow half). The
# announce is an un-epoched knock — the joiner does not know the fleet's
# clocks yet, so the tag cannot carry them; the offer is addressed per
# joiner rank so a concurrent second announcer can never consume another
# rank's admission.
_JOIN_ANNOUNCE_TAG = "ctl:join:announce"
_JOIN_OFFER_TAG = "ctl:join:offer"

config.define_flag(
    "supervisor_max_retries",
    2,
    "revert+retry attempts per pass before the supervisor escalates to a "
    "checkpoint resume (and, failing that, gives up)",
)
config.define_flag(
    "on_poisoned_pass",
    "fail",
    "supervisor policy when a pass's load quarantined data beyond the "
    "admission thresholds (DataPoisonedError — deterministic, never "
    "retried): 'fail' raises, 'skip_pass' drops the pass and continues "
    "the day, 'degrade' trains over the pass with the quarantined "
    "records dropped (loss fraction recorded in the incident and the "
    "pass metrics)",
)


class PassRejected(RuntimeError):
    """A health gate rejected an otherwise-completed pass."""

    def __init__(self, gate: str, detail: str):
        super().__init__(f"pass rejected by {gate} gate: {detail}")
        self.gate = gate
        self.detail = detail


class PassFailure(RuntimeError):
    """The supervisor exhausted retries AND escalation for one pass."""


class CoordinatedAbort(RuntimeError):
    """A peer rank voted NO on this pass (its gate fired or its attempt
    raised), or the verdict exchange itself failed — this rank's locally
    healthy attempt must revert so the cluster retries in lockstep."""

    def __init__(self, detail: str):
        super().__init__(f"pass aborted by peer verdict: {detail}")
        self.detail = detail


class EpochCoordinator:
    """Control-plane verdict exchange + pass-epoch bookkeeping for one rank.

    ``exchange_verdict`` is an allgather on tag ``ctl:verdict:<key>@e<N>``
    (payload ``b"\\x01"`` = ok, ``b"\\x00" + detail`` = abort): it returns
    the GLOBAL verdict, and treats its own transport failure/timeout as an
    abort vote — a rank that cannot hear its peers must not confirm.
    ``advance`` bumps the epoch after a revert and raises the transport's
    stale-frame floor, so nothing a reverted attempt left in flight can
    reach the retried attempt's exchanges (the epoch suffix is the same
    ``@e<N>`` convention DistributedWorkingSet tags carry)."""

    def __init__(self, transport, timeout: Optional[float] = None):
        self.transport = transport
        self.timeout = timeout
        self.epoch = 0
        # elastic mode re-raises PeerDeadError instead of folding it into
        # an abort vote: a dead peer is a MEMBERSHIP event (verdict round,
        # ownership shrink, adoption), not a retryable pass failure — the
        # supervisor's death handler owns it. Off by default so
        # non-elastic runs keep the historical fail-as-abort behavior.
        self.raise_peer_dead = False

    def exchange_verdict(
        self, key: str, ok: bool, detail: str = "", fatal: bool = False
    ):
        """Returns (global_ok, detail) after every rank has voted.

        ``fatal=True`` re-raises a LOCAL transport failure/timeout instead
        of folding it into a NO vote. A commit-point exchange (the migrate
        epoch flip) must use it: a rank that times out cannot tell whether
        its peers committed, and quietly voting NO while they did leaves
        this rank serving the old map against their new one — split-brain
        the epoch integer can't detect. Better to die loudly and be shrunk
        out by the survivors."""
        payload = b"\x01" if ok else b"\x00" + detail.encode()[:512]
        tag = f"ctl:verdict:{key}@e{self.epoch}"
        try:
            votes = self.transport.allgather(payload, tag, timeout=self.timeout)
        except PeerDeadError as e:
            if self.raise_peer_dead:
                raise
            STAT_ADD("supervisor_verdict_exchange_errors")
            return False, f"verdict exchange failed: {e!r}"
        except (OSError, TimeoutError) as e:
            STAT_ADD("supervisor_verdict_exchange_errors")
            if fatal:
                raise
            return False, f"verdict exchange failed: {e!r}"
        # membership-confirmed dead ranks contribute b"" placeholder slots,
        # not NO votes
        live_fn = getattr(self.transport, "live_ranks", None)
        live = set(live_fn()) if live_fn is not None else set(
            range(self.transport.n_ranks)
        )
        bad = [
            f"rank {r}: {v[1:].decode(errors='replace') or 'aborted'}"
            for r, v in enumerate(votes)
            if r in live and v[:1] != b"\x01"
        ]
        if bad:
            return False, "; ".join(bad)
        return True, ""

    def advance(self, epoch: Optional[int] = None) -> None:
        """Enter the next pass epoch (or adopt the dataset's counter, which
        revert_pass bumps — keeping the two in lockstep)."""
        self.epoch = self.epoch + 1 if epoch is None else epoch
        self.transport.discard_epochs_below(self.epoch)


@dataclass
class ElasticConfig:
    """Opt-in elastic membership for a coordinated supervisor.

    ``shared_root`` is the day root every rank publishes its checkpoint
    tree under (``rank-<r>`` subdirs, checkpoint.rank_root): the adoption
    path opens a DEAD rank's tree read-only through it. ``migrate_skew``
    > 1.0 additionally arms planned migration: at a confirmed pass
    boundary, when the max/mean per-rank key-load ratio crosses it, the
    supervisor recuts ownership boundaries and streams the moving ranges
    (see docs/ROBUSTNESS.md, "Elastic membership & key migration").

    The grow half (docs/ROBUSTNESS.md, "Elastic grow & autoscale"):
    ``initial_live`` names the ranks actually RUNNING at day start when
    the transport's endpoint list reserves slots for future joiners —
    the supervisor marks the others dead and installs the even ownership
    split over the initial set. ``target_ranks`` is the autoscale
    ceiling: a waiting joiner is admitted at a published pass boundary
    only while the live count is below it (None admits whenever one
    knocks). ``hot_migrate`` switches the planned-migration load vector
    from raw key counts to the Parallax-style hotness prior (tier
    residency + decayed shows, table/dist_ws.hot_shard_loads) — the
    joiner carve is ALWAYS hotness-weighted."""

    shared_root: str
    migrate_skew: float = 0.0  # <= 1.0 disables planned migration
    adopt_retries: int = 2
    member_timeout: Optional[float] = None
    target_ranks: Optional[int] = None
    initial_live: Optional[Sequence[int]] = None
    hot_migrate: bool = False


@dataclass
class HealthGates:
    nan_ratio_max: float = 0.05
    auc_window: int = 5
    auc_min_history: int = 3
    auc_floor_margin: float = 0.05
    auc_absolute_floor: Optional[float] = None


@dataclass
class RetryPolicy:
    max_retries: Optional[int] = None  # None -> supervisor_max_retries flag
    backoff_s: float = 0.5
    backoff_mult: float = 2.0
    backoff_max_s: float = 30.0
    # injectable for tests (chaos schedules must not wall-clock sleep)
    sleep: Callable[[float], None] = field(default=time.sleep, repr=False)

    @property
    def retries(self) -> int:
        if self.max_retries is not None:
            return self.max_retries
        return int(config.get_flag("supervisor_max_retries"))

    def backoff(self, attempt: int) -> float:
        return min(
            self.backoff_s * self.backoff_mult ** max(0, attempt - 1),
            self.backoff_max_s,
        )


@dataclass
class Incident:
    """One structured entry of the supervisor's incident log."""

    pass_seq: int
    date: Optional[str]
    kind: str      # load_error | train_error | gate_nan | gate_auc |
                   # prefetch_error | ckpt_save_error | escalate_resume |
                   # gave_up | skipped | peer_abort | data_poisoned |
                   # rank_death | migrate | migrate_abort | rank_join |
                   # join_abort
    action: str    # retry | revert_retry | resume | raise | skip
    attempt: int
    detail: str = ""
    wall_time: float = field(default_factory=time.time)

    def as_dict(self) -> Dict[str, Any]:
        return {
            "pass_seq": self.pass_seq,
            "date": self.date,
            "kind": self.kind,
            "action": self.action,
            "attempt": self.attempt,
            "detail": self.detail,
            "wall_time": self.wall_time,
        }


class PassSupervisor:
    """Fault-tolerant driver for the pass/day loop of one trainer.

    ``checkpoint`` (a CheckpointManager) enables both the escalation path
    and the per-pass publishing ``run_day`` performs; without it the
    supervisor still reverts/retries but gives up when retries exhaust.
    """

    def __init__(
        self,
        dataset,
        trainer,
        checkpoint=None,
        gates: Optional[HealthGates] = None,
        retry: Optional[RetryPolicy] = None,
        round_to: int = 512,
        shrink: bool = True,
        on_give_up: str = "raise",  # raise | skip (drop the pass, keep the day)
        transport=None,
        on_poisoned: Optional[str] = None,  # None -> on_poisoned_pass flag
        elastic: Optional[ElasticConfig] = None,
    ):
        if on_give_up not in ("raise", "skip"):
            raise ValueError(f"on_give_up must be 'raise' or 'skip', got {on_give_up!r}")
        if on_poisoned not in (None, "fail", "skip_pass", "degrade"):
            raise ValueError(
                "on_poisoned must be None, 'fail', 'skip_pass' or "
                f"'degrade', got {on_poisoned!r}"
            )
        self.ds = dataset
        self.tr = trainer
        self.table = dataset.table
        self.checkpoint = checkpoint
        self.gates = gates or HealthGates()
        self.retry = retry or RetryPolicy()
        # multi-rank: verdict exchange + epoch bookkeeping; a single-rank
        # transport needs no coordination
        self.coord = (
            EpochCoordinator(transport)
            if transport is not None and getattr(transport, "n_ranks", 1) > 1
            else None
        )
        if self.coord is not None:
            self.coord.epoch = getattr(dataset, "pass_epoch", 0)
        # elastic membership: a dead peer becomes a verdict round + owner-
        # ship shrink + shard adoption instead of a dead day. Requires the
        # coordinator (single-rank runs have no membership to lose) and a
        # dataset that carries an OwnershipMap.
        self.elastic = elastic
        if elastic is not None and self.coord is not None:
            self.coord.raise_peer_dead = True
            tp = self.coord.transport
            if elastic.initial_live is not None:
                # the endpoint list reserves slots for FUTURE joiners: only
                # initial_live ranks are running now. Mark the rest dead so
                # collectives don't wait on empty slots, and start from the
                # even ownership split over the actual fleet.
                live0 = sorted(int(r) for r in elastic.initial_live)
                if tp.rank not in live0:
                    raise ValueError(
                        f"rank {tp.rank} is not in initial_live {live0} — "
                        "a rank outside the initial fleet joins via "
                        "join_day, not run_day"
                    )
                tp.mark_dead([r for r in range(tp.n_ranks) if r not in live0])
                if getattr(dataset, "ownership", None) is None:
                    dataset.ownership = _membership.OwnershipMap.even_over(
                        dataset.n_mesh_shards, live0
                    )
            omap0 = getattr(dataset, "ownership", None)
            STAT_SET(
                "membership.epoch", omap0.epoch if omap0 is not None else 0
            )
            STAT_SET(
                "membership.live_ranks",
                len(omap0.live_ranks) if omap0 is not None else tp.n_ranks,
            )
        # set when ownership flipped mid-chain: the next checkpoint save
        # re-anchors with a base (a delta must not straddle an epoch flip)
        self._force_base = False
        # the map the LAST ownership flip replaced: adoption falls back to
        # it when a dead rank's chain predates the flip (it died before
        # its own re-anchor save committed)
        self._prev_ownership = None
        self.round_to = round_to
        self.shrink = shrink
        self.on_give_up = on_give_up
        self._on_poisoned = on_poisoned
        # poisoned pass admitted under the degrade policy: the next
        # begin_pass (and any revert-retry of it) must bypass the gate
        self._admit_poisoned = False
        # default the dataset's dead-letter dir under the durable root so
        # quarantined records live next to the checkpoints they shadow
        if (
            checkpoint is not None
            and getattr(dataset, "quarantine_dir", "absent") is None
        ):
            dataset.quarantine_dir = os.path.join(checkpoint.root, "quarantine")
        # a supervisor without a device fails here, at construction, with
        # the typed error — not mid-pass; then the persistent compile
        # cache, placed by utils/compilecache's rule (never under the
        # checkpoint root: a root that moves is a cache that never hits)
        from paddlebox_tpu.utils import backendguard, compilecache

        self.backend = backendguard.bring_up()
        compilecache.enable()
        # telemetry plane: metric series + incident bundles live under the
        # durable checkpoint root (obs/) so postmortems travel with the
        # artifacts they explain; without a checkpoint both stay off
        # unless the obs_incident_dir flag points somewhere explicitly
        self.metrics: Optional[MetricsWriter] = None
        self._incident_dir: Optional[str] = None
        if checkpoint is not None:
            obs_dir = os.path.join(checkpoint.root, "obs")
            rank = getattr(transport, "rank", 0) if transport is not None else 0
            self.metrics = MetricsWriter(obs_dir, rank=rank)
            self._incident_dir = os.path.join(obs_dir, "incidents")
        self.incidents: List[Incident] = []
        self._auc_history: deque = deque(maxlen=self.gates.auc_window)
        self._pass_seq = 0
        self._date: Optional[str] = None
        # (date, tuple(files)) of the pass whose load this supervisor kicked
        # into the dataset's boundary feed stage. The marker doubles as the
        # "set_date already consumed" record: a kicked pass's set_date runs
        # at kick time, so the adopting (or falling-back) run_pass must NOT
        # call it again — pass_id would double-bump and shift the load's
        # sampling/shuffle seeds off the sequential run's.
        self._prefetch: Optional[tuple] = None

    # ---- incident log ----------------------------------------------------

    def _record(self, kind: str, action: str, attempt: int, detail: str = "") -> Incident:
        inc = Incident(
            pass_seq=self._pass_seq,
            date=self._date,
            kind=kind,
            action=action,
            attempt=attempt,
            detail=detail,
        )
        self.incidents.append(inc)
        STAT_ADD("supervisor_incidents")
        # one literal per kind (MON005): the incident vocabulary is closed
        # (Incident.kind docstring), so the metric family stays enumerable
        if kind == "load_error":
            STAT_ADD("supervisor_load_error")
        elif kind == "prefetch_error":
            STAT_ADD("supervisor_prefetch_error")
        elif kind == "data_poisoned":
            STAT_ADD("supervisor_data_poisoned")
        elif kind == "ckpt_save_error":
            STAT_ADD("supervisor_ckpt_save_error")
        elif kind == "peer_abort":
            STAT_ADD("supervisor_peer_abort")
        elif kind == "train_error":
            STAT_ADD("supervisor_train_error")
        elif kind == "escalate_resume":
            STAT_ADD("supervisor_escalate_resume")
        elif kind == "gave_up":
            STAT_ADD("supervisor_gave_up")
        elif kind == "gate_nan":
            STAT_ADD("supervisor_gate_nan")
        elif kind == "gate_auc":
            STAT_ADD("supervisor_gate_auc")
        elif kind == "rank_death":
            STAT_ADD("supervisor_rank_death")
        elif kind == "migrate":
            STAT_ADD("supervisor_migrate")
        elif kind == "migrate_abort":
            STAT_ADD("supervisor_migrate_abort")
        elif kind == "rank_join":
            STAT_ADD("supervisor_rank_join")
        elif kind == "join_abort":
            STAT_ADD("supervisor_join_abort")
        else:  # pragma: no cover - new kinds must be added above
            STAT_ADD("supervisor_other")
        PROFILER.instant(f"supervisor:{kind}", inc.as_dict())
        if kind in _FATAL_INCIDENT_KINDS and action != "degrade":
            # the pass is lost: publish the last N spans + stat snapshot
            # + this incident as an atomic incident-<ts>.json bundle
            FLIGHT_RECORDER.dump(
                f"supervisor_{kind}", detail, dir_path=self._incident_dir
            )
        return inc

    # ---- pieces ----------------------------------------------------------

    def _load_with_retry(self, date: Optional[str], files: Sequence[str]) -> None:
        for attempt in range(self.retry.retries + 1):
            try:
                if date is not None:
                    self.ds.set_date(date)
                self.ds.set_filelist(list(files))
                self.ds.load_into_memory()
                return
            except Exception as e:
                # the fs tier already burned its own retry-until-open
                # budget; reaching here means the input is still missing
                # or the reader died mid-stream
                if attempt >= self.retry.retries:
                    self._record("load_error", "raise", attempt, repr(e))
                    raise PassFailure(
                        f"load failed after {attempt + 1} attempts: {e}"
                    ) from e
                self._record("load_error", "retry", attempt, repr(e))
                self.retry.sleep(self.retry.backoff(attempt + 1))

    def _kick_prefetch(self, date: Optional[str], files: Sequence[str]) -> None:
        """Stage the NEXT pass's load behind the live pass's training.

        Kicks the dataset's boundary feed pipeline — threaded read, key
        premerge, gated host-row prefetch (see BoxPSDataset.
        _stage_boundary_prefetch) — on the preload thread, so by the time
        ``run_pass`` reaches the next pass its data is already staged.
        Opportunistic: any failure here is an incident, never an attempt
        failure — the next ``run_pass`` falls back to a synchronous load.
        Coordinated (multi-rank) runs don't kick: the load there is itself
        a lockstep verdict exchange that must stay on the pass boundary.
        """
        if self.coord is not None or not config.get_flag("boundary_pipeline"):
            return
        key = (date, tuple(files))
        try:
            if date is not None and self._prefetch != key:
                self.ds.set_date(date)
            # marker set as soon as set_date is consumed: even if the kick
            # dies right after, the fallback load must skip set_date
            self._prefetch = key
            self.ds.set_filelist(list(files))
            self.ds.preload_into_memory()
        except Exception as e:
            self._record("prefetch_error", "deferred", 0, repr(e))

    def _adopt_prefetch(self, date: Optional[str], files: Sequence[str]) -> None:
        """Consume (or cancel) a previously kicked prefetch, then ensure the
        pass's data is staged — falling back to the synchronous retrying
        load when the kick failed, was reverted away, or targeted a
        different pass."""
        marker, self._prefetch = self._prefetch, None
        key = (date, tuple(files))
        if marker == key:
            staged = False
            try:
                self.ds.wait_preload_done()
                # a revert (or a failed kick) may have discarded the staged
                # slot after the marker was set — verify before trusting it
                staged = self.ds._staged is not None
            except Exception as e:
                self._record("prefetch_error", "retry", 0, repr(e))
                self.ds.discard_staged()
            if not staged:
                # set_date already consumed at kick time: date=None
                self._load_with_retry(None, files)
            return
        if marker is not None:
            # stale kick — the caller changed the schedule; cancel it
            try:
                self.ds.wait_preload_done()
            except Exception:
                # the staged load is discarded either way, but a failed
                # one is still a failed load: count it, don't erase it
                STAT_ADD("supervisor_stale_preload_errors")
            self.ds.discard_staged()
        self._load_with_retry(date, files)

    @property
    def on_poisoned(self) -> str:
        """Effective poisoned-pass policy (constructor arg wins, else the
        on_poisoned_pass flag)."""
        v = self._on_poisoned or str(config.get_flag("on_poisoned_pass"))
        if v not in ("fail", "skip_pass", "degrade"):
            raise ValueError(
                f"on_poisoned_pass must be fail|skip_pass|degrade, got {v!r}"
            )
        return v

    def _poison_report(self) -> Optional[Dict[str, Any]]:
        """The dataset's admission verdict for the loaded pass (None for
        datasets without the quarantine surface, e.g. test doubles)."""
        rep_fn = getattr(self.ds, "admission_report", None)
        return rep_fn() if rep_fn is not None else None

    def _handle_poisoned(
        self, detail: str, rep: Optional[Dict[str, Any]]
    ) -> bool:
        """Apply the on_poisoned policy to an already-global poison verdict.
        True -> proceed with the pass (degrade), False -> drop it
        (skip_pass); the fail policy raises DataPoisonedError."""
        policy = self.on_poisoned
        loss = ""
        if rep is not None and (rep["bad_lines"] or rep["bad_files"]):
            loss = (
                f" (loss: {rep['bad_lines']} lines / {rep['bad_files']} "
                f"files, line_fraction={rep['line_fraction']:.5f})"
            )
        if policy == "degrade":
            self._record("data_poisoned", "degrade", 0, detail + loss)
            self._admit_poisoned = True
            return True
        if policy == "skip_pass":
            self._record("data_poisoned", "skip", 0, detail + loss)
            drop = getattr(self.ds, "drop_pass_data", None)
            if drop is not None:
                drop()
            return False
        self._record("data_poisoned", "raise", 0, detail + loss)
        raise DataPoisonedError(
            detail, report=rep, dead_letter=(rep or {}).get("dead_letter")
        )

    def _gate(self, out: Dict[str, float]) -> None:
        g = self.gates
        batches = out.get("batches", 0.0)
        if batches:
            ratio = out.get("nan_batches", 0.0) / batches
            if ratio > g.nan_ratio_max:
                raise PassRejected(
                    "nan",
                    f"{ratio:.3f} of batches NaN-skipped "
                    f"(max {g.nan_ratio_max:.3f})",
                )
        auc = out.get("auc")
        if auc is None or not np.isfinite(auc):
            return
        if g.auc_absolute_floor is not None and auc < g.auc_absolute_floor:
            raise PassRejected(
                "auc", f"auc {auc:.4f} under absolute floor {g.auc_absolute_floor:.4f}"
            )
        if len(self._auc_history) >= g.auc_min_history:
            floor = float(np.mean(self._auc_history)) - g.auc_floor_margin
            if auc < floor:
                raise PassRejected(
                    "auc",
                    f"auc {auc:.4f} under trailing floor {floor:.4f} "
                    f"(window of {len(self._auc_history)} confirmed passes)",
                )

    def _attempt(
        self, n_batches: Optional[int], prefetch: Optional[tuple] = None
    ) -> Dict[str, float]:
        """One armed begin->train->gate->[global verdict]->confirm cycle."""
        err: Optional[Exception] = None
        out: Dict[str, float] = {}
        try:
            if not self.ds._in_pass:
                # first attempt, or a revert re-armed the in-memory data.
                # admit_poisoned only reaches datasets that know the kwarg
                # (and only under the degrade policy) — test doubles and
                # older datasets keep their plain signature
                kw = {"admit_poisoned": True} if self._admit_poisoned else {}
                self.ds.begin_pass(
                    round_to=self.round_to, enable_revert=True, trainer=self.tr,
                    **kw,
                )
            self.tr.prepare_pass(self.ds, n_batches)
            if prefetch is not None:
                # training is about to occupy the device: stage the next
                # pass's load/premerge/prefetch behind it
                self._kick_prefetch(prefetch[0], prefetch[1])
            out = self.tr.train_pass(self.ds, n_batches=n_batches)
            # the trained table just landed: kick the host writeback now so
            # it overlaps the gate/verdict window instead of blocking the
            # boundary. Safe pre-verdict — the armed guard's revert covers
            # partial writeback, and revert_pass cancels the kick.
            if hasattr(self.ds, "kick_writeback"):
                self.ds.kick_writeback(self.tr.trained_table())
            self._gate(out)
        except Exception as e:
            if self.coord is None:
                raise
            # hold the local failure until the verdict is published: peers
            # are (or soon will be) waiting on this rank's vote, and only
            # a NO that every rank hears aborts the pass everywhere
            err = e
        if self.coord is not None:
            ok, detail = self.coord.exchange_verdict(
                f"pass:{self._pass_seq}", err is None, repr(err) if err else ""
            )
            if err is not None:
                raise err
            if not ok:
                raise CoordinatedAbort(detail)
        # confirm ONLY after the global verdict: the guard is still armed
        # up to here, so every rank that must revert still can
        # classic (host) writeback: a guard is armed, so the carried-table
        # boundary is gated off anyway — hand over the host copy
        self.ds.end_pass(self.tr.trained_table(), shrink=self.shrink)
        return out

    def _revert(self, attempt: int, cause: BaseException) -> None:
        if isinstance(cause, PassRejected):
            kind = f"gate_{cause.gate}"
        elif isinstance(cause, CoordinatedAbort):
            kind = "peer_abort"
        else:
            kind = "train_error"
        try:
            self.ds.revert_pass()
        except Exception as e:
            # an unrevertable pass (guard lost, revert itself died) can
            # only be healed by the durable tier
            self._record(kind, "revert_failed", attempt, f"{cause!r}; revert: {e!r}")
            raise PassFailure(f"revert failed after {cause!r}: {e}") from e
        self._record(kind, "revert_retry", attempt, repr(cause))

    def _escalate(self, attempt: int, cause: BaseException) -> None:
        """Resume the last durable (manifest-verified) state and re-enter."""
        state = self.checkpoint.resume(self.table, self.tr)
        # external overwrite of table rows + dense params: the trainer's
        # cached device state is stale now
        self.tr._state = None
        self.tr._state_ws = None
        self._record(
            "escalate_resume", "resume", attempt, f"{cause!r} -> resumed {state}"
        )

    def _save_checkpoint(self, mode: str) -> None:
        assert self.checkpoint is not None
        for attempt in range(self.retry.retries + 1):
            try:
                if mode == "base" or self._force_base:
                    # an ownership flip mid-day re-anchors the chain: the
                    # old chain's deltas cover the pre-flip key ranges and
                    # must not be extended across the epoch
                    self.checkpoint.save_base(self._date, self.table, self.tr)
                    self._force_base = False
                else:
                    self.checkpoint.save_delta(self._date, self.table, self.tr)
                return
            except MembershipEpochError as e:
                # belt-and-braces: the cursor says the chain predates this
                # rank's ownership epoch — re-anchor instead of retrying
                # the refused delta
                self._record("ckpt_save_error", "retry", attempt, repr(e))
                self._force_base = True
            except Exception as e:
                # atomic publishing means a failed attempt left nothing
                # under a final name — a retry starts clean
                if attempt >= self.retry.retries:
                    self._record("ckpt_save_error", "raise", attempt, repr(e))
                    raise PassFailure(
                        f"checkpoint {mode} save failed after "
                        f"{attempt + 1} attempts: {e}"
                    ) from e
                self._record("ckpt_save_error", "retry", attempt, repr(e))
                self.retry.sleep(self.retry.backoff(attempt + 1))
        raise PassFailure(
            f"checkpoint {mode} save failed: retry budget exhausted "
            "re-anchoring across an ownership-epoch flip"
        )

    # ---- elastic membership ---------------------------------------------

    def _ownership_map(self):
        """The dataset's current OwnershipMap, defaulting to the even
        split over all transport ranks (epoch 0) when none was installed
        yet — identical to what DistributedWorkingSet defaults to."""
        omap = getattr(self.ds, "ownership", None)
        if omap is None:
            omap = _membership.OwnershipMap.even(
                self.ds.n_mesh_shards, self.coord.transport.n_ranks
            )
        return omap

    def _install_ownership(self, new_map, prev_map=None) -> None:
        """Atomically adopt a successor OwnershipMap: dataset routing,
        checkpoint epoch, and the chain re-anchor flip together.

        The re-anchor base save happens HERE, before any training resumes
        under the new map — not at the next pass boundary. Deferring it
        opens a window where a rank that dies mid-pass leaves a chain
        predating the flip: shard ranges it gained in the flip would be
        absent from (or stale in) that chain, and adoption would silently
        restore them from the seeded init. A rank whose re-anchor save
        itself fails raises (PassFailure after retries) and is shrunk out
        by the survivors, whose adoption then uses the previous owners'
        chains for its un-anchored gained ranges (``_prev_ownership``).

        ``prev_map`` overrides what is recorded as the map this flip
        replaced — the membership round passes its SYNCED base so every
        survivor records the same predecessor, even one that re-entered
        the round a map behind its peers."""
        self._prev_ownership = (
            prev_map if prev_map is not None else self._ownership_map()
        )
        self.ds.ownership = new_map
        if self.checkpoint is not None:
            self.checkpoint.ownership_epoch = new_map.epoch
            self.checkpoint.live_ranks = [int(r) for r in new_map.live_ranks]
        self._force_base = True
        STAT_SET("membership.epoch", new_map.epoch)
        STAT_SET("membership.live_ranks", len(new_map.live_ranks))
        if self.checkpoint is not None and self._date is not None:
            self._save_checkpoint("base")

    def _handle_rank_death(self, e: PeerDeadError) -> None:
        """Survivor-side membership change: verdict round -> map sync ->
        shrunk map -> shard adoption from the dead ranks' durable
        checkpoint shards.

        Re-entrant under further deaths: a peer dying WHILE the round runs
        surfaces as a nested PeerDeadError from any of its collectives;
        rather than killing the day, the new evidence is unioned into the
        dead set and the whole round re-runs from the refreshed set —
        bounded by the rank count, since each re-entry strictly grows it.

        On return the retried pass runs on the survivors over exactly the
        table state a fresh shrunk-membership run would hold (adoption is
        an idempotent upsert from the last pass boundary, and keys never
        checkpointed are recreated from the seeded init — both bitwise-
        equal to the fresh run, pinned by tests/test_elastic.py)."""
        assert self.elastic is not None and self.coord is not None
        tp = self.coord.transport
        last = e
        for round_no in range(tp.n_ranks + 1):
            tp.mark_dead(last.dead)
            try:
                self._membership_round(last)
                return
            except PeerDeadError as nested:
                last = nested
                self._record(
                    "rank_death", "retry", round_no,
                    f"peer died mid-membership-round: {nested!r}",
                )
        raise PassFailure(
            f"membership change did not converge within {tp.n_ranks + 1} "
            f"rounds; last evidence: {last!r}"
        ) from last

    def _membership_round(self, e: PeerDeadError) -> None:
        """One attempt of the membership change; raises PeerDeadError when
        yet another peer dies mid-round (caller unions and re-enters)."""
        tp = self.coord.transport
        # revert anything the dying attempt armed before touching the table
        if getattr(self.ds, "_in_pass", False):
            try:
                self.ds.revert_pass()
            except Exception as re_err:
                self._record(
                    "rank_death", "revert_failed", 0,
                    f"{e!r}; revert: {re_err!r}",
                )
                raise PassFailure(
                    f"revert failed after peer death {e!r}: {re_err}"
                ) from re_err
        self.coord.advance(getattr(self.ds, "pass_epoch", None))
        # membership verdict round: every survivor converges on one dead
        # set (the proposal is encoded in the collective tag)
        agreed = _membership.agree_membership(
            tp, self._pass_seq, timeout=self.elastic.member_timeout
        )
        # map sync: a survivor whose PREVIOUS round was cut short by this
        # death re-enters one map behind its peers; all derive the
        # successor from the highest-epoch base so epochs and boundaries
        # agree everywhere (divergent same-epoch maps raise — split-brain)
        old_map = self._ownership_map()
        base_map = _membership.sync_map(
            tp, self._pass_seq, agreed, old_map,
            timeout=self.elastic.member_timeout,
        )
        # adoption sources are judged against MY installed map: a rank
        # that missed an intermediate flip never adopted its pieces, so
        # for it each dead rank's range is the wider pre-flip one
        newly_dead = [d for d in agreed if old_map.is_live(d)]
        new_map = base_map.shrink(agreed)
        my_rank = tp.rank
        adopted_ranges = []
        for d in newly_dead:
            dlo, dhi = old_map.range_of(d)
            mlo, mhi = new_map.range_of(my_rank)
            lo, hi = max(dlo, mlo), min(dhi, mhi)
            if lo < hi:
                adopted_ranges.append([lo, hi])
        # adoption: bounded retries in ISOLATION — the pass must not retry
        # under a half-installed map (keys routed to a dead owner would
        # silently vanish from the exchange)
        adopt_err: Optional[Exception] = None
        adopted_keys = 0
        for a in range(self.elastic.adopt_retries + 1):
            try:
                adopted_keys = sum(
                    _membership.adopt_dead_shards(
                        self.table, self.elastic.shared_root, d,
                        old_map, new_map, my_rank,
                        prev_map=self._prev_ownership,
                    )
                    for d in newly_dead
                )
                adopt_err = None
                break
            except Exception as ae:
                adopt_err = ae
                if a < self.elastic.adopt_retries:
                    self._record("rank_death", "retry", a, repr(ae))
                    self.retry.sleep(self.retry.backoff(a + 1))
        # every survivor must finish adopting before anyone re-enters the
        # pass — and one survivor failing adoption aborts all (the dead
        # ranges would be served by nobody). The tag carries the successor
        # map's epoch AND content fingerprint: post-sync these are
        # identical everywhere, so a mismatch can only mean a protocol
        # bug — it stalls loudly instead of committing divergent maps.
        ok, detail = self.coord.exchange_verdict(
            f"member:{self._pass_seq}:{new_map.epoch}:{new_map.fingerprint()}",
            adopt_err is None,
            repr(adopt_err) if adopt_err else "",
        )
        if adopt_err is not None:
            self._record("rank_death", "raise", 0, repr(adopt_err))
            raise PassFailure(
                f"shard adoption failed after {self.elastic.adopt_retries + 1} "
                f"attempts: {adopt_err}"
            ) from adopt_err
        if not ok:
            self._record("rank_death", "raise", 0, detail)
            raise PassFailure(f"peer shard adoption failed: {detail}")
        self._install_ownership(new_map, prev_map=base_map)
        self._record(
            "rank_death", "revert_retry", 0,
            f"dead={list(agreed)} survivors={list(new_map.live_ranks)} "
            f"ownership_epoch={new_map.epoch} adopted_keys={adopted_keys}",
        )
        bundle = {
            "dead": [int(d) for d in agreed],
            "survivors": [int(r) for r in new_map.live_ranks],
            "ownership_epoch": new_map.epoch,
            "adopted_ranges": adopted_ranges,
            "adopted_keys": int(adopted_keys),
        }
        FLIGHT_RECORDER.note_incident("membership_change", bundle)
        FLIGHT_RECORDER.dump(
            "rank_death", json.dumps(bundle), dir_path=self._incident_dir
        )
        PROFILER.instant("supervisor:membership_change", bundle)

    def _gather_shard_loads(
        self, omap, hot: bool, tag: str
    ) -> np.ndarray:
        """Allgather the global per-mesh-shard load vector under ``omap``.

        Each live rank contributes exactly its owned slice as little-
        endian float64 (8 bytes/shard). ``hot=False`` counts raw owned
        keys; ``hot=True`` weighs them by the Parallax-style hotness
        prior — tiered residency + decayed show counts, computed by
        table/dist_ws.hot_shard_loads — so planners move traffic, not
        tombstone mass. Either way the vector is deterministic from the
        boundary's table state, so every rank derives the identical plan
        from the identical gather."""
        from paddlebox_tpu.table.sparse_table import key_to_shard

        tp = self.coord.transport
        # the carried device table may hold rows the host store lags on —
        # planners read host rows, so everything owed must land first
        drain = getattr(self.table, "drain_pending", None)
        if drain is not None:
            drain()
        lo, hi = omap.range_of(tp.rank)
        if hot:
            from paddlebox_tpu.table.dist_ws import hot_shard_loads

            local = hot_shard_loads(self.table, omap, tp.rank)
        else:
            keys = self.table.keys()
            sh = key_to_shard(keys, omap.n_mesh_shards)
            mine = sh[(sh >= lo) & (sh < hi)]
            local = np.bincount(mine - lo, minlength=hi - lo).astype(
                np.float64
            )
        views = tp.allgather(
            local.astype("<f8").tobytes(), tag,
            timeout=self.elastic.member_timeout,
        )
        loads = np.zeros(omap.n_mesh_shards, np.float64)
        for r in omap.live_ranks:
            rlo, rhi = omap.range_of(r)
            v = views[r]
            if len(v) != (rhi - rlo) * 8:
                # never recut from a silently zero-filled view: the plan
                # would be deterministic (all ranks see the same garbage)
                # yet systematically wrong
                STAT_ADD("membership.load_view_errors")
                raise RuntimeError(
                    f"load view from rank {r} has {len(v)} bytes, expected "
                    f"{(rhi - rlo) * 8} for shard range [{rlo},{rhi})"
                )
            loads[rlo:rhi] = np.frombuffer(v, dtype="<f8")
        return loads

    def _maybe_migrate(self) -> None:
        """Planned migration at a confirmed pass boundary: recut ownership
        boundaries when per-rank key-load skew crosses the threshold and
        stream the moving shard ranges owner->owner. Atomic at the
        boundary: receivers stage, a commit verdict decides, and only a
        global YES flips the epoch — any failure leaves the old epoch
        serving and the plan is re-derived at the next boundary."""
        assert self.elastic is not None and self.coord is not None
        tp = self.coord.transport
        omap = self._ownership_map()
        if len(omap.live_ranks) < 2:
            return
        loads = self._gather_shard_loads(
            omap, self.elastic.hot_migrate,
            f"ctl:load:{self._pass_seq}@e{self.coord.epoch}",
        )
        new_map = _membership.plan_rebalance(
            omap, loads, self.elastic.migrate_skew
        )
        if new_map is None:
            # every rank derived None from the identical global vector —
            # no verdict round needed for a unanimous no-op
            return
        seq = f"{self._pass_seq}.{new_map.epoch}"
        xfer = None
        xfer_err: Optional[Exception] = None
        try:
            xfer = _membership.migrate_ranges(
                tp, self.table, omap, new_map, seq, self.coord.epoch,
                timeout=self.elastic.member_timeout,
            )
        except Exception as me:
            xfer_err = me
        # the commit verdict must be ATOMIC: a rank whose verdict round
        # merely times out cannot tell whether peers committed, so folding
        # the timeout into a local "no" would leave it on the old map while
        # peers flip — colliding epoch numbers over divergent boundaries.
        # fatal=True makes local transport failure here raise instead; this
        # rank dies with PassFailure and the survivors shrink it out. The
        # tag carries the successor map's content fingerprint so bases that
        # diverged for any other reason stall loudly rather than commit.
        try:
            ok, detail = self.coord.exchange_verdict(
                f"migrate:{seq}:{new_map.fingerprint()}",
                xfer_err is None,
                repr(xfer_err) if xfer_err else "",
                fatal=True,
            )
        except PeerDeadError:
            raise  # a DEAD peer is decidable — membership handling owns it
        except (OSError, TimeoutError) as ve:
            STAT_ADD("membership.migrations_aborted")
            self._record("migrate_abort", "raise", 0, repr(ve))
            raise PassFailure(
                f"migrate commit verdict uncertain (transport failure "
                f"mid-round): {ve!r}"
            ) from ve
        if not ok or xfer_err is not None:
            # old epoch still serves; staged pieces are discarded and the
            # plan is re-derived at the next boundary (FLT008 contract)
            STAT_ADD("membership.migrations_aborted")
            self._record(
                "migrate_abort", "retry", 0,
                detail or repr(xfer_err),
            )
            return
        _membership.commit_staged(self.table, xfer["staged"])
        self._install_ownership(new_map)
        STAT_ADD("membership.migrated_keys", int(xfer["recv_keys"]))
        STAT_ADD("membership.migration_bytes", int(xfer["sent_bytes"]))
        self._record(
            "migrate", "commit", 0,
            f"ownership_epoch={new_map.epoch} moves={xfer['moves']} "
            f"recv_keys={xfer['recv_keys']} sent_bytes={xfer['sent_bytes']}",
        )
        FLIGHT_RECORDER.note_incident(
            "migration", {
                "ownership_epoch": new_map.epoch,
                "moves": xfer["moves"],
                "recv_keys": int(xfer["recv_keys"]),
                "sent_bytes": int(xfer["sent_bytes"]),
            },
        )

    # ---- elastic grow: the join protocol --------------------------------

    def _boundary_elastic(self, publishing: bool) -> None:
        """One elastic action per confirmed pass boundary, the autoscale
        loop's decision point: admit a waiting joiner if the policy allows
        (and the chain it must catch up from is being published), else
        consider a planned hot-range migration. One action, not both — an
        admission already recut ownership at this boundary, and the next
        boundary re-derives skew under the grown map."""
        admitted = False
        if publishing:
            admitted = self._maybe_admit_joiner()
        if not admitted and self.elastic.migrate_skew > 1.0:
            self._maybe_migrate()

    def _maybe_admit_joiner(self) -> bool:
        """Boundary scan of the grow half: look for announce knocks from
        non-live ranks, converge the fleet on ONE joiner, and run the
        admission round. The scan rides an allgather and admits only the
        INTERSECTION of what every live rank saw — a knock still in
        flight to some peer admits at the next boundary instead of
        splitting the fleet. Returns True when a joiner was committed."""
        assert self.elastic is not None and self.coord is not None
        tp = self.coord.transport
        omap = self._ownership_map()
        pend = tp.pending_sources(_JOIN_ANNOUNCE_TAG)
        waiting = [int(r) for r in pend if not omap.is_live(r)]
        # consume the knocks now that they're counted: a waiting joiner
        # re-announces every few hundred ms, and unconsumed frames from an
        # already-admitted (or policy-refused) rank must not pile up
        for r in pend:
            while r in tp.pending_sources(_JOIN_ANNOUNCE_TAG):
                tp.recv(_JOIN_ANNOUNCE_TAG, r, timeout=1.0)
        views = tp.allgather(
            json.dumps(waiting).encode(),
            f"ctl:joinscan:{self._pass_seq}@e{self.coord.epoch}",
            timeout=self.elastic.member_timeout,
        )
        common: Optional[set] = None
        for r in omap.live_ranks:
            seen = set(json.loads(views[r].decode() or "[]"))
            common = seen if common is None else (common & seen)
        if not common:
            return False
        if (
            self.elastic.target_ranks is not None
            and len(omap.live_ranks) >= self.elastic.target_ranks
        ):
            # at (or above) the autoscale target: leave announcers waiting
            return False
        return self._admit_joiner(min(common), omap)

    def _admit_joiner(self, joiner: int, omap) -> bool:
        """Survivor side of one admission round.

        Hot loads are gathered among the CURRENT live set (the joiner
        owns nothing and has nothing to vote with yet), the successor map
        carves the joiner its quantile cuts, the lowest live rank
        sponsors the offer, and the ceding flanks stream their ranges
        through the staged ``migrate_ranges`` path. The commit verdict
        composes with the death invariants: the JOINER dying mid-round
        aborts the join cleanly at the old epoch (no shrink — the fleet
        never grew); a SURVIVOR dying aborts the join and re-raises so
        the caller's death handler runs the shrink."""
        tp = self.coord.transport
        loads = self._gather_shard_loads(
            omap, True, f"ctl:jload:{self._pass_seq}@e{self.coord.epoch}"
        )
        new_map = omap.grow(joiner, loads)
        planned = [
            [int(lo), int(hi)]
            for lo, hi, _src, dst in _membership.plan_moves(omap, new_map)
            if dst == joiner
        ]
        seq = f"{self._pass_seq}.{new_map.epoch}"
        # readmit BEFORE any collective that counts the joiner's slot.
        # Deliberately after the load gather: mark_alive keeps the link's
        # seq space (transport docstring), and a genuinely new incarnation
        # already reset its inbound counter at HELLO.
        tp.mark_alive(joiner)
        if tp.rank == min(omap.live_ranks):
            # one sponsor hands the joiner everything it needs to sync:
            # both maps, the day/pass clocks, and the pass epoch its
            # frames must carry
            offer = {
                "old_map": omap.to_json(),
                "new_map": new_map.to_json(),
                "date": self._date,
                "pass_seq": self._pass_seq,
                "pass_epoch": self.coord.epoch,
            }
            tp.send(
                joiner, f"{_JOIN_OFFER_TAG}:{joiner}",
                json.dumps(offer).encode(),
            )
        join_err: Optional[Exception] = None
        xfer = None
        try:
            xfer = _membership.migrate_ranges(
                tp, self.table, omap, new_map, seq, self.coord.epoch,
                timeout=self.elastic.member_timeout,
            )
        except Exception as me:
            join_err = me
        try:
            ok, detail = self.coord.exchange_verdict(
                f"join:{seq}:{new_map.fingerprint()}",
                join_err is None,
                repr(join_err) if join_err else "",
                fatal=True,
            )
        except PeerDeadError as e:
            tp.mark_dead([joiner])
            if set(int(d) for d in e.dead) <= {int(joiner)}:
                # ONLY the joiner died mid-join: clean local abort, the
                # fleet stays at the old epoch — no shrink round runs
                # because membership never actually grew
                self._join_abort(
                    joiner, new_map, planned, f"joiner died mid-join: {e!r}"
                )
                return False
            # a SURVIVOR died during the join: abort it, then let the
            # caller's death handler run the shrink over the old map
            self._join_abort(joiner, new_map, planned, repr(e))
            raise
        except (OSError, TimeoutError) as ve:
            # commit-point uncertainty: same contract as migrate — die
            # loudly rather than guess which side of the flip peers took
            self._join_abort(joiner, new_map, planned, repr(ve))
            raise PassFailure(
                f"join commit verdict uncertain (transport failure "
                f"mid-round): {ve!r}"
            ) from ve
        if not ok or join_err is not None:
            # the joiner (or a ceding flank) voted NO: nothing was
            # committed anywhere — receivers only staged — so the old
            # epoch keeps serving bitwise and the joiner may re-announce
            tp.mark_dead([joiner])
            self._join_abort(
                joiner, new_map, planned,
                detail if join_err is None else repr(join_err),
            )
            return False
        _membership.commit_staged(self.table, xfer["staged"])
        self._install_ownership(new_map, prev_map=omap)
        STAT_ADD("membership.joins_total")
        self._record(
            "rank_join", "commit", 0,
            f"joiner={int(joiner)} ownership_epoch={new_map.epoch} "
            f"planned_ranges={planned} sent_keys={xfer['sent_keys']}",
        )
        bundle = {
            "joiner": int(joiner),
            "live": [int(r) for r in new_map.live_ranks],
            "ownership_epoch": int(new_map.epoch),
            "planned_ranges": planned,
            "sent_keys": int(xfer["sent_keys"]),
        }
        FLIGHT_RECORDER.note_incident("rank_join", bundle)
        PROFILER.instant("supervisor:rank_join", bundle)
        return True

    def _join_abort(self, joiner: int, new_map, planned, reason) -> None:
        """Abort bookkeeping for a failed or refused admission. Nothing
        was committed (receivers only staged), so the fleet stays at the
        OLD epoch bitwise; the incident bundle — joiner rank, the ranges
        it would have taken, the epoch that never happened, and why —
        lands under <ckpt>/obs/incidents for the postmortem."""
        bundle = {
            "joiner": int(joiner),
            "planned_ranges": [[int(lo), int(hi)] for lo, hi in planned],
            "ownership_epoch": int(new_map.epoch),
            "reason": str(reason),
        }
        STAT_ADD("membership.joins_aborted")
        self._record("join_abort", "retry", 0, json.dumps(bundle))
        FLIGHT_RECORDER.note_incident("join_abort", bundle)
        FLIGHT_RECORDER.dump(
            "join_abort", json.dumps(bundle), dir_path=self._incident_dir
        )
        PROFILER.instant("supervisor:join_abort", bundle)

    # ---- elastic grow: the joiner side -----------------------------------

    def _announce_join(self) -> None:
        """Best-effort knock on every potential sponsor. Fires the
        ``membership.join_announce`` fault site (FLT008: an injected
        failure aborts nothing durable — the announce is simply retried).
        Unreachable peers are expected — the announcer does not know who
        is live; the survivors' scan intersects what actually arrived."""
        tp = self.coord.transport
        _fault_fire("membership.join_announce")
        for dst in range(tp.n_ranks):
            if dst == tp.rank or tp.is_marked_dead(dst):
                continue
            try:
                tp.send(dst, _JOIN_ANNOUNCE_TAG, b"")
            # a knock bouncing off a dead or not-yet-up peer is the
            # normal case — the announcer re-knocks every ~250ms and
            # the survivors' scan intersects what actually arrived
            # pbox-lint: disable=EXC007
            except (ConnectionError, OSError):
                continue

    def _await_offer(self, deadline: float) -> Optional[Dict[str, Any]]:
        """Announce (re-announcing every ~250ms) until a sponsor's offer
        arrives; None on deadline. Every queued offer is consumed and the
        NEWEST wins — a stale offer from an earlier aborted round must
        not shadow the live one (its maps would fingerprint-mismatch the
        fleet's verdict tag and stall the round out)."""
        tp = self.coord.transport
        tag = f"{_JOIN_OFFER_TAG}:{tp.rank}"
        last_announce = -1.0
        while True:
            now = time.monotonic()
            if now >= deadline:
                return None
            if now - last_announce >= 0.25:
                self._announce_join()
                last_announce = now
            payload = None
            srcs = tp.pending_sources(tag)
            while srcs:
                for s in srcs:
                    payload = tp.recv(tag, s, timeout=1.0)
                srcs = tp.pending_sources(tag)
            if payload is not None:
                return json.loads(payload.decode())
            time.sleep(0.02)

    def _catch_up(self, old_map, new_map) -> Dict[str, Any]:
        """Serve-follower catch-up: rebuild the gained ranges from the
        ceding owners' PUBLISHED base+delta chains — the Follower's CRC-
        verified chain apply (serve/follower.apply_published_chain),
        including mid-chain epoch re-anchors: a valid watermark is always
        single-epoch (validate_watermark rejects straddles), so a chain
        that re-anchored mid-day is simply read from its newest base.

        Returns per-piece (keys, rows) in ``plan_moves`` order — aligned
        1:1 with what ``migrate_ranges`` stages — plus the ceding owners'
        decay-epoch clock. Fires ``membership.catchup_apply`` once per
        ceding source (FLT008: an injected failure aborts the join at the
        OLD epoch — nothing was committed — and a retried join
        succeeds)."""
        from paddlebox_tpu.serve.follower import apply_published_chain
        from paddlebox_tpu.table.sparse_table import (
            HostSparseTable,
            key_to_shard,
        )

        me = self.coord.transport.rank
        pieces = [
            (lo, hi, src)
            for lo, hi, src, dst in _membership.plan_moves(old_map, new_map)
            if dst == me
        ]
        scratches: Dict[int, Any] = {}
        decay_epochs = 0
        keys_by_piece: List[np.ndarray] = []
        rows_by_piece: List[np.ndarray] = []
        for lo, hi, src in pieces:
            if src not in scratches:
                _fault_fire("membership.catchup_apply")
                scratch = HostSparseTable(
                    self.table.layout, self.table.opt,
                    n_shards=self.table.n_shards,
                )
                state = apply_published_chain(
                    rank_root(self.elastic.shared_root, src), scratch
                )
                if state is None:
                    raise RuntimeError(
                        f"ceding rank {src} has no published chain under "
                        f"{self.elastic.shared_root!r} — cannot catch up"
                    )
                scratches[src] = scratch
                decay_epochs = max(
                    decay_epochs, getattr(scratch, "decay_epochs", 0)
                )
            scratch = scratches[src]
            keys = np.sort(scratch.keys())
            sh = key_to_shard(keys, old_map.n_mesh_shards)
            sel = keys[(sh >= lo) & (sh < hi)]
            keys_by_piece.append(sel)
            rows_by_piece.append(
                scratch.pull_or_create(sel)
                if len(sel)
                else np.zeros((0, self.table.layout.width), np.float32)
            )
        return {
            "keys_by_piece": keys_by_piece,
            "rows_by_piece": rows_by_piece,
            "decay_epochs": int(decay_epochs),
            "keys": int(sum(len(k) for k in keys_by_piece)),
        }

    def _verify_catchup(self, catchup: Dict[str, Any], staged) -> None:
        """Bitwise cross-check, chain vs wire: at a published boundary
        the ceding owner's chain IS its table state, so the rows the
        joiner rebuilt from disk must equal the rows it was streamed —
        any divergence means a torn chain or a protocol bug, and the join
        must abort (the migrated copy is never trusted on faith)."""
        if len(staged) != len(catchup["keys_by_piece"]):
            raise RuntimeError(
                f"catch-up derived {len(catchup['keys_by_piece'])} pieces "
                f"but the transfer staged {len(staged)}"
            )
        for i, (mkeys, mrows) in enumerate(staged):
            ckeys = catchup["keys_by_piece"][i]
            crows = catchup["rows_by_piece"][i]
            if not (
                np.array_equal(mkeys, ckeys) and np.array_equal(mrows, crows)
            ):
                raise RuntimeError(
                    f"catch-up/transfer divergence on piece {i}: the "
                    "published chain and the live migration disagree "
                    f"({len(ckeys)} chain keys vs {len(mkeys)} wire keys)"
                )

    def _join_attempt(self, offer: Dict[str, Any]) -> bool:
        """One admission attempt from a sponsor's offer (joiner side).

        Sync the fleet's clocks, mark the ranks the successor map says
        are dead, catch up from the published chains, receive the staged
        transfer, cross-check the two bitwise, then vote in the commit
        round. Once the offer is consumed this rank MUST vote — peers
        block on its verdict slot, so every local failure (including a
        dead ceding peer) folds into a NO vote rather than a silent bail;
        only the verdict exchange itself failing abandons the round."""
        tp = self.coord.transport
        me = tp.rank
        old_map = _membership.OwnershipMap.from_json(offer["old_map"])
        new_map = _membership.OwnershipMap.from_json(offer["new_map"])
        # adopt the fleet's clocks BEFORE any collective: verdict tags are
        # scoped by pass_seq and pass epoch
        self._pass_seq = int(offer["pass_seq"])
        self._date = offer["date"]
        epoch = int(offer["pass_epoch"])
        self.coord.epoch = epoch
        if hasattr(self.ds, "pass_epoch"):
            self.ds.pass_epoch = epoch
        tp.discard_epochs_below(epoch)
        dead = [
            r for r in range(tp.n_ranks)
            if r != me and not new_map.is_live(r)
        ]
        if dead:
            tp.mark_dead(dead)
        seq = f"{self._pass_seq}.{new_map.epoch}"
        planned = [
            [int(lo), int(hi)]
            for lo, hi, _src, dst in _membership.plan_moves(old_map, new_map)
            if dst == me
        ]
        join_err: Optional[Exception] = None
        xfer = None
        catchup = None
        try:
            catchup = self._catch_up(old_map, new_map)
            xfer = _membership.migrate_ranges(
                tp, self.table, old_map, new_map, seq, epoch,
                timeout=self.elastic.member_timeout,
            )
            self._verify_catchup(catchup, xfer["staged"])
        except Exception as e:
            # includes PeerDeadError: peers still block on this slot's
            # verdict, so fold the failure into a NO vote
            join_err = e
        try:
            ok, detail = self.coord.exchange_verdict(
                f"join:{seq}:{new_map.fingerprint()}",
                join_err is None,
                repr(join_err) if join_err else "",
                fatal=True,
            )
        except PeerDeadError as e:
            # the fleet itself lost a rank mid-round: the survivors will
            # shrink and re-offer; go back to announcing
            tp.mark_dead(e.dead)
            self._record(
                "join_abort", "retry", 0, f"sponsor fleet lost a rank: {e!r}"
            )
            return False
        except (OSError, TimeoutError) as ve:
            raise PassFailure(
                f"join commit verdict uncertain (transport failure "
                f"mid-round): {ve!r}"
            ) from ve
        if not ok or join_err is not None:
            self._join_abort(
                me, new_map, planned,
                detail if join_err is None else repr(join_err),
            )
            return False
        _membership.commit_staged(self.table, xfer["staged"])
        if catchup["decay_epochs"] and not getattr(
            self.table, "decay_epochs", 0
        ):
            # the carved rows' decay clock must match their previous
            # owner's, or the first decay after the join drifts off a
            # fresh fixed-size run
            self.table.decay_epochs = catchup["decay_epochs"]
        self._install_ownership(new_map, prev_map=old_map)
        STAT_ADD("membership.joins_total")
        self._record(
            "rank_join", "commit", 0,
            f"joiner={me} ownership_epoch={new_map.epoch} "
            f"recv_keys={xfer['recv_keys']} catchup_keys={catchup['keys']}",
        )
        bundle = {
            "joiner": int(me),
            "live": [int(r) for r in new_map.live_ranks],
            "ownership_epoch": int(new_map.epoch),
            "planned_ranges": planned,
            "recv_keys": int(xfer["recv_keys"]),
            "catchup_keys": int(catchup["keys"]),
        }
        FLIGHT_RECORDER.note_incident("rank_join", bundle)
        PROFILER.instant("supervisor:rank_join", bundle)
        return True

    def join_day(
        self,
        pass_files: Sequence[Sequence[str]],
        n_batches: Optional[int] = None,
        publish: bool = True,
        timeout: float = 60.0,
    ) -> List[Optional[Dict[str, float]]]:
        """JOINER-side day entrypoint: the grow dual of ``run_day``.

        Announce -> await a sponsor's offer -> catch up from the ceding
        owners' published base+delta chains (the serve follower's CRC-
        verified chain apply) -> receive the carved ranges through the
        staged migrate path -> global fingerprint-tagged commit verdict
        -> durable base re-anchor (``_install_ownership``) -> run the
        REMAINING passes of the day in lockstep with the fleet. An
        aborted admission (injected fault mid-catch-up, a refused
        verdict, a survivor death mid-round) leaves the fleet at the old
        epoch bitwise and this rank simply re-announces; ``timeout``
        bounds the total wait for admission.

        Saves are always deltas: the admission itself re-anchored a base
        at the new epoch, so the joiner's chain starts there and
        ``save_delta``'s refuse-to-straddle rule is satisfied by
        construction."""
        if self.elastic is None or self.coord is None:
            raise ValueError(
                "join_day requires elastic mode and a coordinated transport"
            )
        deadline = time.monotonic() + timeout
        while True:
            if time.monotonic() >= deadline:
                raise PassFailure(
                    f"rank {self.coord.transport.rank} was not admitted "
                    f"within {timeout:.1f}s"
                )
            try:
                offer = self._await_offer(deadline)
                if offer is None:
                    continue
                if self._join_attempt(offer):
                    break
            except InjectedFault as e:
                # an injected announce/catch-up fault is retryable: note
                # it and knock again (FLT008 recovery contract)
                self._record("join_abort", "retry", 0, repr(e))
            self.retry.sleep(0.01)
        outs: List[Optional[Dict[str, float]]] = []
        do_save = publish and self.checkpoint is not None
        start = self._pass_seq
        for p in range(start, len(pass_files)):
            files = pass_files[p]
            nxt = (
                (self._date, tuple(pass_files[p + 1]))
                if p + 1 < len(pass_files)
                else None
            )
            outs.append(
                self.run_pass(
                    files, date=self._date, n_batches=n_batches,
                    save="delta" if do_save else None, prefetch=nxt,
                )
            )
            try:
                self._boundary_elastic(do_save)
            except PeerDeadError as e:
                self._handle_rank_death(e)
            if self.metrics is not None:
                self.metrics.maybe_snapshot()
        return outs

    # ---- the supervised pass --------------------------------------------

    def run_pass(
        self,
        files: Sequence[str],
        date: Optional[str] = None,
        n_batches: Optional[int] = None,
        save: Optional[str] = None,  # None | "base" | "delta"
        prefetch: Optional[tuple] = None,  # (date, files) of the NEXT pass
    ) -> Optional[Dict[str, float]]:
        """Load, train, gate, and publish one pass, healing failures.

        ``prefetch`` names the pass that follows this one: once training is
        underway its load is kicked into the dataset's boundary feed stage,
        and the next ``run_pass`` over the same (date, files) adopts the
        staged result instead of loading synchronously (``run_day`` threads
        this automatically).

        Returns the pass metrics, or None when the pass was dropped
        (``on_give_up="skip"`` after retries AND escalation failed).
        """
        if save not in (None, "base", "delta"):
            raise ValueError(f"save must be None, 'base' or 'delta', got {save!r}")
        if save is not None and self.checkpoint is None:
            raise ValueError("save requires a CheckpointManager")
        self._pass_seq += 1
        self._date = date if date is not None else self._date
        self._admit_poisoned = False
        pass_t0 = time.monotonic()
        if self.coord is None:
            self._adopt_prefetch(date, files)
        else:
            # coordinate the load the same way as the pass verdict: a rank
            # whose input never materialized must take every peer down with
            # it NOW, not leave them hanging in the first exchange
            while True:
                load_err: Optional[PassFailure] = None
                try:
                    self._load_with_retry(date, files)
                except PassFailure as e:
                    load_err = e
                try:
                    ok, detail = self.coord.exchange_verdict(
                        f"load:{self._pass_seq}",
                        load_err is None,
                        repr(load_err) if load_err else "",
                    )
                except PeerDeadError as e:
                    # only raised in elastic mode: shrink membership and
                    # redo the (unarmed) load on the survivors
                    if self.elastic is None:
                        raise
                    self._handle_rank_death(e)
                    continue
                break
            if load_err is not None:
                raise load_err
            if not ok:
                # nothing armed yet — no revert, just a clean global stop
                self._record("peer_abort", "raise", 0, detail)
                raise PassFailure(
                    f"pass {self._pass_seq} aborted: peer load failed: {detail}"
                )
        # poison-aware admission: DataPoisonedError is DETERMINISTIC — the
        # same filelist replays the same corruption on every attempt, so it
        # is resolved here, before the retry loop, under the on_poisoned
        # policy. In coordinated runs the verdict rides the same allgather
        # as the pass/load verdicts so every rank admits or rejects in
        # lockstep (one rank degrading a pass its peer re-runs clean would
        # desync the working-set exchange).
        rep = self._poison_report()
        poisoned = rep is not None and rep["poisoned"]
        poison_detail = rep["detail"] if poisoned else ""
        if self.coord is not None and rep is not None:
            ok, gdetail = self.coord.exchange_verdict(
                f"poison:{self._pass_seq}", not poisoned, poison_detail
            )
            if not ok and not poisoned:
                poisoned = True
                poison_detail = f"peer pass data poisoned: {gdetail}"
        if poisoned and not self._handle_poisoned(poison_detail, rep):
            return None
        escalated = False
        attempt = 0
        while True:
            try:
                with PROFILER.record_event("supervised_pass_attempt", "supervisor"):
                    out = self._attempt(n_batches, prefetch=prefetch)
                break
            except DataPoisonedError as e:
                # belt-and-braces: the pre-loop check above resolves poison
                # before anything is armed, so reaching here means the
                # thresholds/policy changed under a live attempt. Still
                # deterministic — never burn backoff retries on it.
                self._record("data_poisoned", "raise", attempt, repr(e))
                raise
            except PeerDeadError as e:
                if self.elastic is None or self.coord is None:
                    # hardware loss without elastic membership stays what
                    # it always was: terminal for the day
                    raise
                # membership event, not a pass failure: verdict round,
                # ownership shrink, adoption — then retry the pass on the
                # survivors with a FRESH budget (the hardware loss costs
                # one pass retry, never the day)
                self._handle_rank_death(e)
                attempt = 0
                escalated = False
                continue
            except Exception as e:
                self._revert(attempt, e)
                if self.coord is not None:
                    # revert_pass bumped ds.pass_epoch; adopt it (or bump
                    # our own for datasets without the counter) and purge
                    # the aborted attempt's in-flight frames
                    self.coord.advance(getattr(self.ds, "pass_epoch", None))
                attempt += 1
                if attempt > self.retry.retries:
                    if not escalated and self.checkpoint is not None:
                        self._escalate(attempt, e)
                        escalated = True
                        attempt = 0
                        continue
                    if self.on_give_up == "skip":
                        self._record("gave_up", "skip", attempt, repr(e))
                        return None
                    self._record("gave_up", "raise", attempt, repr(e))
                    raise PassFailure(
                        f"pass {self._pass_seq} failed after retries"
                        + (" and checkpoint resume" if escalated else "")
                    ) from e
                self.retry.sleep(self.retry.backoff(attempt))
        if self._admit_poisoned and rep is not None:
            # degrade accounting: the pass manifest records what was lost
            out["quarantined_line_fraction"] = float(rep["line_fraction"])
            out["quarantined_bad_lines"] = float(rep["bad_lines"])
            out["quarantined_bad_files"] = float(rep["bad_files"])
        auc = out.get("auc")
        if auc is not None and np.isfinite(auc):
            self._auc_history.append(float(auc))
        if save is not None:
            self._save_checkpoint(save)
        STAT_OBSERVE("supervisor.pass_s", time.monotonic() - pass_t0)
        if self.metrics is not None:
            # pass-boundary series point: counters + per-pass deltas +
            # histogram summaries, labeled so obs_report can build the
            # per-pass table without guessing at boundaries
            self.metrics.snapshot(
                f"pass:{self._pass_seq}",
                extra={
                    k: float(v)
                    for k, v in out.items()
                    if isinstance(v, (int, float)) and np.isfinite(v)
                },
            )
        return out

    def run_day(
        self,
        date: str,
        pass_files: Sequence[Sequence[str]],
        n_batches: Optional[int] = None,
        publish: bool = True,
    ) -> List[Optional[Dict[str, float]]]:
        """One day = base save after the first pass, delta saves after the
        rest (the reference's SaveBase + per-pass need_save_delta cadence).
        ``publish=False`` trains without checkpointing."""
        outs: List[Optional[Dict[str, float]]] = []
        do_save = publish and self.checkpoint is not None
        for p, files in enumerate(pass_files):
            mode = None if not do_save else ("base" if p == 0 else "delta")
            nxt = (
                (date, tuple(pass_files[p + 1]))
                if p + 1 < len(pass_files)
                else None
            )
            outs.append(
                self.run_pass(
                    files, date=date, n_batches=n_batches, save=mode,
                    prefetch=nxt,
                )
            )
            if self.elastic is not None and self.coord is not None:
                # confirmed + published boundary: the one place membership
                # may grow (admit a waiting joiner) or ownership may move
                # planned ranges — either way an atomic epoch flip on a
                # global fingerprint-tagged commit verdict
                try:
                    self._boundary_elastic(do_save)
                except PeerDeadError as e:
                    # a rank died during the boundary round: membership
                    # handling, then the next pass runs on the survivors
                    self._handle_rank_death(e)
            if self.metrics is not None:
                # wall-clock cadence between the per-pass points: on long
                # passes obs_metrics_interval_s paces extra ticks
                self.metrics.maybe_snapshot()
        return outs
