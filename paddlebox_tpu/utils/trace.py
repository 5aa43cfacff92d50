"""Event profiler with chrome-trace export.

Parity with the reference's event profiler (platform/profiler.{h,cc}:
``RecordEvent`` scoped annotations, profiler.h:127) and its chrome-trace
exporter (tools/timeline.py:115-137). On TPU the heavy lifting belongs to
jax.profiler (XLA traces); this host-side layer times the Python/runtime
stages around the device (pack, infeed, pass pipeline) and writes the same
``chrome://tracing`` JSON format.

Telemetry-plane upgrades (docs/OBSERVABILITY.md):

- the event buffer is a bounded ring (flag ``trace_max_events``); when
  full, the oldest data events are dropped and counted in
  ``trace.dropped_events`` instead of growing a soak's RSS without limit;
- tids are stable small per-thread ids (1, 2, ...) with chrome
  ``thread_name`` metadata, and ``set_process(rank)`` stamps pid=rank +
  ``process_name`` so merged multi-rank traces get one labeled process
  row per rank;
- every span/instant also feeds the always-on flight recorder
  (``obs/flight_recorder.py``) — even with tracing disabled — so an
  incident bundle can show the last N spans before a death;
- spans recorded inside an ``obs.trace_span`` context carry
  trace_id/span_id args for cross-rank correlation
  (``tools/obs_report.py --merge-traces``);
- every span is also a ``jax.profiler.TraceAnnotation`` named
  ``pbx:<name>``: nothing while no profiler session runs, a host event on
  the device trace's own clock while one does, so a device idle gap can
  be read under the span that covered it;
- a per-name count and summed seconds are kept always (``totals()``), and
  ``record_event`` yields an object whose ``seconds`` is the span's length
  once it has closed, so a stage that publishes its seconds as a stat
  times itself once.
"""

from __future__ import annotations

import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Deque, Dict, List, Optional

from jax.profiler import TraceAnnotation

from paddlebox_tpu import config
from paddlebox_tpu.obs.flight_recorder import FLIGHT_RECORDER
from paddlebox_tpu.obs.trace_context import current_trace
from paddlebox_tpu.utils.monitor import STAT_ADD

config.define_flag(
    "trace_max_events", 200_000,
    "profiler ring capacity per process; once full the oldest data "
    "events are dropped (counted in trace.dropped_events)",
)


def _trace_args() -> Optional[Dict[str, str]]:
    ctx = current_trace()
    return ctx.as_args() if ctx is not None else None


class Span:
    """What ``record_event`` yields; ``seconds`` is set when the span closes."""

    __slots__ = ("seconds",)

    def __init__(self):
        self.seconds = 0.0


class Profiler:
    def __init__(self, max_events: Optional[int] = None):
        self._lock = threading.Lock()
        self._max_events = max_events  # None -> flag trace_max_events
        # ring state: touched only by the *_locked helpers below, whose
        # callers all hold _lock (THR002 can't see through the helpers)
        self._events: Deque[Dict] = deque()  # synchronized-by: _lock (held by *_locked callers)
        self._thread_meta: List[Dict] = []  # synchronized-by: _lock (held by *_locked callers)
        self._tids: Dict[int, int] = {}  # synchronized-by: _lock (held by *_locked callers)
        self._dropped = 0  # synchronized-by: _lock (held by *_locked callers)
        self._totals: Dict[str, List] = {}  # guarded-by: _lock; name -> [count, seconds]
        self._pid = 0  # guarded-by: _lock
        self._process_name = "rank0"  # guarded-by: _lock
        self.enabled = False

    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def set_process(self, rank: int, name: Optional[str] = None) -> None:
        """Label this process's rows: pid=rank, a readable process_name.
        Events are stamped with the pid at export, so calling this after
        spans were already recorded still yields one coherent row."""
        with self._lock:
            self._pid = int(rank)
            self._process_name = name or f"rank{int(rank)}"
        FLIGHT_RECORDER.set_rank(int(rank))

    @property
    def dropped_events(self) -> int:
        with self._lock:
            return self._dropped

    # -- recording --------------------------------------------------------
    def _tid_locked(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = len(self._tids) + 1
            self._tids[ident] = tid
            self._thread_meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "tid": tid,
                    "args": {"name": threading.current_thread().name},
                }
            )
        return tid

    def _append_locked(self, event: Dict) -> None:
        cap = self._max_events
        if cap is None:
            cap = int(config.get_flag("trace_max_events"))
        while len(self._events) >= max(1, cap):
            self._events.popleft()
            self._dropped += 1
            STAT_ADD("trace.dropped_events")
        self._events.append(event)

    @contextmanager
    def record_event(self, name: str, category: str = "host"):
        """Scoped annotation (platform::RecordEvent parity). Always feeds
        the flight recorder, the per-name totals and a running jax profiler
        session (as ``pbx:<name>``); appends to the chrome-trace ring only
        when enabled. Yields a ``Span``."""
        span = Span()
        t0 = time.perf_counter_ns()
        try:
            with TraceAnnotation("pbx:" + name):
                yield span
        finally:
            t1 = time.perf_counter_ns()
            span.seconds = (t1 - t0) * 1e-9
            with self._lock:
                tot = self._totals.setdefault(name, [0, 0.0])
                tot[0] += 1
                tot[1] += span.seconds
            args = _trace_args()
            FLIGHT_RECORDER.note_span(
                name, category, t0 / 1e3, (t1 - t0) / 1e3, args)
            if self.enabled:
                event = {
                    "name": name,
                    "cat": category,
                    "ph": "X",
                    "ts": t0 / 1e3,  # chrome trace wants microseconds
                    "dur": (t1 - t0) / 1e3,
                }
                if args:
                    event["args"] = args
                with self._lock:
                    event["tid"] = self._tid_locked()
                    self._append_locked(event)

    def instant(self, name: str, args: Optional[Dict] = None,
                category: str = "incident") -> None:
        """Zero-duration structured event (chrome trace "i" phase): the
        supervisor's incident log lands in the same timeline as the pass
        stages it interrupted, with the details in ``args``. Instants feed
        the flight recorder, tracing enabled or not: incident-category
        ones into the incident ring, the rest (transport markers etc.)
        into the span ring as zero-duration entries."""
        merged = dict(args or {})
        tctx = _trace_args()
        if tctx:
            merged.update(tctx)
        if category == "incident":
            FLIGHT_RECORDER.note_incident(name, merged, category)
        else:
            FLIGHT_RECORDER.note_span(
                name, category, time.perf_counter_ns() / 1e3, 0.0, merged)
        if not self.enabled:
            return
        event = {
            "name": name,
            "cat": category,
            "ph": "i",
            "s": "g",  # global scope: draw the incident across rows
            "ts": time.perf_counter_ns() / 1e3,
            "args": merged,
        }
        with self._lock:
            event["tid"] = self._tid_locked()
            self._append_locked(event)

    def totals(self) -> Dict[str, Dict[str, float]]:
        """{span name: {"count", "seconds"}} over every span closed since
        the last ``reset``, tracing enabled or not."""
        with self._lock:
            return {n: {"count": c, "seconds": s} for n, (c, s) in self._totals.items()}

    # -- export -----------------------------------------------------------
    def export_chrome_trace(self, path: str) -> int:
        """Write chrome://tracing JSON (timeline.py parity). Returns the
        number of DATA events written (metadata rows excluded)."""
        from paddlebox_tpu.utils.fs import atomic_write

        with self._lock:
            data = [dict(e) for e in self._events]
            thread_meta = [dict(m) for m in self._thread_meta]
            pid = self._pid
            pname = self._process_name
            dropped = self._dropped
        meta = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": pname}},
            {"name": "process_sort_index", "ph": "M", "pid": pid, "tid": 0,
             "args": {"sort_index": pid}},
        ]
        for m in thread_meta:
            m["pid"] = pid
        for e in data:
            e["pid"] = pid
        payload = {
            "traceEvents": meta + thread_meta + data,
            "displayTimeUnit": "ms",
            "otherData": {"rank": pid, "dropped_events": dropped},
        }
        with atomic_write(path) as f:
            json.dump(payload, f)
        return len(data)

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._thread_meta.clear()
            self._tids.clear()
            self._dropped = 0
            self._totals.clear()


# process-global profiler, like the reference's g_state
PROFILER = Profiler()


def record_event(name: str, category: str = "host"):
    return PROFILER.record_event(name, category)
