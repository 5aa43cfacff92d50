"""The harness's own spans and compile events. A span is recorded on the
host's clock and, while a profiler trace is running, as a ``TraceAnnotation``
named ``bench:<name>`` in the trace itself, so that a device gap can be named
by the span that covered it."""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import List, Tuple

import jax


class Recorder:
    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.spans: List[Tuple[str, float, float]] = []  # name, start, end
        self.compiles: List[Tuple[float, float, str]] = []  # end time, seconds, name
        self.slow: List[Tuple[str, float, str]] = []  # jax event, seconds, name
        jax.monitoring.register_event_duration_secs_listener(self._on_compile)

    def _on_compile(self, event: str, duration: float, **kw) -> None:
        if event == self.COMPILE_EVENT:
            self.compiles.append((time.perf_counter(), duration, kw.get("fun_name", "?")))
        if duration >= 1.0:  # tracing, lowering, compiling: what took long, by name
            self.slow.append((event.rsplit("/", 1)[-1], round(duration, 2),
                              kw.get("fun_name", "?")))

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench:{name}"):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def seconds(self, name: str) -> float:
        """Summed length of every span of that name."""
        return sum(e - s for n, s, e in self.spans if n == name)

    def compiles_between(self, t0: float, t1: float) -> List[Tuple[float, float, str]]:
        return [c for c in self.compiles if t0 <= c[0] <= t1]
