"""paddlebox_tpu.obs — the unified telemetry plane.

Five pieces, one registry:

- ``histogram``      log2-bucketed distributions behind ``STAT_OBSERVE``
- ``metrics_writer`` rank-tagged JSONL series of registry snapshots
- ``trace_context``  (trace_id, span_id) propagation across PBTX frames
- ``flight_recorder`` always-on ring of recent spans/stats/incidents,
                      dumped as ``incident-<ts>.json`` on fatal errors
- ``program_scopes`` which ``jax.named_scope`` each instruction of a
                      compiled program belongs to, kept per program for
                      whoever reads a device trace of this process

Exports are lazy (PEP 562): ``utils/monitor.py`` imports
``obs.histogram`` at import time, and ``metrics_writer``/
``flight_recorder`` import monitor back — eager re-exports here would
close that loop into an ImportError.
"""

from __future__ import annotations

_LAZY = {
    "Histogram": ("paddlebox_tpu.obs.histogram", "Histogram"),
    "merge_all": ("paddlebox_tpu.obs.histogram", "merge_all"),
    "MetricsWriter": ("paddlebox_tpu.obs.metrics_writer", "MetricsWriter"),
    "read_series": ("paddlebox_tpu.obs.metrics_writer", "read_series"),
    "TraceContext": ("paddlebox_tpu.obs.trace_context", "TraceContext"),
    "trace_span": ("paddlebox_tpu.obs.trace_context", "trace_span"),
    "current_trace": ("paddlebox_tpu.obs.trace_context", "current_trace"),
    "FlightRecorder": ("paddlebox_tpu.obs.flight_recorder", "FlightRecorder"),
    "FLIGHT_RECORDER": (
        "paddlebox_tpu.obs.flight_recorder", "FLIGHT_RECORDER"),
}

__all__ = sorted(_LAZY)


def __getattr__(name):
    try:
        mod_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod_name), attr)
