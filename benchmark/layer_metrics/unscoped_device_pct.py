"""Share of the traced steps' device time that lies in none of the four
groups of scopes (batch assembly, pull, model, push): the superstep's
operations outside every named scope, and other programs' operations."""

from benchmark import scope_times


def read(run):
    st = scope_times.of(run)
    if not st or st["total_ms"] <= 0:
        return None
    return 100.0 * st["unscoped_ms"] / st["total_ms"]
