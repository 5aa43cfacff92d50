"""Resolving every key of the pass's records to its table row when the
resident pass is built: the program's own span resident.resolve_rows."""

from benchmark import scope_times


def read(run):
    return scope_times.span_seconds(run, "resident.resolve_rows")
