"""Device milliseconds a traced step spends in the pull (scope pull: the
gather of the distinct rows from the table, their gating, and their
expansion to one row an id)."""

from benchmark import scope_times


def read(run):
    return scope_times.group_ms(run, "pull")
