"""Device milliseconds a traced step spends assembling its batch on the
device (scope build_batch: offsets, ragged gather of row ids, sort, dedup
scan, scatter of the inverse)."""

from benchmark import scope_times


def read(run):
    return scope_times.group_ms(run, "batch_assembly")
