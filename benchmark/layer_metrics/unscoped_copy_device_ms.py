"""Device milliseconds a traced step spends, outside every named scope, in
instructions that only copy or transpose: the copies layout assignment
inserts (kind ``copy`` of ``benchmark/unscoped_times.py``)."""

from benchmark import unscoped_times


def read(run):
    return unscoped_times.kind_ms(run, "copy")
