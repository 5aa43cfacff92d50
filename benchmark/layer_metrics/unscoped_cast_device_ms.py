"""Device milliseconds a traced step spends, outside every named scope, in
instructions that only convert an element type (or round it in place,
``reduce-precision``), with a copy or a transpose at most (kind ``cast`` of
``benchmark/unscoped_times.py``)."""

from benchmark import unscoped_times


def read(run):
    return unscoped_times.kind_ms(run, "cast")
