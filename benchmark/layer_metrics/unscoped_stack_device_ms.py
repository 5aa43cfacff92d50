"""Device milliseconds a traced step spends, outside every named scope, in
the layer scan's slicing and stacking: a dynamic slice read out of, or
written into, a stacked array with no arithmetic on floats (kind ``stack`` of
``benchmark/unscoped_times.py``)."""

from benchmark import unscoped_times


def read(run):
    return unscoped_times.kind_ms(run, "stack")
