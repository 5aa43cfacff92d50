"""Device milliseconds a traced step spends in what is left of the time
outside the four groups of scopes (kind ``other`` of
``benchmark/unscoped_times.py``): unscoped instructions that are neither
``stack``, ``cast`` nor ``copy`` (the TPU's expansion of a prefix sum, adds
of a carry), other programs' operations, and scopes in none of the groups.
With the three other kinds it adds up to what ``unscoped_device_pct`` is a
share of."""

from benchmark import unscoped_times


def read(run):
    return unscoped_times.kind_ms(run, "other")
