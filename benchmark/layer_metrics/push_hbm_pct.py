"""The table scatter's share of the HBM peak: the bytes the push must read
and write (benchmark/work/sparse_split.py) over the time of scope
push/table_scatter."""

from benchmark import scope_times
from benchmark.work import sparse_split


def read(run):
    need = sparse_split.push_bytes(run["cell"]["cfg"], run["distinct_rows_per_step"])
    return scope_times.hbm_pct(run, "push/table_scatter", need)
