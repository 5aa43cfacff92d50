"""The table gather's share of the HBM peak: the bytes the pull must read
(benchmark/work/sparse_split.py) over the time of scope pull/table_gather."""

from benchmark import scope_times
from benchmark.work import sparse_split


def read(run):
    need = sparse_split.pull_bytes(run["cell"]["cfg"], run["distinct_rows_per_step"])
    return scope_times.hbm_pct(run, "pull/table_gather", need)
