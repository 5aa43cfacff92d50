"""The sparse path's share of the HBM peak: the least bytes a step must move
(benchmark/work/sparse.py, from the distinct rows of the traced batches' like)
over the device's busy time per traced step."""

from benchmark.work import sparse


def read(run):
    red = run.get("reduced")
    if not red or not run.get("peaks") or not red["n_modules"] or red["busy_s"] <= 0:
        return None
    steps = red["n_modules"] * run["scan_batches"]
    need = sparse.bytes_per_step(run["cell"]["cfg"], run["distinct_rows_per_step"])
    return 100.0 * need / (red["busy_s"] / steps) / run["peaks"]["hbm_bytes_per_s"]
