"""The hyper-connections' share of the HBM peak: the least bytes their
sublayers must move in a step, forward and backward
(benchmark/work/xing4.py::mhc_bytes_per_step), over the time of the scopes
model/hc_* (which holds the recomputation too). The mechanism's roofline: it
has no matrix-product work to speak of. None where the kind counts no such
bytes or the program has no such scope."""

from benchmark import program, scope_prefix


def read(run):
    cfg = run["cell"]["cfg"]
    work = program.kind_modules(cfg)[2]
    ms = scope_prefix.ms(run, lambda s: s.startswith("model/hc_"))
    if ms is None or not run.get("peaks") or not hasattr(work, "mhc_bytes_per_step"):
        return None
    return 100.0 * work.mhc_bytes_per_step(cfg) / (1e-3 * ms) / run["peaks"]["hbm_bytes_per_s"]
