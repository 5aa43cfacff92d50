"""The gated delta rule's share of the HBM peak: the least bytes it must
move in a step, forward and backward
(benchmark/work/<kind>.py::delta_rule_bytes_per_step), over the time of the
scopes */linear_attn/delta_rule (which holds the recomputation too). The
rule's roofline: it has no matrix-product work to speak of beside the
model's. None where the kind counts no such bytes or the program has no
such scope."""

from benchmark import program, scope_prefix


def read(run):
    cfg = run["cell"]["cfg"]
    work = program.kind_modules(cfg)[2]
    ms = scope_prefix.ms(run, lambda s: s.endswith("linear_attn/delta_rule"))
    if ms is None or not run.get("peaks") or not hasattr(work, "delta_rule_bytes_per_step"):
        return None
    return 100.0 * work.delta_rule_bytes_per_step(cfg) / (1e-3 * ms) / run["peaks"]["hbm_bytes_per_s"]
