"""The routed experts' share of the chip's bf16 peak: the grouped products
over the assignments the step counted on held experts, forward and backward
(benchmark/work/glm_moe_lite.py), over the time of the scopes */moe/experts."""

from benchmark import program, scope_prefix


def read(run):
    cfg = run["cell"]["cfg"]
    work = program.kind_modules(cfg)[2]
    counted = run.get("counters_per_step", {}).get("held_assignments")
    if counted is None or not hasattr(work, "experts_flops"):
        return None
    return scope_prefix.mfu_pct(run, work.experts_flops(cfg, counted),
                                lambda s: s.endswith("moe/experts"))
