"""Attention scores' share of the chip's bf16 peak: the causal half of QK^T
and PV of every attention layer, forward and backward
(benchmark/work/glm_moe_lite.py), over the time of the scopes */mla/scores
(which holds the recomputation too)."""

from benchmark import program, scope_prefix


def read(run):
    cfg = run["cell"]["cfg"]
    work = program.kind_modules(cfg)[2]
    if not hasattr(work, "scores_flops_per_step"):
        return None
    return scope_prefix.mfu_pct(run, work.scores_flops_per_step(cfg),
                                lambda s: s.endswith("mla/scores"))
