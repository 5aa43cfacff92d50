"""The window layers' attention scores as a share of the chip's bf16 peak: the
visible pairs of QK^T and PV of every sliding layer (each query's last
sliding_window keys), forward and backward (benchmark/work/afmoe.py), over
the time of the scopes */attn/scores_window (which holds the recomputation
too)."""

from benchmark import program, scope_prefix


def read(run):
    cfg = run["cell"]["cfg"]
    work = program.kind_modules(cfg)[2]
    if not hasattr(work, "window_scores_flops_per_step"):
        return None
    return scope_prefix.mfu_pct(run, work.window_scores_flops_per_step(cfg),
                                lambda s: s.endswith("attn/scores_window"))
