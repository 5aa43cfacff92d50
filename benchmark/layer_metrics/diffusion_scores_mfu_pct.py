"""The attention scores under the block-diffusion mask as a share of the
chip's bf16 peak: the visible pairs of QK^T and PV of every layer (a clean
query its own and the earlier blocks, a noisy one the earlier clean blocks and
its own noisy block: L^2 + 4 L a head), forward and backward
(benchmark/work/sdar.py), over the time of the scopes */attn/scores_diffusion.
By pairs, not by tiles: what the kernel computes of a tile and masks away is
not counted."""

from benchmark import program, scope_prefix


def read(run):
    cfg = run["cell"]["cfg"]
    work = program.kind_modules(cfg)[2]
    if not hasattr(work, "diffusion_scores_flops_per_step"):
        return None
    return scope_prefix.mfu_pct(run, work.diffusion_scores_flops_per_step(cfg),
                                lambda s: s.endswith("attn/scores_diffusion"))
