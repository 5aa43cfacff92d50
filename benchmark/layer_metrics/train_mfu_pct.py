"""The whole step's share of the chip's bf16 peak, from the device trace: the
model's operations per sample (benchmark/work/<kind>.py) times the samples of
the traced periods, over those periods' whole length, gaps included."""

from benchmark import program


def read(run):
    red = run.get("reduced")
    if not red or not run.get("peaks") or not red["n_modules"] or red["window_s"] <= 0:
        return None
    cfg = run["cell"]["cfg"]
    _, _, work = program.kind_modules(cfg)
    samples = red["n_modules"] * run["scan_batches"] * cfg["batch_size"]
    flops = work.flops_per_sample(cfg) * samples
    return 100.0 * flops / red["window_s"] / run["peaks"]["bf16_flops_per_s"]
