"""The share of a step's rows that carry loss: the masked positions over the
rows of the step (clean and noised), both the step's own counters, mean over
the window's steps. A block-diffusion record trains its masked positions
alone: every other row of the doubled sequence is context."""


def read(run):
    counted = run.get("counters_per_step", {})
    masked, rows = counted.get("masked_positions"), counted.get("tokens")
    if masked is None or not rows:
        return None
    return 100.0 * masked / rows
