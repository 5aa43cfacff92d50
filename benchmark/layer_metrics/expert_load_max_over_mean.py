"""The largest held expert's load over the mean load of the held experts, all
expert layers of a step, mean over the window's steps: the step's own counter
(one stacked array in its metrics)."""


def read(run):
    return run.get("counters_per_step", {}).get("expert_load_max_over_mean")
