"""Share of the traced steady stretch (whole periods of the superstep
program: the start of the first complete one to the start of the last) in
which no operation ran on the device."""


def read(run):
    red = run.get("reduced")
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
