"""Mean time between the end of one traced superstep program and the start
of the next, over the traced periods."""


def read(run):
    red = run.get("reduced")
    if not red or red["n_modules"] < 1:
        return None
    mods = red["modules"]
    gaps = [b[0] - a[1] for a, b in zip(mods[:-1], mods[1:])]
    return 1e3 * sum(gaps) / len(gaps)
