"""Device busy time of the traced supersteps over their steps."""


def read(run):
    red = run.get("reduced")
    if not red or not red["n_modules"]:
        return None
    return 1e3 * red["busy_s"] / (red["n_modules"] * run["scan_batches"])
