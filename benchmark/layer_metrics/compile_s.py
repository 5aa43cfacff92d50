"""jax's backend-compile events (a build or a load from the persistent
cache) before the window opened, summed."""


def read(run):
    t0 = run["t_window"][0]
    return sum(c[1] for c in run["rec"].compiles if c[0] < t0)
