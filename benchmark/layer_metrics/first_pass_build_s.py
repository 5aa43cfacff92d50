"""begin_pass and prepare_pass of the first pass."""


def read(run):
    rec = run["rec"]
    s = rec.seconds("first_begin_pass") + rec.seconds("first_prepare_pass")
    return s if s > 0 else None
