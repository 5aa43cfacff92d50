"""Harness spans around set_filelist + load_into_memory (and the preload's
join), summed."""


def read(run):
    s = run["rec"].seconds("load")
    return s if s > 0 else None
