"""The host pull of the pass's rows inside begin_pass: the program's own
span boundary.pull, summed over the process."""

from benchmark import scope_times


def read(run):
    return scope_times.span_seconds(run, "boundary.pull")
