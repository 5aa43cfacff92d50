"""Device milliseconds a traced step spends in the push (scope push: the
per-id merge of gradients, sparse adagrad on the distinct rows, the scatter
into the table)."""

from benchmark import scope_times


def read(run):
    return scope_times.group_ms(run, "push")
