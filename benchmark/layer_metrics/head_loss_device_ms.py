"""Device milliseconds a traced step spends in the output head and the loss of
both heads (scope loss/head: final norms, blocked logits, cross-entropy)."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.startswith("loss/head"))
