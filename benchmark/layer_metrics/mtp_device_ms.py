"""Device milliseconds a traced step spends in the multi-token-prediction
module (scopes model/mtp/*: eh_proj, its attention, its expert layer)."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.startswith("model/mtp"))
