"""Device milliseconds a traced step spends in the main stack's expert layers
outside attention (scopes model/moe/*: router, dispatch, experts, combine,
shared), forward, recomputed and backward."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.startswith("model/moe"))
