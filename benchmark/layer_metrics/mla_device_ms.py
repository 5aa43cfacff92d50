"""Device milliseconds a traced step spends in the main stack's latent
attention (scopes model/mla/*: projections, rope, scores, output), forward,
recomputed and backward."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.startswith("model/mla"))
