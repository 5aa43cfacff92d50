"""Device milliseconds a traced step spends in grouped-query attention (scopes
model/attn/*: the four projections, QK-norm and rope, window and full scores,
the gated output with its norm), forward, recomputed and backward."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.startswith("model/attn"))
