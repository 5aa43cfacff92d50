"""Device milliseconds a traced step spends in linear attention (scopes
model/linear_attn/*: the q, k, v projections, the short convolutions and L2
norms, beta and the decays, the gated delta rule, the gated output norm, W_o
with the norm after it), forward, recomputed and backward. None where the
program has no such scope."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.startswith("model/linear_attn/"))
