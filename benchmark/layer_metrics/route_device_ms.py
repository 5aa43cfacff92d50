"""Device milliseconds a traced step spends deciding and laying out the
routing (scopes */moe/router and */moe/dispatch: the router's product, top-k
and softmax, the sort of the assignments into row blocks, the gather of a
block's tokens), forward, recomputed and backward. Where the router stands
ahead of attention its choice depends on the layer's input alone."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.endswith(("moe/router", "moe/dispatch")))
