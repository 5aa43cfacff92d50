"""Device milliseconds a traced step spends computing the hyper-connections'
maps (scopes model/hc_*/maps: the norm, the 24-column product, the sigmoids
and the 40 normalisations of a 4 x 4 matrix a token), forward, recomputed and
backward: latency- and layout-bound. None where the program has no such
scope."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.startswith("model/hc_") and s.endswith("/maps"))
