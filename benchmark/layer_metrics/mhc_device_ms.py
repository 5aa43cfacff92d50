"""Device milliseconds a traced step spends in the hyper-connections (scopes
model/hc_attn/*, model/hc_mlp/*, model/hc_out: the maps with their Sinkhorn
rounds, the streams' read and their mixed write), forward, recomputed and
backward. None where the program has no such scope."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.startswith("model/hc_"))
