"""Device milliseconds a traced step spends in the gated delta rule (scopes
*/linear_attn/delta_rule: the chunks' decays, triangular inverses and
products, and the scan that passes the state from chunk to chunk), forward,
recomputed and backward. None where the program has no such scope."""

from benchmark import scope_prefix


def read(run):
    return scope_prefix.ms(run, lambda s: s.endswith("linear_attn/delta_rule"))
