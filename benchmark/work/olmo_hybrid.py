"""Operations and bytes of Olmo-Hybrid's share as the configuration file cuts
it. A multiply-add counts 2; the backward pass costs twice the forward;
recomputed operations do not count. The full layers' scores count the pairs
a query may see (its causal prefix: T (T + 1) / 2 a head); the delta rule its
recurrent form's three matrix-vector products a token and head (S k, the
rank-one update, S q: 6 dk dv), whatever form computes it. Norms, the short
convolutions, the gates' nonlinearities and softmax are left out. The counts
are of the mathematics, whatever kernel does it."""


def held_types(c: dict) -> list:
    return c["layer_types"][:c["num_hidden_layers"]]


def layers(c: dict, kind: str) -> int:
    return sum(t == kind for t in held_types(c))


def _linear_proj(c: dict) -> float:
    """A token through a linear layer's products: q, k, v, the two gates, the output gate, W_o."""
    H, nh = c["hidden_size"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    return 2.0 * H * nh * (2 * dk + dv + 2 + dv + dv)


def _full_proj(c: dict) -> float:
    H, n = c["hidden_size"], c["num_attention_heads"] * c["head_dim"]
    return 2.0 * H * 4 * n  # q, k, v, o


def delta_rule_forward_per_token(c: dict) -> float:
    return 6.0 * c["linear_num_value_heads"] * c["linear_key_head_dim"] * c["linear_value_head_dim"]


def scores_forward_per_record(c: dict) -> float:
    """QK^T and PV of one full layer, every held head, causal."""
    T = c["seq_len"]
    return 2.0 * c["num_attention_heads"] * 2 * c["head_dim"] * T * (T + 1) / 2.0


def forward_per_record(c: dict) -> float:
    H, T = c["hidden_size"], c["seq_len"]
    lin, full = layers(c, "linear_attention"), layers(c, "full_attention")
    per_token = (lin * (_linear_proj(c) + delta_rule_forward_per_token(c)) + full * _full_proj(c)
                 + (lin + full) * 2.0 * 3 * H * c["intermediate_size"]
                 + 2.0 * H * c["vocab_size"])
    return per_token * T + full * scores_forward_per_record(c)


def flops_per_sample(c: dict) -> float:
    """A sample is one record of seq_len tokens; forward and backward."""
    return 3.0 * forward_per_record(c)


def full_scores_flops_per_step(c: dict) -> float:
    return 3.0 * layers(c, "full_attention") * scores_forward_per_record(c) * c["batch_size"]


def delta_rule_bytes_per_step(c: dict) -> float:
    """The least bytes the delta rule must move in a step: a token and held
    head's forward reads q and k (dk each), v (dv), alpha and beta and writes o
    (dv), float32; the backward twice that; recomputed passes do not count.
    0.85 GB at 4,096 tokens, 10 heads of 96 / 192, 3 linear layers."""
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    per_head = (2 * dk + dv + 2 + dv) * 4.0
    return (3.0 * layers(c, "linear_attention") * per_head * c["linear_num_value_heads"]
            * c["seq_len"] * c["batch_size"])
