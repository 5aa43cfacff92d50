"""Operations of SmallThinker's share as the configuration file cuts it. A
multiply-add counts 2; the backward pass costs twice the forward; recomputed
operations do not count. Attention counts the pairs a query may see: its
causal prefix on a full layer (T (T + 1) / 2 a head), the last
``sliding_window_size`` keys of it on a sliding layer (the sum over t of
min(t + 1, window)); the routed experts the share of the assignments that
land on the experts held (the counted ones, where a run gives them). Norms,
rope, softmax, the gate's ReLU and the router's top-k are left out. The
counts are of the mathematics, whatever kernel does it."""


def _attn_proj(c: dict) -> float:
    H, d = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return 2.0 * H * (nq + 2 * nkv + nq)  # q, k, v, o


def visible_pairs(c: dict, sliding: bool) -> float:
    """(query, key) pairs of one head over one record."""
    T = c["seq_len"]
    W = min(c["sliding_window_size"], T) if sliding else T
    return W * (W + 1) / 2.0 + (T - W) * W


def scores_forward_per_record(c: dict, sliding: bool) -> float:
    """QK^T and PV of one attention layer of that kind, all query heads."""
    return 2.0 * c["num_attention_heads"] * 2 * c["head_dim"] * visible_pairs(c, sliding)


def layers(c: dict, sliding: bool) -> int:
    return sum(bool(k) == sliding for k in c["held_sliding_layout"])


def expert_forward(c: dict) -> float:
    """One token through one 768-wide ReLU-gated expert."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_ffn_hidden_size"]


def forward_per_record(c: dict) -> float:
    H, T = c["hidden_size"], c["seq_len"]
    held_share = (c["moe_num_active_primary_experts"] * c["moe_num_primary_experts"]
                  / c["router_experts"])
    layer = _attn_proj(c) + 2.0 * H * c["router_experts"] + held_share * expert_forward(c)
    per_token = len(c["held_sliding_layout"]) * layer + 2.0 * H * c["vocab_size"]
    return (per_token * T
            + layers(c, True) * scores_forward_per_record(c, True)
            + layers(c, False) * scores_forward_per_record(c, False))


def flops_per_sample(c: dict) -> float:
    """A sample is one record of seq_len tokens; forward and backward."""
    return 3.0 * forward_per_record(c)


def window_scores_flops_per_step(c: dict) -> float:
    return 3.0 * layers(c, True) * scores_forward_per_record(c, True) * c["batch_size"]


def full_scores_flops_per_step(c: dict) -> float:
    return 3.0 * layers(c, False) * scores_forward_per_record(c, False) * c["batch_size"]


def experts_flops(c: dict, held_assignments: float) -> float:
    """The grouped products over the assignments counted on held experts."""
    return 3.0 * expert_forward(c) * held_assignments
