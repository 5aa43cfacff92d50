"""Operations of GLM-4.7-Flash's share as the configuration file cuts it. A
multiply-add counts 2; the backward pass costs twice the forward; recomputed
operations do not count. Causal attention counts the positions a query may
see ((T + 1) / 2 on average), the routed experts the share of the
assignments that land on the experts held (the counted ones, where a run
gives them). Norms, rope, softmax and the router's top-k are left out."""


def _attn_proj(c: dict) -> float:
    H, nh = c["hidden_size"], c["num_attention_heads"]
    dq = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return 2.0 * (H * c["q_lora_rank"] + c["q_lora_rank"] * nh * dq
                  + H * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
                  + c["kv_lora_rank"] * nh * (c["qk_nope_head_dim"] + c["v_head_dim"])
                  + nh * c["v_head_dim"] * H)


def scores_forward_per_token(c: dict) -> float:
    """QK^T and PV of one attention layer for one query, over the causal half."""
    keys = (c["seq_len"] + 1) / 2.0
    dq = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return 2.0 * c["num_attention_heads"] * (dq + c["v_head_dim"]) * keys


def expert_forward(c: dict) -> float:
    """One token through one 1536-wide SwiGLU expert."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def attention_layers(c: dict) -> int:
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def expert_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"] + c["num_nextn_predict_layers"]


def forward_per_token(c: dict) -> float:
    H = c["hidden_size"]
    held_share = c["num_experts_per_tok"] * c["n_routed_experts"] / c["router_experts"]
    expert_layer = (2.0 * H * c["router_experts"]
                    + (c["n_shared_experts"] + held_share) * expert_forward(c))
    return (attention_layers(c) * (_attn_proj(c) + scores_forward_per_token(c))
            + c["first_k_dense_replace"] * 2.0 * 3 * H * c["intermediate_size"]
            + expert_layers(c) * expert_layer
            + c["num_nextn_predict_layers"] * 2.0 * 2 * H * H  # eh_proj
            + (1 + c["num_nextn_predict_layers"]) * 2.0 * H * c["vocab_size"])


def flops_per_sample(c: dict) -> float:
    """A sample is one record of seq_len tokens; forward and backward."""
    return 3.0 * forward_per_token(c) * c["seq_len"]


def scores_flops_per_step(c: dict) -> float:
    return 3.0 * attention_layers(c) * scores_forward_per_token(c) * c["seq_len"] * c["batch_size"]


def experts_flops(c: dict, held_assignments: float) -> float:
    """The grouped products over the assignments counted on held experts."""
    return 3.0 * expert_forward(c) * held_assignments
