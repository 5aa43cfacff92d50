"""Operations of SDAR's share as the configuration file cuts it. A
multiply-add counts 2; the backward pass costs twice the forward; recomputed
operations do not count. A record is ``seq_len`` = 2 x ``data_len`` rows (the
clean tokens and their noised copy): projections, router and experts run on
all of them, the head on the noisy half alone. Attention counts the pairs a
query may see under the block-diffusion mask: a clean query its own and the
earlier blocks (4 (blk + 1) keys), a noisy one the earlier clean blocks and
its own noisy block (4 blk + 4): L^2 + 4 L a head at blocks of 4. The routed
experts count the share of the assignments that land on the experts held (the
counted ones, where a run gives them). Norms, rope, softmax, the gate's SiLU
and the router's top-k are left out. The counts are of the mathematics,
whatever kernel does it."""


def _attn_proj(c: dict) -> float:
    H, d = c["hidden_size"], c["head_dim"]
    nq, nkv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return 2.0 * H * (nq + 2 * nkv + nq)  # q, k, v, o


def visible_pairs(c: dict) -> float:
    """(query, key) pairs of one head over one record."""
    L, n = c["data_len"], c["block_length"]
    return 2.0 * sum(n * (b + 1) for b in range(L // n)) * n  # both halves see n (blk + 1) keys a query


def scores_forward_per_record(c: dict) -> float:
    """QK^T and PV of one attention layer, all query heads."""
    return 2.0 * c["num_attention_heads"] * 2 * c["head_dim"] * visible_pairs(c)


def expert_forward(c: dict) -> float:
    """One row through one 768-wide SwiGLU expert."""
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def forward_per_record(c: dict) -> float:
    H, T, L = c["hidden_size"], c["seq_len"], c["data_len"]
    held_share = c["num_experts_per_tok"] * c["num_experts"] / c["router_experts"]
    layer = _attn_proj(c) + 2.0 * H * c["router_experts"] + held_share * expert_forward(c)
    return (c["num_hidden_layers"] * (layer * T + scores_forward_per_record(c))
            + 2.0 * H * c["vocab_size"] * L)


def flops_per_sample(c: dict) -> float:
    """A sample is one record of data_len trained tokens; forward and backward."""
    return 3.0 * forward_per_record(c)


def diffusion_scores_flops_per_step(c: dict) -> float:
    return 3.0 * c["num_hidden_layers"] * scores_forward_per_record(c) * c["batch_size"]


def experts_flops(c: dict, held_assignments: float) -> float:
    """The grouped products over the assignments counted on held experts."""
    return 3.0 * expert_forward(c) * held_assignments
