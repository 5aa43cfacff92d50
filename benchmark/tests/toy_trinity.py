"""The Trinity cell at a size a CPU test can hold: the real configuration and
mix files with their sizes replaced (every mechanism kept: grouped-query heads
with QK-norm and the gate, a window shorter than the record, a full layer
without positions among sliding ones, 8 experts top 2 with 2 held beside the
shared one, the four norms, the muP scale), and limits read off toy runs."""

from benchmark import run as bench_run

WORKLOAD = "trinity_mini_ep8.pass_train"
TOY_LIMITS = {
    "early_loss_gap": 1e-4, "logit_gap": 1e-3, "counter_gap": 0.0,
    "sparse_grad_gap": 0.02, "sparse_delta_gap": 0.02,
    "dense_grad_gap": 0.02, "dense_delta_gap": 0.02, "router_flip_share": 0.01,
}
TOY_SIZES = dict(
    hidden_size=64, embedx_dim=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    sliding_window=16, intermediate_size=160, moe_intermediate_size=48, router_experts=8,
    num_experts=2, experts_offset=2, num_experts_per_tok=2, num_hidden_layers=4,
    held_layer_types=["sliding_attention", "sliding_attention", "full_attention",
                      "sliding_attention"],
    vocab_size=64, seq_len=32, batch_size=2, attn_block=8, loss_block=16, expert_block=8)


def cell(seed: int = 3_000_000_033, trace: bool = False, **cfg_over) -> dict:
    cfg = bench_run.load_json("benchmark", "configs", "trinity_mini_ep8.json")
    cfg.update(TOY_SIZES)
    cfg.update(cfg_over)
    mix = bench_run.load_json("benchmark", "traffic", "pass_tokens.trinity.json")
    mix.update(seq_len=cfg["seq_len"], vocab=cfg["vocab_size"],
               train_records=32 * cfg["batch_size"])
    return {"workload": WORKLOAD, "chips": 1, "cfg": cfg, "mix": mix,
            "limits": dict(TOY_LIMITS), "seed": seed, "seconds": 1.0, "trace": trace}
