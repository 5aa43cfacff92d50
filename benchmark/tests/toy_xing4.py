"""The Xing4 cell at a size a CPU test can hold: the real configuration and mix
files with their sizes replaced (every mechanism kept: four streams under
hyper-connections with 20 Sinkhorn rounds, latent attention at unequal
query/key and value widths under YaRN, a dense layer and 2 expert layers of 8
experts top 2 with 2 held beside a shared one), and limits read off toy runs."""

from benchmark import run as bench_run

WORKLOAD = "xing4_29b_a4b_ep8.pass_train"
TOY_LIMITS = {
    "early_loss_gap": 1e-4, "logit_gap": 1e-3, "counter_gap": 0.0,
    "sparse_grad_gap": 0.02, "sparse_delta_gap": 0.02,
    "dense_grad_gap": 0.02, "dense_delta_gap": 0.02, "router_flip_share": 0.01,
}
TOY_SIZES = dict(
    hidden_size=64, embedx_dim=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
    moe_intermediate_size=48, router_experts=8, n_routed_experts=2, experts_offset=2,
    num_experts_per_tok=2, num_hidden_layers=3, vocab_size=64, seq_len=32, batch_size=2,
    attn_block=8, loss_block=16, expert_block=8)


def cell(seed: int = 3_000_000_042, trace: bool = False, **cfg_over) -> dict:
    cfg = bench_run.load_json("benchmark", "configs", "xing4_29b_a4b_ep8.json")
    cfg.update(TOY_SIZES)
    # YaRN at the toy's 4 rope pairs: one frequency kept, one on the ramp (low 0, high 2), two divided
    cfg["rope_scaling"] = {**cfg["rope_scaling"], "original_max_position_embeddings": 16, "factor": 8,
                           "beta_fast": 2, "beta_slow": 0.05}
    cfg.update(cfg_over)
    mix = bench_run.load_json("benchmark", "traffic", "pass_tokens.xing4.json")
    mix.update(seq_len=cfg["seq_len"], vocab=cfg["vocab_size"], train_records=32 * cfg["batch_size"])
    return {"workload": WORKLOAD, "chips": 1, "cfg": cfg, "mix": mix,
            "limits": dict(TOY_LIMITS), "seed": seed, "seconds": 1.0, "trace": trace}
