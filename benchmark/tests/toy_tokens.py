"""The token cell at a size a CPU test can hold: the real configuration and
mix files with their sizes replaced (every mechanism kept: latent attention,
8 experts top 2 with 2 held, a dense layer, 2 expert layers, MTP), and limits
read off toy runs."""

from benchmark import run as bench_run

TOY_LIMITS = {
    "early_loss_gap": 1e-4, "logit_gap": 1e-3, "counter_gap": 0.0,
    "sparse_grad_gap": 0.02, "sparse_delta_gap": 0.02,
    "dense_grad_gap": 0.02, "dense_delta_gap": 0.02, "router_flip_share": 0.01,
}
TOY_SIZES = dict(
    hidden_size=64, embedx_dim=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, intermediate_size=160,
    moe_intermediate_size=48, router_experts=8, n_routed_experts=2, experts_offset=2,
    num_experts_per_tok=2, num_hidden_layers=3, vocab_size=64, seq_len=32,
    attn_block=8, loss_block=16, expert_block=8)


def cell(seed: int = 3_000_000_023, trace: bool = False, **cfg_over) -> dict:
    cfg = bench_run.load_json("benchmark", "configs", "glm47_flash_ep8.json")
    cfg.update(TOY_SIZES)
    cfg.update(cfg_over)
    mix = bench_run.load_json("benchmark", "traffic", "pass_tokens.glm47.json")
    mix.update(seq_len=cfg["seq_len"], vocab=cfg["vocab_size"])
    return {"workload": "glm47_flash_ep8.pass_train", "chips": 1, "cfg": cfg, "mix": mix,
            "limits": dict(TOY_LIMITS), "seed": seed, "seconds": 1.0, "trace": trace}
