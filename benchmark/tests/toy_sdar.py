"""The SDAR cell at a size a CPU test can hold: the real configuration and mix
files with their sizes replaced (every mechanism kept: the record twice, clean
and noised, under the block-diffusion mask in blocks of 4, query blocks that
hold two blocks, 6 query heads over 2 key-value heads with QK-norm and rope at
the position inside the half, 8 experts top 2 with 2 held and no shared one,
the loss over the masked positions alone), and limits read off toy runs."""

from benchmark import run as bench_run

WORKLOAD = "sdar_30b_a3b_ep8.pass_train"
TOY_LIMITS = {
    "early_loss_gap": 1e-4, "logit_gap": 1e-3, "counter_gap": 0.0,
    "sparse_grad_gap": 0.02, "sparse_delta_gap": 0.02,
    "dense_grad_gap": 0.02, "dense_delta_gap": 0.02, "router_flip_share": 0.01,
}
TOY_SIZES = dict(
    hidden_size=64, embedx_dim=64, num_attention_heads=6, num_key_value_heads=2, head_dim=16,
    moe_intermediate_size=48, router_experts=8, num_experts=2, experts_offset=2,
    num_experts_per_tok=2, num_hidden_layers=4, held_layers=[0, 1, 2, 3], vocab_size=64,
    mask_id=63, seq_len=64, data_len=32, block_length=4, batch_size=2,
    attn_block=8, loss_block=16, expert_block=8)


def cell(seed: int = 3_000_000_039, trace: bool = False, **cfg_over) -> dict:
    cfg = bench_run.load_json("benchmark", "configs", "sdar_30b_a3b_ep8.json")
    cfg.update(TOY_SIZES)
    # rows of range 1: at the toy's width and 32 positions a row of range 4 drowns what an
    # attention block adds, and the leak (the planted fault) would hardly show
    cfg["sparse_opt"] = {**cfg["sparse_opt"], "initial_range": 1.0}
    cfg.update(cfg_over)
    mix = bench_run.load_json("benchmark", "traffic", "pass_diffusion.sdar.json")
    mix.update({k: cfg[k] for k in ("seq_len", "data_len", "block_length", "mask_id")},
               vocab=cfg["vocab_size"], train_records=32 * cfg["batch_size"])
    return {"workload": WORKLOAD, "chips": 1, "cfg": cfg, "mix": mix,
            "limits": dict(TOY_LIMITS), "seed": seed, "seconds": 1.0, "trace": trace}
