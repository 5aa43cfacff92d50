"""The Olmo-Hybrid cell at a size a CPU test can hold: the real configuration
and mix files with their sizes replaced (every mechanism kept: two periods of
three Gated DeltaNet layers and a full layer without positions, the short
convolutions, beta up to 2, the decays, QK-norm over the held projection, a
dense SwiGLU in every layer, the chunked rule over four chunks a record), and
limits read off toy runs."""

from benchmark import run as bench_run

WORKLOAD = "olmo_hybrid_7b_hp3.pass_train"
TOY_LIMITS = {  # 8 layers 64 wide: bfloat16 operands reach the gradients by a few percent
    "early_loss_gap": 1e-3, "logit_gap": 1e-3, "counter_gap": 0.0,
    "sparse_grad_gap": 0.1, "sparse_delta_gap": 0.1,
    "dense_grad_gap": 0.05, "dense_delta_gap": 0.05,
}
TOY_SIZES = dict(
    hidden_size=64, embedx_dim=64, num_attention_heads=2, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=16, intermediate_size=96, num_hidden_layers=8, vocab_size=64,
    seq_len=32, batch_size=2, attn_block=8, loss_block=16, chunk=8)


def cell(seed: int = 3_000_000_046, trace: bool = False, **cfg_over) -> dict:
    cfg = bench_run.load_json("benchmark", "configs", "olmo_hybrid_7b_hp3.json")
    cfg.update(TOY_SIZES)
    cfg.update(cfg_over)
    mix = bench_run.load_json("benchmark", "traffic", "pass_tokens.olmo_hybrid.json")
    mix.update(seq_len=cfg["seq_len"], vocab=cfg["vocab_size"], train_records=32 * cfg["batch_size"])
    return {"workload": WORKLOAD, "chips": 1, "cfg": cfg, "mix": mix,
            "limits": dict(TOY_LIMITS), "seed": seed, "seconds": 1.0, "trace": trace}
