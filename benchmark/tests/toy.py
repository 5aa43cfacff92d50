"""A cell at a size a CPU test can hold: the real configuration and mix files
with only their sizes replaced, and limits read off toy runs (the cells' own
limits come from chip runs at full size)."""

from benchmark import run as bench_run

TOY_LIMITS = {  # the CPU reads 1e-7 and under on each: the reference's tower is the program's
    "early_loss_gap": 1e-4, "counter_gap": 0.0,
    "sparse_grad_gap": 1e-3, "sparse_delta_gap": 1e-3,
    "dense_grad_gap": 1e-3, "dense_delta_gap": 1e-3,
}


def cell(config: str = "dcn_multislot", traffic: str = "pass_fill.dcn", seed: int = 3_000_000_019,
         trace: bool = False, **mix_over) -> dict:
    cfg = bench_run.load_json("benchmark", "configs", config + ".json")
    cfg.update(num_slots=6, embedx_dim=8, hidden=[32, 16], batch_size=64, auc_buckets=1000)
    mix = bench_run.load_json("benchmark", "traffic", traffic + ".json")
    mix.update(train_records=1024, fill_records=1024, n_files=2,
               field_cardinalities=[100, 39884406, 3, 39043, 2953546, 155])
    mix.update(mix_over)
    return {"workload": config + ".pass_train", "chips": 1, "cfg": cfg, "mix": mix,
            "limits": dict(TOY_LIMITS), "seed": seed, "seconds": 1.0, "trace": trace}
