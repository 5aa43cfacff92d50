"""Readings for the limits: what the comparison reads when the reference
itself stands in the program's place, (a) computed in bfloat16, the nearest
precision below the float32 the configurations state, and (b) with the
planted fault, the second half of every batch left out, each judged by the
harness's own comparison against the cell's own limits
(``benchmark/limits/<workload>.json``): ``correct`` has to come out false.
Run on the chip at the cell's own size; no part of a benchmark run.

    python3 -m benchmark.control --workload <name> --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from benchmark import compare, gen, program, run as bench_run
from benchmark.drivers import common
from benchmark.reference import step as ref_step


def readings(cell: dict, seed: int) -> dict:
    cfg, mix = cell["cfg"], cell["mix"]
    K, B = common.scan_batches(), cfg["batch_size"]
    _, keys, labels = gen.make_pass(None, mix, cfg["num_slots"], seed)
    b_keys = keys[: K * B].reshape(K, B, -1)
    b_labels = labels[: K * B].reshape(K, B)
    del keys, labels
    sample = common.sample_keys(b_keys)
    _, ref, _ = program.kind_modules(cfg)
    weights = program.make_weights(cfg, seed)
    with jax.default_matmul_precision("highest"):
        runs = {
            name: ref_step.run_steps(ref.forward, weights, cfg, seed, b_keys, b_labels,
                                     sample, **kw)
            for name, kw in (("reference", {}), ("bf16", {"dtype": jnp.bfloat16}),
                             ("half_batch", {"half_batch": True}))
        }
    out = {}
    for name in ("bf16", "half_batch"):
        gaps = compare.gaps(runs[name], runs["reference"], cfg)
        correct, checks = compare.judge(gaps, cell["limits"])
        out[name] = {"correct": correct, "checks": checks, "values": gaps,
                     "fails": sorted(k for k, (v, lim) in checks.items() if not v <= lim)}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = bench_run.resolve(bench_run.load_json("BENCHMARK.json"), args.workload)
    for seed in args.seeds:
        print("control: " + json.dumps({"workload": args.workload, "seed": seed,
                                        **readings(cell, seed)}), flush=True)


if __name__ == "__main__":
    main()
