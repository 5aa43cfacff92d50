"""Readings for the Trinity cell's limits: what the comparison reads when the
reference itself stands in the program's place, (a) computed wholly in
bfloat16 (table, parameters, every operation), the nearest precision below
the one the configuration states, and (b) with a planted fault of this
architecture's own, **the window ignored**: every sliding layer full causal
(rope where it was), each judged by the harness's own comparison against the
cell's own limits: ``correct`` has to come out false. ``control_tokens.py``'s
``half_batch`` says nothing where a batch is one record (it leaves the whole
batch out and reads NaN). Run on the chip at the cell's own size; no part of a
benchmark run.

    python3 -m benchmark.control_afmoe --workload <name> --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from benchmark import compare, compare_tokens, gen_tokens, program, run as bench_run
from benchmark.drivers import common, pass_train_tokens
from benchmark.reference import token_step


def readings(cell: dict, seed: int) -> dict:
    cfg, mix = cell["cfg"], cell["mix"]
    K, B = common.scan_batches(), cfg["batch_size"]
    _, ids = gen_tokens.make_pass(None, mix, seed)
    b_ids = ids[: K * B].reshape(K, B, -1)
    sample = common.sample_keys((b_ids + gen_tokens.KEY_BASE).astype("uint64"))
    _, ref, _ = program.kind_modules(cfg)
    def run(c, **kw):
        with jax.default_matmul_precision("highest"):
            got = token_step.run_steps(
                ref.forward, pass_train_tokens.make_weights(cfg, seed), c, seed, b_ids, sample, **kw)
        common.release()  # one dense state on the device at a time
        return got

    reference, out = run(cfg), {}
    # one control's host copies (leaves before and after, Adam's moment: 7.8 GB) at a time
    for name, c, kw in (("bf16", cfg, {"dtype": jnp.bfloat16}),
                        ("window_ignored", {**cfg, "ignore_window": True}, {})):
        gaps = compare_tokens.gaps(run(c, **kw), reference, cfg)
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
