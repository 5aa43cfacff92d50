"""Readings for the Olmo-Hybrid cell's limits: what the comparison reads when
the reference itself stands in the program's place, (a) computed wholly in
bfloat16 (table, parameters, every operation), the nearest precision below
the one the configuration states, and with two planted faults of this
architecture's own: (b) **beta = sigmoid(.)**, ``linear_allow_neg_eigval``
ignored (no negative eigenvalues), (c) **no decay**, alpha = 1; each judged
by the harness's own comparison against the cell's own limits: ``correct``
has to come out false. Run on the chip at the cell's own size, one seed a
process; no part of a benchmark run.

    python3 -m benchmark.control_olmo_hybrid --workload <name> --seeds 1 [--controls no_decay]
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from benchmark import compare, compare_tokens, gen_tokens, program, run as bench_run
from benchmark.drivers import common, pass_train_tokens
from benchmark.reference import token_step

CONTROLS = {  # name -> (keys of the configuration the reference is handed, arguments of the step loop)
    "bf16": ({}, {"dtype": jnp.bfloat16}),
    "beta_one": ({"linear_allow_neg_eigval": False}, {}),
    "no_decay": ({"decay_left_out": True}, {}),
}


def readings(cell: dict, seed: int, controls=tuple(CONTROLS)) -> dict:
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
    for name in controls:
        keys, kw = CONTROLS[name]
        gaps = compare_tokens.gaps(run({**cfg, **keys}, **kw), reference, cfg)
        correct, checks = compare.judge(gaps, cell["limits"])
        out[name] = {"correct": correct, "checks": checks, "values": gaps,
                     "fails": sorted(k for k, (v, lim) in checks.items() if not v <= lim)}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="+", choices=sorted(CONTROLS), default=list(CONTROLS))
    args = ap.parse_args()
    cell = bench_run.resolve(bench_run.load_json("BENCHMARK.json"), args.workload)
    for seed in args.seeds:
        print("control: " + json.dumps({"workload": args.workload, "seed": seed,
                                        **readings(cell, seed, args.controls)}), flush=True)


if __name__ == "__main__":
    main()
