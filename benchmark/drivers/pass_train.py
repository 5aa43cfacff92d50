"""Window driver ``pass_train``: steady training inside one pass.

Set-up: generate, load, begin_pass (the whole pass: its table has every
key), trainer, prepare_pass over the mix's ``train_records`` (the M batches
at the pass's head), the first superstep from the seed (compiles the scan
program; read for the comparison), three more calls to time a step and a
call's start. Window:
``train_pass`` over those M batches, again and again, N batches in all, N
whole supersteps sized to last ``--seconds``, closed by ``block_until_ready``
on the trained table. The fill records behind the M batches are never
trained: a window is seconds of a pass that would take many minutes. Any
compilation inside the window fails the run.
"""

from __future__ import annotations

import functools
import shutil

from benchmark import program
from benchmark.drivers import common


def run(cell: dict, rec) -> dict:
    cfg, mix = cell["cfg"], cell["mix"]
    K, B = common.scan_batches(), cfg["batch_size"]
    M = int(mix["train_records"]) // B // K * K
    if M < 2 * K:
        raise ValueError(f"train_records has to hold two supersteps of {K} batches of {B}")
    common.check_native()
    work, files, keys, labels = common.generate(cell, rec)
    try:
        box, ds = program.make_dataset(cfg, cell["seed"])
        common.open_pass(ds, files, rec, prefix="first_")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    weights = program.make_weights(cfg, cell["seed"])
    trainer = program.make_trainer(cfg, box, weights)
    with rec.span("first_prepare_pass"):
        trainer.prepare_pass(ds, n_batches=M)
    if not trainer._use_resident(ds, False, False):
        raise AssertionError("the trainer would take the host-packer path")
    first = common.first_superstep(cell, ds, trainer, keys, labels, weights, rec)
    del keys, labels
    # one superstep to settle; then a short and a long call: a call that starts on an
    # idle device pays its start in full (the window pays it once), the rest is steps
    long = min(M, 4 * K)
    common.warm_pass_tail(long)  # or the long call's own small programs compile inside it
    with rec.span("time_a_step"):
        common.timed_train(trainer, ds, [K])
        _, t0, t1 = common.timed_train(trainer, ds, [K])
        _, t2, t3 = common.timed_train(trainer, ds, [long])
    step_s = max(((t3 - t2) - (t1 - t0)) / (long - K), 1e-4)
    start_s = min(max((t1 - t0) - K * step_s, 0.0), 0.5 * cell["seconds"])
    n = max(7 * K if cell["trace"] else K,
            round((cell["seconds"] - start_s) / step_s / K) * K)
    calls = [M] * (n // M) + ([n % M] if n % M else [])
    for size in set(calls):
        common.warm_pass_tail(size)

    tracer, on_batch = None, None
    if cell["trace"]:
        tracer = common.Tracer(names=("traced", "train_pass"))
        seen = [0]

        def on_batch(i, m):  # brackets supersteps 2..6 of the window
            seen[0] += 1
            if seen[0] == K + 1:
                tracer.start()
            elif seen[0] == 6 * K + 1:
                tracer.stop()

    with rec.span("window"):
        outs, t0, t1 = common.timed_train(trainer, ds, calls, on_batch)
    compiles = rec.compiles_between(t0, t1)
    if compiles:
        print(f"bench: {len(compiles)} compile events inside the window, "
              f"{sum(c[1] for c in compiles):.3f} s: {[c[2] for c in compiles]}", flush=True)
    bad = (sum(o["batches"] for o in outs) != n or any(o["nan_batches"] for o in outs)
           or bool(compiles))
    return {
        "t_window": (t0, t1), "window_s": t1 - t0, "steps": n, "samples": n * B,
        "attempted": n, "failed": n if bad else 0, "scan_batches": K, "calls": calls,
        "end_to_end": {"train_samples_per_s": n * B / (t1 - t0)},
        "warm_step_s": step_s, "call_start_s": start_s,
        "loss": outs[-1]["loss"], "auc": float(outs[-1]["auc"]),
        "keys_in_pass": int(ds.ws.n_keys), "table_rows": int(ds.ws.capacity),
        "distinct_rows_per_step": common.distinct_rows_per_step(first["keys"]),
        "ids_per_step": B * cfg["num_slots"],
        "memory_peak_bytes": common.memory_peak_bytes(),
        "check": functools.partial(common.check_first_superstep, cell, first, weights),
        "trace": tracer.load() if tracer else None, "trace_module": "superstep",
    }
