"""Window driver ``pass_train_diffusion``: steady training of a
block-diffusion language model inside one pass of records that hold their
tokens twice, clean and noised (``benchmark/gen_diffusion.py``). Everything
but ``run`` is ``pass_train_tokens``'s, imported: the dataset, the trainer
over handed-over weights, step 1's forward, the first superstep and its
comparison, the timed calls. ``run`` is that driver's with this generator and
its own check of the mix against the configuration: the records are not
independent draws, a key of the vocabulary (the MASK id) is a third of the
slot, and a sample is one record of ``data_len`` trained tokens on ``seq_len``
= 2 x ``data_len`` rows.
"""

from __future__ import annotations

import functools
import json
import shutil
import tempfile

import numpy as np

from benchmark import gen_diffusion
from benchmark.drivers import common
from benchmark.drivers.pass_train_tokens import (
    _warm_counters_tail, check_first_superstep, first_superstep, make_dataset, make_trainer,
    make_weights, timed_train)

SHARED = ("seq_len", "data_len", "block_length", "mask_id")  # what mix and configuration both state


def run(cell: dict, rec) -> dict:
    cfg, mix = cell["cfg"], cell["mix"]
    K, B, T, L = common.scan_batches(), cfg["batch_size"], cfg["seq_len"], cfg["data_len"]
    if mix["vocab"] != cfg["vocab_size"] or any(mix[k] != cfg[k] for k in SHARED):
        raise ValueError("the traffic's records do not fit the configuration's vocabulary, record "
                         "length, block length or MASK id")
    M = int(mix["train_records"]) // B // K * K
    if M < 2 * K:
        raise ValueError(f"train_records has to hold two supersteps of {K} batches of {B}")
    common.check_native()
    work = tempfile.mkdtemp(prefix="bench_data_")
    try:
        with rec.span("generate"):
            files, ids = gen_diffusion.make_pass(work, mix, cell["seed"])
        box, ds = make_dataset(cfg, cell["seed"])
        common.open_pass(ds, files, rec, prefix="first_")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    trainer = make_trainer(cfg, box, make_weights(cfg, cell["seed"]))
    with rec.span("first_prepare_pass"):
        trainer.prepare_pass(ds, n_batches=M)
    if not trainer._use_resident(ds, False, False):
        raise AssertionError("the trainer would take the host-packer path")
    first = first_superstep(cell, ds, trainer, ids, rec)
    # a short and a long call: a call's start is paid once a window, the rest is steps (see pass_train)
    long = min(M, 2 * K)
    common.warm_pass_tail(long)
    with rec.span("time_a_step"):  # the first superstep was the one to settle
        _, t0, t1 = timed_train(trainer, ds, [K])
        _, t2, t3 = timed_train(trainer, ds, [long])
    step_s = max(((t3 - t2) - (t1 - t0)) / (long - K), 1e-4)
    start_s = min(max((t1 - t0) - K * step_s, 0.0), 0.5 * cell["seconds"])
    n = max(7 * K if cell["trace"] else K,
            round((cell["seconds"] - start_s) / step_s / K) * K)
    calls = [M] * (n // M) + ([n % M] if n % M else [])
    names = trainer.model.counter_names
    for size in set(calls):
        common.warm_pass_tail(size)
        _warm_counters_tail(size, len(names))

    tracer = common.Tracer(names=("traced", "train_pass")) if cell["trace"] else None
    counters, seen = [], [0]

    def on_batch(i, m):  # a traced run brackets supersteps 2..6 of the window, and counts them
        seen[0] += 1
        if tracer is not None and seen[0] == K + 1:
            tracer.start()
        elif tracer is not None and seen[0] == 6 * K + 1:
            tracer.stop()
        if tracer is None or K < seen[0] <= 6 * K:
            counters.append(m["counters"])

    with rec.span("window"):
        outs, t0, t1 = timed_train(trainer, ds, calls, on_batch)
    compiles = rec.compiles_between(t0, t1)
    if compiles:
        print(f"bench: {len(compiles)} compile events inside the window, "
              f"{sum(c[1] for c in compiles):.3f} s: {[c[2] for c in compiles]}", flush=True)
    bad = (sum(o["batches"] for o in outs) != n or any(o["nan_batches"] for o in outs)
           or bool(compiles))
    counted = np.mean(np.stack([np.asarray(c, np.float64) for c in counters]), axis=0)
    print("bench: tokens " + json.dumps({
        "tokens_per_s": n * B * L / (t1 - t0), "tokens_per_step": B * L, "rows_per_step": B * T,
        "counters_per_step": dict(zip(names, counted.tolist()))}), flush=True)
    return {
        "t_window": (t0, t1), "window_s": t1 - t0, "steps": n, "samples": n * B,
        "attempted": n, "failed": n if bad else 0, "scan_batches": K, "calls": calls,
        "end_to_end": {"train_samples_per_s": n * B / (t1 - t0)},
        "warm_step_s": step_s, "call_start_s": start_s,
        "loss": outs[-1]["loss"], "auc": float(outs[-1]["auc"]),
        "keys_in_pass": int(ds.ws.n_keys), "table_rows": int(ds.ws.capacity),
        "distinct_rows_per_step": common.distinct_rows_per_step(first["ids"]),
        "ids_per_step": B * T,
        "counters_per_step": dict(zip(names, counted.tolist())),
        "memory_peak_bytes": common.memory_peak_bytes(),
        "check": functools.partial(check_first_superstep, cell, first),
        "trace": tracer.load() if tracer else None, "trace_module": "superstep",
    }
