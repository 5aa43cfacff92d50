"""Window driver ``pass_train_tokens``: steady training of a language model
inside one pass of token records, through the same entry points as
``pass_train`` (``BoxPSDataset`` -> ``begin_pass`` -> ``CTRTrainer.prepare_pass``
/ ``train_pass``, resident superstep).

Set-up: generate the records (``benchmark/gen_tokens.py``), load, begin_pass
(the table is the vocabulary slice the pass saw), trainer with the seed's
dense weights handed over (``hand_over_dense``: no second copy, the dense
state is two thirds of the chip), prepare_pass over the mix's
``train_records``, the model's forward on the first batch (step 1's logit
terms and expert choices, for the comparison), the first superstep from the
seed (compiles the scan program; read for the comparison), three more calls
to time a step and a call's start. Window:
``train_pass`` over those batches, again and again, N whole supersteps sized
to last ``--seconds``, closed by ``block_until_ready`` on the trained table
and the dense leaves. Any compilation inside the window fails the run.
"""

from __future__ import annotations

import functools
import json
import shutil
import tempfile
import time

import jax
import numpy as np
import optax

from paddlebox_tpu import BoxWrapper
from paddlebox_tpu.data import SlotInfo, SlotSchema
from paddlebox_tpu.ops.pull_push import pull_sparse_rows
from paddlebox_tpu.table import SparseOptimizerConfig
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig

from benchmark import gen_tokens, program
from benchmark.drivers import common


def make_dataset(cfg: dict, seed: int):
    so = cfg["sparse_opt"]
    box = BoxWrapper(
        embedx_dim=cfg["embedx_dim"],
        sparse_opt=SparseOptimizerConfig(
            embed_lr=so["embed_lr"], embedx_lr=so["embedx_lr"],
            initial_g2sum=so["initial_g2sum"], initial_range=so["initial_range"],
            embedx_threshold=so["embedx_threshold"], weight_bounds=so["weight_bounds"]),
        seed=seed,
    )
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1),
         SlotInfo("ids", type="float", dense=True, dim=cfg["seq_len"]),
         SlotInfo("tokens")],
        label_slot="label",
    )
    ds = box.make_dataset(schema, batch_size=cfg["batch_size"])
    ds.set_date(program.DATE)
    return box, ds


def make_weights(cfg: dict, seed: int):
    """Dense leaves from the seed, one jitted call, float32 on the device. As
    ``program.make_weights``, but drawn with the counter-based generator the
    device has in hardware: 667M threefry normals take 40 s to compile."""
    _, ref, _ = program.kind_modules(cfg)
    key = jax.random.key(seed % (1 << 31), impl="rbg")
    return jax.jit(lambda k: ref.init(k, cfg, 3 + cfg["embedx_dim"]))(key)


def make_trainer(cfg: dict, box, weights):
    """The trainer over the seed's weights; they are handed over, not copied."""
    build, _, _ = program.kind_modules(cfg)
    model = build.build(cfg, box.layout.pull_width)
    step_cfg = TrainStepConfig(
        num_slots=cfg["num_slots"], batch_size=cfg["batch_size"], layout=box.layout,
        sparse_opt=box.sparse_opt, auc_buckets=cfg["auc_buckets"])
    ad = cfg["dense_opt"]
    # optax counts from 0: update number t = count + 1 uses lr * min(1, t / warmup_steps)
    rate = lambda count: ad["lr"] * jax.numpy.minimum(1.0, (count + 1) / ad["warmup_steps"])  # noqa: E731
    trainer = CTRTrainer(
        model, step_cfg,
        dense_opt=optax.adam(rate, b1=ad["b1"], b2=ad["b2"], eps=ad["eps"]),
        dense_slot="ids", dense_dim=cfg["seq_len"])
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(weights) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(weights))):
        raise ValueError("the reference's weights do not fit the program's model")
    trainer.hand_over_dense(weights)
    return trainer


def timed_train(trainer, ds, calls, on_batch=None):
    """As ``common.timed_train``, closed by the dense leaves too."""
    t0 = time.perf_counter()
    outs = [trainer.train_pass(ds, n_batches=n, on_batch=on_batch) for n in calls]
    jax.block_until_ready((trainer.trained_table_device(), trainer.params))
    return outs, t0, time.perf_counter()


def rows_of(ds, keys: np.ndarray) -> np.ndarray:
    """The pass's table rows of ``keys`` (any shape), every one of them present."""
    pos = np.searchsorted(ds.ws.sorted_keys, keys)
    if not np.array_equal(ds.ws.sorted_keys[np.minimum(pos, ds.ws.n_keys - 1)], keys):
        raise AssertionError("keys of the generated batches are missing from the pass's working set")
    return ds.ws.row_of_sorted[pos]


def step_one_forward(ds, trainer, ids: np.ndarray) -> dict:
    """Step 1's logit terms and expert choices. The timed program carries the
    model's counters only, so set-up asks the model once: its own ``forward``
    on the seed's weights and the first batch's rows as the step pulls them
    (``pull_sparse_rows`` over the pass's opening table)."""
    step, lay = trainer.cfg, trainer.cfg.layout
    rows = rows_of(ds, (ids + gen_tokens.KEY_BASE).astype(np.uint64).ravel())
    opened = jax.numpy.asarray(np.asarray(ds.device_table).reshape(-1, lay.width)[rows])
    pulled = pull_sparse_rows(opened, jax.numpy.arange(len(rows)), lay,
                              step.sparse_opt.embedx_threshold, step.pull_scale)
    emb = pulled[:, lay.cvm_offset:].reshape(ids.shape + (-1,))
    out = jax.jit(trainer.model.forward)(trainer.params, emb, jax.numpy.asarray(ids, np.float32))
    return {k: np.asarray(out[k]) for k in ("parts", "token_logits", "router_choices")}


def first_superstep(cell: dict, ds, trainer, ids: np.ndarray, rec) -> dict:
    """The window's own call through its first superstep from the seed's
    state: the K batches it fed (as the generator's ids), every distinct key
    of them with the program's rows before and after, each step's loss parts,
    the dense leaves and Adam's first moment (host copies: the state trains
    on); before it, step 1's logit terms and expert choices from the model's
    forward on the same state and batch."""
    K, W = common.scan_batches(), ds.table.layout.width
    idx = np.stack(list(ds.batch_indices(K)))  # [K, B] store records = file order
    b_ids = ids[idx]  # [K, B, T]
    sample = common.sample_keys((b_ids + gen_tokens.KEY_BASE).astype(np.uint64))
    rows = rows_of(ds, sample)
    open_rows = np.asarray(ds.device_table).reshape(-1, W)[rows]
    with rec.span("step_one_forward"):
        fwd = step_one_forward(ds, trainer, b_ids[0])
    seen = []
    with rec.span("first_superstep"):
        out = trainer.train_pass(ds, n_batches=K, on_batch=lambda i, m: seen.append(
            {k: m[k] for k in ("loss", "counters")}))
        jax.block_until_ready((trainer.trained_table_device(), trainer.params))
    if out["batches"] != K or out["nan_batches"]:
        raise AssertionError(f"first superstep: {out}")
    table = trainer.trained_table_device().reshape(-1, W)
    host = lambda tree: jax.tree.map(lambda a: np.asarray(a, np.float32), tree)  # noqa: E731
    counters = np.stack([np.asarray(m["counters"], np.float64) for m in seen])
    if not np.allclose(fwd["parts"], counters[0, :2], rtol=1e-3):  # the same state and batch
        raise AssertionError(f"forward's loss parts {fwd['parts']} against step 1's {counters[0, :2]}")
    print("bench: first_superstep_counters " + json.dumps(
        {n: counters[:, i].tolist() for i, n in enumerate(trainer.model.counter_names)}), flush=True)
    return {
        "ids": b_ids, "sample_keys": sample,
        "prog": {
            "losses": np.asarray([float(m["loss"]) for m in seen], np.float64),
            "parts": counters[:, :2],
            "token_logits": fwd["token_logits"], "router_choices": fwd["router_choices"],
            "open_rows": open_rows,
            "rows": np.asarray(table[jax.numpy.asarray(rows)]),
            "params": host(trainer.params),
            "mu": host(trainer.opt_state[0].mu),
        },
    }


def check_first_superstep(cell: dict, first: dict) -> dict:
    """The plain reference over the batches of the first superstep, from the
    seed's weights made anew, against what the program left. Runs on the
    device, so only once the program's state is freed."""
    from benchmark import compare_tokens
    from benchmark.reference import token_step

    cfg = cell["cfg"]
    _, ref, _ = program.kind_modules(cfg)
    with jax.default_matmul_precision("highest"):
        out = token_step.run_steps(ref.forward, make_weights(cfg, cell["seed"]), cfg,
                                   cell["seed"], first["ids"], first["sample_keys"])
    first["prog"]["open_params"] = out["open_params"]  # the seed's weights, as both began
    print("bench: loss_parts " + json.dumps({"program": first["prog"]["parts"].tolist(),
                                             "reference": out["parts"].tolist()}), flush=True)
    print("bench: dense_leaves " + json.dumps(compare_tokens.leaf_table(first["prog"], out)),
          flush=True)
    return compare_tokens.gaps(first["prog"], out, cfg)


def run(cell: dict, rec) -> dict:
    cfg, mix = cell["cfg"], cell["mix"]
    K, B, T = common.scan_batches(), cfg["batch_size"], cfg["seq_len"]
    if (mix["seq_len"], mix["vocab"]) != (T, cfg["vocab_size"]):
        raise ValueError("the traffic's records do not fit the configuration's seq_len and vocabulary")
    M = int(mix["train_records"]) // B // K * K
    if M < 2 * K:
        raise ValueError(f"train_records has to hold two supersteps of {K} batches of {B}")
    common.check_native()
    work = tempfile.mkdtemp(prefix="bench_data_")
    try:
        with rec.span("generate"):
            files, ids = gen_tokens.make_pass(work, mix, cell["seed"])
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
        "tokens_per_s": n * B * T / (t1 - t0), "tokens_per_step": B * T,
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


def _warm_counters_tail(n_batches: int, n_counters: int) -> None:
    """train_pass ends by stacking its n per-batch counter vectors and taking
    their mean over the batches: built in set-up, like the losses' tail."""
    zero = jax.device_put(jax.numpy.zeros((n_counters,), jax.numpy.float32), jax.devices()[0])
    np.asarray(jax.numpy.mean(jax.numpy.stack([zero] * n_batches), axis=0))
