"""Criteo-Kaggle convergence artifact (BASELINE.md configs 1-2).

Trains DeepFM (or LR) through the FULL framework path — slot files ->
BoxPSDataset passes -> native pack -> jitted train step -> AUC registry —
on Criteo display-advertising data and records the final AUC, producing
``CONVERGENCE.json`` next to this script.

Two data modes:

- ``--data-dir DIR`` — REAL Criteo-Kaggle ``train.txt`` (tab-separated:
  label, 13 integer features, 26 categorical hex features). Lines are
  converted to the slot format the reference's data generators emit
  (criteo readers in the PaddleBox ecosystem do the same mapping):
  integer feature i -> slot i key ``(i << 40) | ceil(log2(v+1))``
  (the standard Criteo log2 bucketization), categorical j -> slot 13+j
  key ``(j+13) << 40 | int(hex, 16) & MASK``. Expected AUC after one
  epoch: ~0.77-0.79 (public DeepFM numbers on Criteo-Kaggle).

- ``--synthetic`` — this environment has no network egress and no local
  copy of Criteo, so quality parity is demonstrated on a Criteo-SHAPED
  synthetic: 39 slots, power-law key frequencies (hot head like Criteo's
  categorical skew), ~25% positive rate, and a planted logistic ground
  truth over per-key latent weights so the task has a known learnable
  structure (Bayes AUC ~0.86 at the default noise). The artifact records
  which mode produced it; the real-data number slots in by re-running
  with --data-dir once the dataset is available.

Usage:
  python tools/criteo_convergence.py --synthetic [--rows 400000]
  python tools/criteo_convergence.py --data-dir /path/to/criteo [--rows N]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

N_INT, N_CAT = 13, 26
N_SLOTS = N_INT + N_CAT
CAT_MASK = (1 << 40) - 1


def convert_criteo_line(line: str) -> str | None:
    """One Kaggle train.txt line -> slot-format line (label + 39 slots).

    Returns None for malformed input — wrong column count, non-integer
    label/int feature, non-hex categorical — the reject path a real crawl
    needs (the reference's BufferedLineFileReader drops bad lines the same
    way, data_feed.cc line-parse error branches)."""
    parts = line.rstrip("\n").split("\t")
    if len(parts) != 1 + N_INT + N_CAT:
        return None
    label = parts[0]
    if label not in ("0", "1"):
        return None
    out = [f"1 {label}.0"]
    try:
        for i in range(N_INT):
            v = parts[1 + i]
            if v == "":
                bucket = 0
            else:
                iv = int(v)
                bucket = int(math.log2(iv + 1)) + 1 if iv >= 0 else 0
            out.append(
                f"1 {(np.uint64(i) << np.uint64(40)) | np.uint64(bucket + 1)}"
            )
        for j in range(N_CAT):
            v = parts[1 + N_INT + j]
            key = int(v, 16) & CAT_MASK if v else 0
            out.append(
                f"1 {(np.uint64(N_INT + j) << np.uint64(40)) | np.uint64(key + 1)}"
            )
    except ValueError:
        return None
    return " ".join(out)


def write_real_files(data_dir: str, workdir: str, rows: int, n_files: int = 8):
    src = os.path.join(data_dir, "train.txt")
    files = [
        # fixture writer: workdir is this run's scratch space
        # pbox-lint: disable=IO004
        open(os.path.join(workdir, f"part-{i:03d}.txt"), "w")
        for i in range(n_files)
    ]
    n = 0
    with open(src) as f:
        for line in f:
            s = convert_criteo_line(line)
            if s is None:
                continue
            files[n % n_files].write(s + "\n")
            n += 1
            if rows and n >= rows:
                break
    for fh in files:
        fh.close()
    return [fh.name for fh in files], n


def write_synthetic_files(
    workdir: str,
    rows: int,
    n_files: int = 8,
    seed: int = 0,
    world_seed: int = 0,
    vocab_rows: int | None = None,
):
    """Criteo-shaped synthetic with planted logistic structure.

    ``world_seed`` fixes the ground truth (vocab weights); ``seed`` only
    drives row sampling — a held-out eval set shares the world and differs
    in rows, exactly like a real train/test split."""
    world = np.random.default_rng(world_seed)
    rng = np.random.default_rng(seed)
    # per-slot vocabulary with power-law frequencies (categorical skew);
    # categorical vocab scales with the dataset so keys repeat enough for
    # their embeddings to learn (Criteo's own hot head dominates likewise)
    # vocab_rows pins the key space/world: an eval split must pass the
    # TRAIN row count here or it lives in a different world
    vr = vocab_rows if vocab_rows is not None else rows
    vocab = [
        64 if i < N_INT else max(1000, min(20_000, vr // 12))
        for i in range(N_SLOTS)
    ]
    # planted per-key latent weight; informative slots get higher variance
    slot_strength = world.uniform(0.2, 1.0, N_SLOTS)
    key_w = [
        world.normal(0.0, slot_strength[s], vocab[s]) for s in range(N_SLOTS)
    ]
    bias = -1.1  # ~25% positive rate like Criteo
    files = []
    per = rows // n_files
    for fi in range(n_files):
        path = os.path.join(workdir, f"part-{fi:03d}.txt")
        # zipf-ish draw: mix hot head and uniform tail (~70% of traffic on
        # ~2% of keys, the categorical skew that makes CTR tables work)
        keys = np.empty((per, N_SLOTS), np.int64)
        for s in range(N_SLOTS):
            hot = rng.integers(0, max(vocab[s] // 50, 2), per)
            cold = rng.integers(0, vocab[s], per)
            keys[:, s] = np.where(rng.random(per) < 0.7, hot, cold)
        # logit std ~2: Bayes AUC ~0.9, so a trained model has real signal
        # to recover and the held-out number is meaningful
        logit = bias + sum(
            key_w[s][keys[:, s]] for s in range(N_SLOTS)
        ) / 2.0
        labels = (rng.random(per) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int32)
        # fixture writer: workdir is this run's scratch space
        # pbox-lint: disable=IO004
        with open(path, "w") as f:
            for i in range(per):
                f.write(
                    f"1 {labels[i]}.0 "
                    + " ".join(
                        f"1 {(s << 40) | (int(keys[i, s]) + 1)}"
                        for s in range(N_SLOTS)
                    )
                    + "\n"
                )
        files.append(path)
    return files, per * n_files


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", help="dir containing Criteo-Kaggle train.txt")
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--embedx", type=int, default=8)
    ap.add_argument("--model", choices=["deepfm", "lr"], default="deepfm")
    ap.add_argument(
        "--cpu", action="store_true",
        help="pin the CPU backend (same as JAX_PLATFORMS=cpu)",
    )
    ap.add_argument("--out", default=os.path.join(os.path.dirname(__file__), "..", "CONVERGENCE.json"))
    args = ap.parse_args()
    if not args.synthetic and not args.data_dir:
        ap.error("pick --synthetic or --data-dir")

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import optax

    from paddlebox_tpu.data import BoxPSDataset, SlotInfo, SlotSchema
    from paddlebox_tpu.models import DeepFM, LogisticRegression
    from paddlebox_tpu.table import (
        HostSparseTable,
        SparseOptimizerConfig,
        ValueLayout,
    )
    from paddlebox_tpu.train import CTRTrainer, TrainStepConfig

    t0 = time.time()
    with tempfile.TemporaryDirectory() as workdir:
        if args.synthetic:
            files, n_rows = write_synthetic_files(workdir, args.rows)
            mode = "synthetic-criteo-shaped"
        else:
            files, n_rows = write_real_files(args.data_dir, workdir, args.rows)
            mode = "criteo-kaggle"
        schema = SlotSchema(
            [SlotInfo("label", type="float", dense=True, dim=1)]
            + [SlotInfo(f"s{i}") for i in range(N_SLOTS)],
            label_slot="label",
        )
        layout = ValueLayout(embedx_dim=args.embedx)
        opt_cfg = SparseOptimizerConfig(
            embed_lr=0.1, embedx_lr=0.1, embedx_threshold=0.0, initial_range=0.01
        )
        table = HostSparseTable(layout, opt_cfg, n_shards=64, seed=0)
        ds = BoxPSDataset(schema, table, batch_size=args.batch, seed=0,
                          shuffle_mode="local")
        ds.set_filelist(files)
        if args.model == "deepfm":
            model = DeepFM(num_slots=N_SLOTS, feat_width=layout.pull_width,
                           embedx_dim=args.embedx, hidden=(256, 128))
        else:
            model = LogisticRegression(num_slots=N_SLOTS, feat_width=layout.pull_width)
        cfg = TrainStepConfig(
            num_slots=N_SLOTS, batch_size=args.batch, layout=layout,
            sparse_opt=opt_cfg, auc_buckets=100_000, check_nan=True,
        )
        tr = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-3))
        tr.init_params(jax.random.PRNGKey(0))
        per_pass = []
        for p in range(args.passes):
            ds.set_date(f"pass{p}")
            ds.load_into_memory()
            ds.begin_pass(round_to=512)
            out = tr.train_pass(ds)
            ds.end_pass(tr.trained_table(), shrink=False)
            per_pass.append(round(out["auc"], 4))
            print(f"pass {p}: auc={out['auc']:.4f} loss={out['loss']:.4f}",
                  file=sys.stderr)
        # held-out eval: FRESH rows from the same distribution through the
        # metrics-only eval step (SetTestMode) — generalization, not
        # memorization, is what quality parity means
        eval_auc = None
        if args.synthetic:
            eval_dir = os.path.join(workdir, "eval")
            os.makedirs(eval_dir)
            eval_files, _ = write_synthetic_files(
                eval_dir, max(args.rows // 4, 20_000), seed=1234,
                vocab_rows=args.rows,
            )
            ds.set_date("eval")
            ds.set_filelist(eval_files)
            ds.load_into_memory()
            ds.begin_pass(round_to=512)
            tr.set_test_mode(True)
            ev = tr.train_pass(ds)
            tr.set_test_mode(False)
            ds.end_pass(tr.trained_table(), shrink=False)
            eval_auc = round(ev["auc"], 4)
            print(f"held-out eval: auc={eval_auc:.4f}", file=sys.stderr)
        artifact = {
            "metric": "ctr_convergence_auc",
            "mode": mode,
            "model": args.model,
            "rows": n_rows,
            "passes": args.passes,
            "batch": args.batch,
            "embedx_dim": args.embedx,
            "auc_per_pass": per_pass,
            "final_auc": per_pass[-1],
            "holdout_eval_auc": eval_auc,
            "platform": jax.devices()[0].platform,
            "wall_s": round(time.time() - t0, 1),
            "table_keys": len(table),
        }
    out_path = os.path.abspath(args.out)
    from paddlebox_tpu.utils.fs import atomic_write

    with atomic_write(out_path) as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact))


if __name__ == "__main__":
    main()
