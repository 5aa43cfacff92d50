"""All five BASELINE.json configs, one command: per-config end-to-end
training throughput + AUC on synthetic data at each config's shape.

The benchmark's cells (BENCHMARK.json) measure configs 3 and 4 at full
shape on the chip; this harness proves all five configurations RUN end to
end on the same machinery:

  1. LR on Criteo-shaped slots (single-device, plain logistic regression)
  2. Wide&Deep (wide linear arm + deep tower)
  3. DeepFM (reduced shape here; the cell deepfm_criteo measures the full one)
  4. DNN+DCN multi-slot (108 sparse slots, cross network)
  5. MMoE multi-task bottom (shared experts, CTR head)

Prints one JSON line per config. Usage:
  python tools/config_bench.py [--rows N] [--batches N]
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from paddlebox_tpu.utils.backendguard import bring_up  # noqa: E402


def write_files(tmpdir, rng, n_rows, n_slots, key_space):
    path = os.path.join(tmpdir, "part-000.txt")
    hot = rng.integers(1, 1 << 10, (n_rows, n_slots))
    cold = rng.integers(1, key_space, (n_rows, n_slots))
    keys = np.where(rng.random((n_rows, n_slots)) < 0.3, hot, cold)
    labels = (rng.random(n_rows) < 0.2).astype(np.int32)
    with open(path, "w") as f:
        for i in range(n_rows):
            f.write(
                f"1 {labels[i]}.0 "
                + " ".join(f"1 {k}" for k in keys[i])
                + "\n"
            )
    return [path]


def convert_data_dir(data_dir: str, workdir: str):
    """Real-format (Kaggle Criteo) dir -> converted slot-format files.

    Every *.txt in the dir converts line-by-line via convert_criteo_line;
    malformed/truncated lines take the reject path. Returns (files,
    accepted, rejected)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from criteo_convergence import convert_criteo_line

    out_files, n_ok, n_rej = [], 0, 0
    for fn in sorted(os.listdir(data_dir)):
        if not fn.endswith(".txt"):
            continue
        op = os.path.join(workdir, "conv-" + fn)
        # scratch conversion, consumed by this same bench run
        # pbox-lint: disable=IO004
        with open(os.path.join(data_dir, fn)) as fi, open(op, "w") as fo:
            for line in fi:
                s = line.rstrip("\n")
                out = convert_criteo_line(s) if s else None
                if out is None:
                    n_rej += 1
                    continue
                fo.write(out + "\n")
                n_ok += 1
        out_files.append(op)
    if not out_files or n_ok == 0:
        raise ValueError(f"no usable *.txt lines under {data_dir}")
    return out_files, n_ok, n_rej


def run_config(name, model_fn, n_slots, batch, embedx, rows, batches,
               key_space, data_files=None):
    import jax
    import optax

    from paddlebox_tpu.data import BoxPSDataset, SlotInfo, SlotSchema
    from paddlebox_tpu.table import (
        HostSparseTable,
        SparseOptimizerConfig,
        ValueLayout,
    )
    from paddlebox_tpu.train import CTRTrainer, TrainStepConfig

    rng = np.random.default_rng(0)
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(n_slots)],
        label_slot="label",
    )
    layout = ValueLayout(embedx_dim=embedx)
    opt_cfg = SparseOptimizerConfig(embedx_threshold=0.0)
    table = HostSparseTable(layout, opt_cfg, n_shards=8, seed=0)
    with tempfile.TemporaryDirectory() as tmpdir:
        files = (
            data_files
            if data_files is not None
            else write_files(tmpdir, rng, rows, n_slots, key_space)
        )
        ds = BoxPSDataset(schema, table, batch_size=batch, shuffle_mode="local", seed=0)
        ds.set_filelist(files)
        ds.load_into_memory()
        ds.begin_pass(round_to=256)
        model = model_fn(layout)
        cfg = TrainStepConfig(
            num_slots=n_slots, batch_size=batch, layout=layout,
            sparse_opt=opt_cfg, auc_buckets=10_000,
        )
        tr = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-3))
        tr.init_params(jax.random.PRNGKey(0))
        tr.prepare_pass(ds, n_batches=batches)
        tr.train_pass(ds, n_batches=min(8, batches))  # warm
        t0 = time.perf_counter()
        out = tr.train_pass(ds, n_batches=batches)
        dt = time.perf_counter() - t0
        ds.end_pass(tr.trained_table_device())
        table.drain_pending()
    return {
        "config": name,
        "slots": n_slots,
        "batch": batch,
        "samples_per_sec": round(batches * batch / dt, 1),
        "auc": round(out["auc_cumulative"], 4),
        "loss": round(out["loss"], 4),
    }


def main():
    rows = 65_536
    batches = 24
    data_dir = None
    for i, a in enumerate(sys.argv):
        if a == "--rows":
            rows = int(sys.argv[i + 1])
        if a == "--batches":
            batches = int(sys.argv[i + 1])
        if a == "--data-dir":
            data_dir = sys.argv[i + 1]
    device = bring_up()  # whatever jax brings up; every line is stamped

    from paddlebox_tpu.models import (
        DCN,
        DeepFM,
        LogisticRegression,
        MMoE,
        WideDeep,
        task_head,
    )

    configs = [
        (
            "1-lr-criteo",
            lambda lay: LogisticRegression(39, lay.pull_width),
            39, 1024, 8,
        ),
        (
            "2-widedeep",
            lambda lay: WideDeep(39, lay.pull_width, hidden=(64, 32)),
            39, 1024, 8,
        ),
        (
            "3-deepfm-small",
            lambda lay: DeepFM(
                num_slots=39, feat_width=lay.pull_width, embedx_dim=8,
                hidden=(64, 32),
            ),
            39, 1024, 8,
        ),
        (
            "4-dcn-multislot",
            lambda lay: DCN(108, lay.pull_width, n_cross=3, hidden=(64, 32)),
            108, 512, 8,
        ),
        (
            "5-mmoe",
            lambda lay: task_head(
                MMoE(39, lay.pull_width, n_experts=4, expert_hidden=(32,)),
                task=0,
            ),
            39, 1024, 8,
        ),
    ]
    data_ctx = tempfile.TemporaryDirectory() if data_dir else None
    data_files = None
    n_ok = n_rej = 0
    if data_dir:
        # real-format mode: every config runs the converted 39-slot Criteo
        # stream (the day real data appears, point --data-dir at it);
        # malformed lines take the reject path and are counted
        data_files, n_ok, n_rej = convert_data_dir(data_dir, data_ctx.name)
        print(
            json.dumps({
                "data_dir": data_dir, "accepted": n_ok, "rejected": n_rej,
            }),
            flush=True,
        )
    try:
        for name, fn, n_slots, batch, embedx in configs:
            n_batches = batches
            if data_dir:
                n_slots = 39  # the converted stream's slot count
                if name.startswith("4-dcn"):
                    from paddlebox_tpu.models import DCN as _DCN

                    fn = lambda lay: _DCN(  # noqa: E731
                        39, lay.pull_width, n_cross=3, hidden=(64, 32)
                    )
                # size this config to the real corpus (wraparound keeps
                # shapes); per-config locals so one config's clamp can't
                # leak into the next
                batch = min(batch, max(64, n_ok // 4))
                n_batches = min(batches, max(2, n_ok // batch))
            try:
                r = run_config(
                    name, fn, n_slots, batch, embedx, rows, n_batches,
                    key_space=1 << 20, data_files=data_files,
                )
                r["platform"] = device.platform
                r["device_kind"] = device.device_kind
                if data_dir:
                    r["real_format"] = True
                    r["rejected_lines"] = n_rej
                print(json.dumps(r), flush=True)
            except Exception as e:  # one config failing must not hide the rest
                print(json.dumps({"config": name, "error": repr(e)[:300]}), flush=True)
    finally:
        if data_ctx is not None:
            data_ctx.cleanup()


if __name__ == "__main__":
    main()
