"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process, no arguments, from a fresh copy of the checkout, on a machine
with a TPU:

    python chip_smoke.py

It drives the main path once through the entry points a user calls (the
README quickstart), at DeepFM's full supported width — 39 sparse slots,
embedx 16 (row width 21), tower 512/256/128, batch 4096, 100,000 AUC
buckets, default flags — with depth cut to a few supersteps per pass and
weights and data made from a seed:

1. the Pallas kernel that is on a cell's path, the fused causal attention of
   ``ops/pallas_kernels.py``, compiled WITHOUT interpret at the token cell's
   shape, forward and backward, and checked against the blocked XLA form it
   replaces on a TPU (``models/attention.py::_attend_block``);
2. a three-pass day: ``BoxWrapper.make_dataset`` -> ``load_into_memory`` ->
   ``begin_pass`` -> ``CTRTrainer.prepare_pass`` / ``train_pass`` ->
   ``end_pass(trained_table_device())``, with ``save_base`` after pass 1 and
   ``save_delta`` after pass 3. Pass 2 ends WITHOUT a save because every save
   drains the device carrier (``HostSparseTable.drain_pending``): only an
   unsaved boundary reaches ``PassWorkingSet._finalize_spliced``, and the
   smoke must see the carried boundary run on the device;
3. ``load_model`` into a fresh table and trainer, rows and dense compared;
4. the scoring path on the same chip: a ``Follower`` over the checkpoint
   root just written -> ``ScoreServer`` + ``Scorer`` -> a handful of
   ``submit()`` requests, answers compared bitwise with trainer-direct
   scores over ``table_source`` (the repo's own gate, docs/SERVING.md);
5. with more than one chip, the same day over ``make_mesh()`` of all local
   chips, shardings checked, losses compared with the one-chip day.

Any phase failure ends the run non-zero: nothing is caught and skipped. There
is no CPU mode — without a TPU the script says what it found and exits 2
before any phase. The phases are functions of their sizes so that
tests/test_chip_smoke.py can run them tiny on the virtual CPU mesh.

The last line of standard output is one JSON object with exactly these keys,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``, the
device as JAX reports it. The line before it, ``chip_smoke: summary {json}``,
holds what the run found: per-phase seconds, warm-up, compile-cache counters,
compilations across each pass boundary and peak device memory. It claims no
speed: ``"claim": null``. A failed run prints neither.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from collections import Counter
from contextlib import contextmanager
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from paddlebox_tpu import BoxWrapper, config
from paddlebox_tpu.data import SlotInfo, SlotSchema
from paddlebox_tpu.data.parser import parse_line
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.models.attention import _attend_block
from paddlebox_tpu.ops.pallas_kernels import causal_attention
from paddlebox_tpu.parallel import make_mesh
from paddlebox_tpu.serve import Follower, Scorer, ScoreServer, table_source
from paddlebox_tpu.table import SparseOptimizerConfig
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig
from paddlebox_tpu.utils import backendguard, compilecache, native
from paddlebox_tpu.utils.monitor import STAT_GET

# DeepFM at its full supported width (BASELINE.json config 3); only the
# record count is cut: one pass is 16 batches, i.e. the scan program of
# resident_scan_batches=8 steps runs more than once per train_pass.
FULL = dict(
    num_slots=39,
    embedx_dim=16,
    hidden=(512, 256, 128),
    batch=4096,
    auc_buckets=100_000,
    n_files=4,
    records_per_file=16_384,
    key_space=1 << 22,
    hot_space=1 << 12,
    round_to=512,
    host_shards=64,
    score_request_records=64,
)
# the fused attention at the token cell's shape (glm47_flash_ep8: 2 records of
# 4,096 tokens, 20 heads of 256, square tiles of 512)
KERNEL_SHAPE = dict(batch=2, seq=4096, heads=20, head_dim=256, block=512)
# kernel against oracle by the norm. Both round the probabilities to bfloat16
# (2**-9 = 1.95e-3 an element), at different scales: the outputs read 1.97e-3
# to 2.12e-3 apart over shapes and seeds (CPU, interpreted), so the bound is
# two roundings and not tests/test_fused_attention.py's 2e-3, which holds at
# that file's seed; the gradients' bound is that file's.
KERNEL_OUT_RTOL = 4e-3
KERNEL_GRAD_RTOL = 6e-3

DATE = "20260926"
N_PASSES = 3  # base after 1, no save after 2 (carried boundary), delta after 3

# embedx active from the first show; shrink off so that every
# trained key stays in the published model — the scoring parity probe needs
# keys both sides hold (a key shrunk from the trainer's table would be
# re-created there by the reference pull and absent from the follower)
SPARSE_OPT = SparseOptimizerConfig(embedx_threshold=0.0, shrink_threshold=0.0)

# One-chip vs all-chips agreement, in the order of how sharp each check is.
# (a) Show/click counters of sampled keys in the two final host tables:
#     integer increments and the same decay sequence on both sides, so any
#     lost, doubled or mis-routed push shows here at f32 rounding.
# (b) Loss of the first superstep (8 steps): tests/test_sharded.py pins 1e-5
#     for step 1 on the CPU; on the chip the tower runs at the default f32
#     matmul precision (bf16 passes) and each chip reduces a quarter of the
#     batch, measured 2e-5 (CPU, full width) — 1e-3 leaves room for the chip.
# (c) Pass-mean losses and embedding columns: loose. Adam normalises
#     noise-level gradients to +-lr steps, so the 512-wide tower's
#     trajectories separate: at full width on the CPU in f32 the per-batch
#     loss differs by up to 6e-2 and the third pass's mean by 1.8e-2, on the
#     chip by 2.4e-2 (PR 21 runs) — a property of the optimiser, not of the
#     device — while the sparse rows stay within 1e-5. The bounds only catch
#     a structurally wrong run (a dropped shard moves the loss by tens of %).
MESH_COUNTER_RTOL = 1e-6
MESH_FIRST_LOSS_RTOL = 1e-3
MESH_PASS_LOSS_RTOL = 5e-2
MESH_EMBED_ATOL = 1e-2


class CompileCounter:
    """Counts XLA programs built or loaded from the persistent cache
    (jax's backend-compile event fires for both), process-wide, in total
    and by program name."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        self.by_name: Counter = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.n += 1
            self.by_name[kwargs.get("fun_name", "?")] += 1


@contextmanager
def timed(seconds: Dict[str, float], name: str):
    t0 = time.perf_counter()
    yield
    seconds[name] = round(seconds.get(name, 0.0) + time.perf_counter() - t0, 3)


def make_schema(num_slots: int) -> SlotSchema:
    return SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(num_slots)],
        label_slot="label",
    )


def write_pass_files(dirpath: str, prefix: str, rng, sizes: dict, reuse_pool=None):
    """Slot-format text at CTR-ish shapes: one key per slot, a hot head
    (25% of draws) over a uniform tail; 75% of tail draws recur from the
    previous pass's pool, the regime the carried boundary exists for.
    Returns (files, this pass's cold-key pool)."""
    S, n = sizes["num_slots"], sizes["records_per_file"]
    files, pool = [], []
    for fi in range(sizes["n_files"]):
        hot = rng.integers(1, sizes["hot_space"], (n, S))
        cold = rng.integers(1, sizes["key_space"], (n, S))
        if reuse_pool is not None:
            recur = reuse_pool[rng.integers(0, len(reuse_pool), (n, S))]
            cold = np.where(rng.random((n, S)) < 0.75, recur, cold)
        take_hot = rng.random((n, S)) < 0.25
        keys = np.where(take_hot, hot, cold)
        pool.append(keys[~take_hot])
        labels = (rng.random(n) < 0.2).astype(np.int64)
        path = os.path.join(dirpath, f"{prefix}-{fi:03d}.txt")
        with open(path, "w") as f:
            for lab, row in zip(labels.tolist(), keys.tolist()):
                f.write(f"1 {lab}.0 1 " + " 1 ".join(map(str, row)) + "\n")
        files.append(path)
    return files, np.concatenate(pool)


def write_day(dirpath: str, sizes: dict, seed: int) -> List[List[str]]:
    rng = np.random.default_rng(seed)
    day, pool = [], None
    for p in range(N_PASSES):
        files, pool = write_pass_files(dirpath, f"pass{p}", rng, sizes, pool)
        day.append(files)
    return day


def check_pallas_kernels(batch: int, seq: int, heads: int, head_dim: int, block: int,
                         interpret: bool) -> dict:
    """Compile and run the fused causal attention, forward and backward, and
    compare it with the blocked XLA form (relative error by the norm)."""
    shape = (batch, seq, heads, head_dim)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v = (jax.random.normal(a, shape).astype(jnp.bfloat16) for a in ks[:3])
    g = jax.random.normal(ks[3], shape)
    scale = head_dim ** -0.5

    def fused(q, k, v):
        return causal_attention(q, k, v, scale, block, interpret)

    def blocked(q, k, v):
        return jnp.concatenate(
            [_attend_block(q, k, v, i, block, scale, 1, None, None) for i in range(0, seq, block)],
            axis=1)

    def both_ways(f, q, k, v, g):
        o, back = jax.vjp(f, q, k, v)
        return (o, *back(g))

    both_ways = jax.jit(both_ways, static_argnums=0)
    seconds: Dict[str, float] = {}
    with timed(seconds, "fused_s"):
        got = jax.block_until_ready(both_ways(fused, q, k, v, g))
    with timed(seconds, "blocked_s"):
        want = jax.block_until_ready(both_ways(blocked, q, k, v, g))
    gaps = {}
    for name, a, b, rtol in zip(("o", "dq", "dk", "dv"), got, want,
                                (KERNEL_OUT_RTOL,) + 3 * (KERNEL_GRAD_RTOL,)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        gaps[name] = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        if not gaps[name] < rtol:
            raise AssertionError(
                f"fused attention: {name} is {gaps[name]:.3g} from the blocked form, over {rtol}")
    return {
        "kernel": "causal_attention",
        "shape": {"batch": batch, "seq": seq, "heads": heads, "head_dim": head_dim,
                  "block": block},
        "interpret": interpret,
        "rel_gap": gaps,
        **seconds,
    }


def measure_table_tiling(rows: int, width: int) -> Optional[dict]:
    """Device bytes an f32 [rows, width] array really occupies (from the
    allocator's own counter) and the tiled layout the runtime gave it — what
    a narrow-row table costs in HBM. None where the backend reports no
    memory stats (the CPU)."""
    dev = jax.devices()[0]
    if dev.memory_stats() is None:
        return None
    before = dev.memory_stats()["bytes_in_use"]
    x = jax.block_until_ready(jnp.zeros((rows, width), jnp.float32))
    used = dev.memory_stats()["bytes_in_use"] - before
    logical = rows * width * 4
    return {
        "rows": rows,
        "width": width,
        "logical_bytes": logical,
        "device_bytes": int(used),
        "ratio": round(used / logical, 3),
        "layout": str(x.format.layout),
    }


def build_trainer(sizes: dict, layout, plan=None, seed: int = 0):
    """(model, single-device step config, trainer) at ``sizes``; with a mesh
    plan the trainer's config carries the per-device batch and the axis."""
    S, B = sizes["num_slots"], sizes["batch"]
    model = DeepFM(
        num_slots=S,
        feat_width=layout.pull_width,
        embedx_dim=sizes["embedx_dim"],
        hidden=sizes["hidden"],
    )
    n = 1 if plan is None else plan.n_devices
    cfg = TrainStepConfig(
        num_slots=S,
        batch_size=B // n,
        layout=layout,
        sparse_opt=SPARSE_OPT,
        auc_buckets=sizes["auc_buckets"],
        axis_name=None if plan is None else plan.axis,
    )
    trainer = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-3), plan=plan)
    trainer.init_params(jax.random.PRNGKey(seed))
    return model, cfg, trainer


def run_day(sizes: dict, day_files, ckpt_root: str, counter: CompileCounter,
            plan=None) -> dict:
    """The README pass loop over ``day_files`` (one file list per pass).

    Returns the day's record plus the live objects later phases need
    (``box``, ``trainer``, ``sample_keys`` of the last pass). Every "the
    path it meant to take" check lives here, next to the call it checks."""
    n_dev = 1 if plan is None else plan.n_devices
    K = int(config.get_flag("resident_scan_batches"))
    n_batches = sizes["n_files"] * sizes["records_per_file"] // sizes["batch"]
    if n_batches < 2 * K:
        raise ValueError(f"{n_batches} batches per pass < 2 x scan length {K}")
    box = BoxWrapper(
        embedx_dim=sizes["embedx_dim"],
        sparse_opt=SPARSE_OPT,
        n_host_shards=sizes["host_shards"],
        seed=0,
    )
    W = box.layout.width
    ds = box.make_dataset(
        make_schema(sizes["num_slots"]),
        batch_size=sizes["batch"],
        n_mesh_shards=n_dev,
        shuffle_mode="local",
        seed=0,
    )
    _, _, trainer = build_trainer(sizes, box.layout, plan)
    passes = []
    c_prev = counter.n  # compilations since the previous pass stopped training
    for p, files in enumerate(day_files):
        rec: Dict = {}
        with timed(rec, "load_s"):
            ds.set_date(DATE)
            ds.set_filelist(files)
            ds.load_into_memory()
        with timed(rec, "begin_pass_s"):
            dev_table = ds.begin_pass(round_to=sizes["round_to"])
        # the classic finalize returns the host array; only the spliced
        # boundary hands back a device array
        rec["spliced"] = isinstance(dev_table, jax.Array)
        if ds.store is None:
            raise AssertionError(
                "native tier not loaded: dataset fell back to the Python parser")
        with timed(rec, "prepare_s"):
            trainer.prepare_pass(ds, n_batches=n_batches)
        if not trainer._use_resident(ds, False, False):
            raise AssertionError("trainer would take the host-packer path")
        rec["keys"] = int(ds.ws.n_keys)
        rec["table_rows"] = int(n_dev * ds.ws.capacity)
        rec["boundary_compiles"] = counter.n - c_prev
        # sampled BEFORE training: the spliced table is donated into the step
        stride = max(1, ds.ws.n_keys // 2048)
        sample_keys = ds.ws.sorted_keys[::stride].copy()
        sample_rows = ds.ws.row_of_sorted[::stride]
        open_rows = np.asarray(dev_table.reshape(-1, W)[sample_rows])

        c0, scan0 = counter.n, counter.by_name["jit(superstep)"]
        with timed(rec, "first_superstep_s"):
            first = trainer.train_pass(ds, n_batches=K)  # compile + upload
        rec["first_loss"] = first["loss"]
        c1 = counter.n
        with timed(rec, "train_s"):
            out = trainer.train_pass(ds, n_batches=n_batches)
        rec["train_compiles"] = [c1 - c0, counter.n - c1]
        c_prev = counter.n
        if counter.by_name["jit(superstep)"] - scan0 != 1:
            raise AssertionError(
                f"pass {p}: the scan program compiled "
                f"{counter.by_name['jit(superstep)'] - scan0} times, not once")
        rec["loss"], rec["auc"] = out["loss"], float(out["auc"])
        if not (np.isfinite(first["loss"]) and np.isfinite(out["loss"])):
            raise AssertionError(f"pass {p}: non-finite loss {first} {out}")
        if out["batches"] != n_batches or out["nan_batches"]:
            raise AssertionError(f"pass {p}: {out}")

        trained = trainer.trained_table_device()
        changed = np.any(
            np.asarray(trained.reshape(-1, W)[sample_rows]) != open_rows, axis=1)
        if not changed.all():
            raise AssertionError(
                f"pass {p}: {int((~changed).sum())} of {len(changed)} sampled "
                "rows equal their pass-open values after a full epoch")
        if plan is not None:
            rec["sharding"] = check_mesh_placement(trainer, plan)
        with timed(rec, "end_pass_s"):
            ds.end_pass(trained)
        with timed(rec, "save_s"):
            if p == 0:
                box.save_base(ckpt_root, DATE, trainer)
            elif p == N_PASSES - 1:
                box.save_delta(ckpt_root, DATE, trainer)
        passes.append(rec)
    if not passes[-1]["spliced"]:
        raise AssertionError(
            "the carried boundary did not run: the last pass opened through "
            "the classic finalize")
    return {
        "record": {
            "n_devices": n_dev,
            "batches_per_pass": n_batches,
            "passes": passes,
        },
        "box": box,
        "trainer": trainer,
        "sample_keys": sample_keys,
    }


def check_mesh_placement(trainer, plan) -> dict:
    """The table is split over every chip, the resident pass arrays are
    replicated on every chip — checked on the arrays the step really uses."""
    devices = set(plan.mesh.devices.flat)
    table = trainer._state.table
    if table.sharding.device_set != devices or table.sharding.is_fully_replicated:
        raise AssertionError(f"table not sharded over the mesh: {table.sharding}")
    shard_shapes = {s.data.shape for s in table.addressable_shards}
    if shard_shapes != {(1,) + table.shape[1:]}:
        raise AssertionError(f"table shards {shard_shapes} of {table.shape}")
    rp = trainer._resident_cache[2]
    for name in ("rows", "labels"):
        arr = getattr(rp, name)
        if arr.sharding.device_set != devices or not arr.sharding.is_fully_replicated:
            raise AssertionError(
                f"resident {name} not replicated on the mesh: {arr.sharding}")
    return {
        "table_shard_shape": list(next(iter(shard_shapes))),
        "table_devices": len(table.sharding.device_set),
        "resident_devices": len(rp.rows.sharding.device_set),
    }


def check_reload(sizes: dict, day: dict, ckpt_root: str) -> dict:
    """load_model into a fresh table + trainer: sampled rows and every dense
    leaf must come back as the live ones."""
    box, trainer, keys = day["box"], day["trainer"], day["sample_keys"]
    box2 = BoxWrapper(
        embedx_dim=sizes["embedx_dim"],
        sparse_opt=SPARSE_OPT,
        n_host_shards=sizes["host_shards"],
        seed=0,
    )
    # another seed: equal dense leaves afterwards can only come from the load
    _, _, trainer2 = build_trainer(sizes, box2.layout, seed=1)
    state = box2.load_model(ckpt_root, trainer2)
    if state is None or state["delta_idx"] != 1:
        raise AssertionError(f"resume loaded {state}, expected base + 1 delta")
    np.testing.assert_allclose(
        box2.table.pull_or_create(keys), box.table.pull_or_create(keys), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(trainer.params), jax.tree.leaves(trainer2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return {"rows_compared": int(len(keys)), "delta_idx": int(state["delta_idx"])}


def check_serving(sizes: dict, day: dict, ckpt_root: str, probe_lines) -> dict:
    """Follower -> ScoreServer + Scorer over the published root; answers
    must equal trainer-direct scores bitwise (docs/SERVING.md's gate).

    Four requests go one at a time (each is scored alone, the same program
    as its reference), then three at once (the batcher may coalesce them —
    per-example math must not depend on what shares the batch)."""
    box, trainer = day["box"], day["trainer"]
    schema = make_schema(sizes["num_slots"])
    model, cfg, dense_holder = build_trainer(sizes, box.layout, seed=1)
    fol = Follower(
        ckpt_root, box.layout, SPARSE_OPT,
        n_host_shards=sizes["host_shards"], trainer=dense_holder,
    )
    if not fol.poll_once() or fol.version().delta_idx != 1:
        raise AssertionError(
            f"follower at delta_idx {fol.version().delta_idx}, expected 1")
    scorer = Scorer(model, cfg)
    m = sizes["score_request_records"]
    records = [parse_line(ln, schema) for ln in probe_lines[: 7 * m]]
    requests = [records[i * m : (i + 1) * m] for i in range(7)]
    direct = [
        scorer.score_records(
            req, schema, table_source(box.layout, box.table),
            trainer.params, trainer.opt_state)
        for req in requests
    ]
    srv = ScoreServer(fol, scorer, schema)
    srv.start()
    try:
        served = [srv.submit(req).result(timeout=600) for req in requests[:4]]
        burst = [srv.submit(req) for req in requests[4:]]
        served += [p.result(timeout=600) for p in burst]
    finally:
        srv.stop()
    for i, (got, want) in enumerate(zip(served, direct)):
        if got.shape != (m,) or not np.isfinite(got).all():
            raise AssertionError(f"request {i}: bad answer {got.shape}")
        if not np.array_equal(got, want):
            raise AssertionError(
                f"request {i}: follower scores differ from trainer-direct, "
                f"max abs {np.abs(got - want).max()}")
    return {
        "requests": len(requests),
        "records_per_request": m,
        "served_delta_idx": int(fol.version().delta_idx),
        "bitwise_equal": True,
        "batches": int(STAT_GET("serve.batches")),
    }


def compare_days(one: dict, mesh: dict, rows_one, rows_mesh) -> dict:
    """One-chip vs all-chips agreement (checks and bounds: module head).
    ``rows_*`` are the same sampled keys' rows from the two final host
    tables."""
    firsts = [d["passes"][0]["first_loss"] for d in (one, mesh)]
    means = [[p["loss"] for p in d["passes"]] for d in (one, mesh)]
    np.testing.assert_allclose(
        rows_mesh[:, :2], rows_one[:, :2], rtol=MESH_COUNTER_RTOL)  # show, clk
    np.testing.assert_allclose(firsts[1], firsts[0], rtol=MESH_FIRST_LOSS_RTOL)
    np.testing.assert_allclose(means[1], means[0], rtol=MESH_PASS_LOSS_RTOL)
    np.testing.assert_allclose(
        rows_mesh[:, 2:], rows_one[:, 2:], rtol=0, atol=MESH_EMBED_ATOL)
    rel = np.abs(np.array(means[1]) / np.array(means[0]) - 1.0)
    return {
        "rows_compared": int(len(rows_one)),
        "counter_max_abs_diff": float(
            np.abs(rows_mesh[:, :2] - rows_one[:, :2]).max()),
        "embed_max_abs_diff": float(np.abs(rows_mesh[:, 2:] - rows_one[:, 2:]).max()),
        "first_superstep_loss": firsts,
        "first_superstep_rel_diff": float(abs(firsts[1] / firsts[0] - 1.0)),
        "pass_loss_one_chip": means[0],
        "pass_loss_mesh": means[1],
        "pass_loss_max_rel_diff": float(rel.max()),
        "rtol": {"counters": MESH_COUNTER_RTOL, "first_superstep": MESH_FIRST_LOSS_RTOL,
                 "pass_mean": MESH_PASS_LOSS_RTOL, "embed_atol": MESH_EMBED_ATOL},
    }


def progress(name: str, record) -> None:
    """A failed run keeps what the finished phases found (never the last
    line of a successful run: that is the bare result object)."""
    print(f"chip_smoke: {name} {json.dumps(record)}", flush=True)


def result_line(device) -> str:
    """The last line of a successful run: exactly these keys, the device as
    JAX reports it. Everything else the run found goes in the summary line."""
    return json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": device.n_devices,
    }})


def main() -> int:
    device = backendguard.bring_up()
    print(
        f"chip_smoke: platform={device.platform} device_kind={device.device_kind} "
        f"count={device.n_devices} jax={jax.__version__}",
        flush=True,
    )
    if device.platform != "tpu":
        print("chip_smoke: needs a TPU and has no CPU mode — not started",
              file=sys.stderr)
        return 2
    compilecache.enable()
    counter = CompileCounter()
    seconds: Dict[str, float] = {}
    t_start = time.perf_counter()

    with timed(seconds, "native_tier_s"):  # a fresh copy builds csrc/ with g++
        if not native.available() or STAT_GET("native.build_failures"):
            raise AssertionError("native tier (csrc/) did not build or load")
    with timed(seconds, "pallas_kernels_s"):
        kernels = check_pallas_kernels(**KERNEL_SHAPE, interpret=False)
    progress("pallas_kernels", kernels)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        with timed(seconds, "write_data_s"):
            day_files = write_day(work, FULL, seed=0)
        with open(day_files[0][0]) as f:
            probe_lines = [next(f) for _ in range(7 * FULL["score_request_records"])]

        root1 = os.path.join(work, "ckpt-1chip")
        with timed(seconds, "day_one_chip_s"):
            one = run_day(FULL, day_files, root1, counter)
        progress("day_one_chip", one["record"])
        peak = {"one_chip": jax.devices()[0].memory_stats()["peak_bytes_in_use"]}
        with timed(seconds, "reload_s"):
            reload_rec = check_reload(FULL, one, root1)
        with timed(seconds, "serving_s"):
            serving = check_serving(FULL, one, root1, probe_lines)
        progress("reload+serving", {"reload": reload_rec, "serving": serving})
        one_rec, width = one["record"], one["box"].layout.width
        rows_one = one["box"].table.pull_or_create(one["sample_keys"])
        keys_one = one["sample_keys"]
        del one  # the one-chip day's table leaves HBM before the next phases
        tiling = measure_table_tiling(one_rec["passes"][0]["table_rows"], width)
        if tiling is None:
            raise AssertionError("the device reports no memory stats")

        mesh_rec = agreement = None
        if device.n_devices > 1:
            plan = make_mesh()
            with timed(seconds, "day_mesh_s"):
                mesh = run_day(
                    FULL, day_files, os.path.join(work, "ckpt-mesh"), counter, plan)
            mesh_rec = mesh["record"]
            mesh_rec["device_ids"] = [int(d.id) for d in plan.mesh.devices.flat]
            mesh_rec["device_coords"] = [
                list(d.coords) for d in plan.mesh.devices.flat]
            progress("day_mesh", mesh_rec)
            if not np.array_equal(mesh["sample_keys"], keys_one):
                raise AssertionError("the two days did not train the same keys")
            agreement = compare_days(
                one_rec, mesh_rec, rows_one,
                mesh["box"].table.pull_or_create(keys_one))
            # lifetime peaks: device 0's includes the one-chip day
            peak["per_device_after_mesh"] = [
                d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()]

    first = one_rec["passes"][0]
    summary = {
        **json.loads(result_line(device)),
        "jax": jax.__version__,
        "n_devices": device.n_devices,
        "total_s": round(time.perf_counter() - t_start, 3),
        "phase_seconds": seconds,
        "warmup_s": round(first["prepare_s"] + first["first_superstep_s"], 3),
        "compile_cache": compilecache.stats(),
        "compilations_total": counter.n,
        "boundary_compiles": [
            p["boundary_compiles"] for p in one_rec["passes"][1:]],
        "peak_bytes_in_use": peak,
        "table_tiling": tiling,
        "pallas_kernels": kernels,
        "native_tier": True,
        "day_one_chip": one_rec,
        "reload": reload_rec,
        "serving": serving,
        "day_mesh": mesh_rec,
        "mesh_vs_one_chip": agreement,
        "claim": None,
    }
    progress("summary", summary)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
