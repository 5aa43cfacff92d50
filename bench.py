"""Headline benchmark: END-TO-END CTR training throughput, samples/sec/chip.

Times ``CTRTrainer.train_pass`` wall-clock at the flagship DeepFM shape —
everything between "records in memory" and "trained table": native batch
pack (C++ ragged gather + dedup), background packer threads, host->device
upload, and the jitted device step (sparse pull -> fused seqpool+CVM ->
DeepFM fwd/bwd -> sparse adagrad push -> dense adam -> online AUC). This is
the full BoxPSWorker::TrainFiles loop (boxps_worker.cc:420-466) including
the data-feed half the reference runs in MiniBatchGpuPack worker threads
(data_feed.h:1418-1542) — not just the device program.

Load (file parse) and pass finalize times are reported as sub-fields; the
headline metric matches the reference's definition of training throughput
(records consumed per second while the trainer runs).

Baseline (BASELINE.json): 1M samples/sec on 64 chips => 15625 samples/sec/chip.
Prints ONE json line: {"metric", "value", "unit", "vs_baseline", "device", ...}.
Needs a TPU: without one it exits non-zero and prints no result (a CPU
rate is never written under a per-chip name).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

# Criteo-DeepFM-ish flagship shape (BASELINE.md config 3)
NUM_SLOTS = 39
EMBEDX_DIM = 16
BATCH = 4096
HIDDEN = (512, 256, 128)
N_FILES = 16
RECORDS_PER_FILE = 8192  # 131072 records = 32 batches per epoch
KEY_SPACE = 1 << 22
TRAIN_BATCHES = 96  # 3 epochs over the pass (wrap-around, lockstep parity)
BASELINE_PER_CHIP = 1_000_000 / 64


def _logkey(search_id: int, cmatch: int, rank: int) -> str:
    """Reference logkey layout (data_feed.cc SlotRecord parse): 11 pad chars,
    3-hex cmatch, 2-hex rank, 16-hex search_id."""
    return (
        "0" * 11
        + format(cmatch, "03x")
        + format(rank, "02x")
        + format(search_id, "016x")
    )


def write_files(tmpdir: str, rng, reuse_pool=None, prefix="part", pv=False) -> tuple:
    """Synthetic slot-format text at CTR-ish shapes: one key per slot drawn
    zipf-ish (hot head + uniform tail), binary label.

    ``reuse_pool``: previous pass's cold-key pool — 75% of cold draws come
    from it, modeling the high day-over-day key recurrence of real CTR
    streams (the regime the device-carried pass boundary exploits).
    ``pv``: prepend a logkey column grouping consecutive records into
    queries of 1-4 ads, so the join phase (PvMerge) has real pv structure.
    Returns (files, cold key pool of this pass)."""
    files = []
    pool_parts = []
    search_id = 1
    for fi in range(N_FILES):
        n = RECORDS_PER_FILE
        hot = rng.integers(1, 1 << 12, (n, NUM_SLOTS))
        cold = rng.integers(1, KEY_SPACE, (n, NUM_SLOTS))
        if reuse_pool is not None:
            recur = reuse_pool[rng.integers(0, len(reuse_pool), (n, NUM_SLOTS))]
            cold = np.where(rng.random((n, NUM_SLOTS)) < 0.75, recur, cold)
        take_hot = rng.random((n, NUM_SLOTS)) < 0.25
        keys = np.where(take_hot, hot, cold)
        pool_parts.append(keys[~take_hot])
        labels = (rng.random(n) < 0.2).astype(np.int32)
        logkeys = None
        if pv:
            # group rows into queries: 1-4 ads per pv, ranks 1..n_ads
            logkeys = []
            i = 0
            while i < n:
                n_ads = int(rng.integers(1, 5))
                for r in range(1, min(n_ads, n - i) + 1):
                    logkeys.append(_logkey(search_id, 222, r))
                search_id += 1
                i += n_ads
        path = os.path.join(tmpdir, f"{prefix}-{fi:03d}.txt")
        with open(path, "w") as f:
            for i in range(n):
                row = keys[i]
                head = f"1 {logkeys[i]} " if pv else ""
                f.write(
                    head
                    + f"1 {labels[i]}.0 "
                    + " ".join(f"1 {k}" for k in row)
                    + "\n"
                )
        files.append(path)
    return files, np.concatenate(pool_parts)


def pv_mode_enabled() -> bool:
    """PBOX_BENCH_PV=1 benches the JOIN phase: pv-merged batches with
    rank_offset through the rank-attention tower (the two-phase join/update
    pipeline's other half; EnablePvMerge branch, data_feed.cc:2165-2198)."""
    return os.environ.get("PBOX_BENCH_PV", "0") == "1"


def _plan_source() -> str:
    """Provenance of the active kernel plan ("builtin defaults" or the
    artifact path), for the bench JSON record."""
    from paddlebox_tpu.ops.kernel_plan import get_plan

    return get_plan().source


def main():
    profile = "--profile" in sys.argv
    from paddlebox_tpu.utils import backendguard, compilecache

    # a per-chip metric needs the chip: no accelerator -> typed error,
    # non-zero exit, no result line
    try:
        device = backendguard.bring_up(require="tpu")
    except backendguard.BackendUnavailableError as e:
        sys.exit(f"bench.py: {e}")

    import jax
    import optax

    # before any compilation, so warmup_s is a cold-vs-warm pair across
    # consecutive runs (placement: utils/compilecache)
    compilecache.enable()

    from paddlebox_tpu.data import BoxPSDataset, SlotInfo, SlotSchema
    from paddlebox_tpu.models import DeepFM, RankDeepFM
    from paddlebox_tpu.table import (
        HostSparseTable,
        SparseOptimizerConfig,
        ValueLayout,
    )
    from paddlebox_tpu.train import CTRTrainer, TrainStepConfig
    from paddlebox_tpu.utils.monitor import STAT_GET
    from paddlebox_tpu.utils.monitor import all_histograms as _all_histograms

    pv = pv_mode_enabled()
    rng = np.random.default_rng(0)
    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(NUM_SLOTS)],
        label_slot="label",
        parse_logkey=pv,
    )
    layout = ValueLayout(embedx_dim=EMBEDX_DIM)
    opt_cfg = SparseOptimizerConfig(embedx_threshold=0.0)
    table = HostSparseTable(layout, opt_cfg, n_shards=64, seed=0)

    with tempfile.TemporaryDirectory() as tmpdir:
        files, key_pool = write_files(tmpdir, rng, pv=pv)

        ds = BoxPSDataset(
            schema, table, batch_size=BATCH, shuffle_mode="local", seed=0
        )
        ds.set_filelist(files)
        t0 = time.perf_counter()
        ds.load_into_memory()
        load_s = time.perf_counter() - t0
        native_store = ds.store is not None

        t0 = time.perf_counter()
        ds.begin_pass(round_to=512)
        if pv:
            # join phase: group records into pvs, serve rank_offset batches
            # (max_rank must match the model's attention block count — the
            # generator emits ranks 1..4)
            ds.set_current_phase(1)
            ds.preprocess_instance(max_rank=4)
        finalize_s = time.perf_counter() - t0

        base = DeepFM(
            num_slots=NUM_SLOTS,
            feat_width=layout.pull_width,
            embedx_dim=EMBEDX_DIM,
            hidden=HIDDEN,
        )
        if pv:
            model = RankDeepFM(
                base, NUM_SLOTS * layout.pull_width, max_rank=4
            )
        else:
            model = base
        cfg = TrainStepConfig(
            num_slots=NUM_SLOTS,
            batch_size=BATCH,
            layout=layout,
            sparse_opt=opt_cfg,
            auc_buckets=100_000,
            model_takes_rank_offset=pv,
        )
        trainer = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-3))
        trainer.init_params(jax.random.PRNGKey(0))

        # warmup: freeze pad shapes over the FULL timed partition (so no
        # shape growth -> recompile lands inside the timed region), then
        # train one superstep chunk to compile the scan-K program and prime
        # packer scratch.
        from paddlebox_tpu import config as _config

        # bf16 boundary wire: halves the departing-slice D2H and new-key
        # H2D at the carried boundary (AUC in the output guards quality)
        _config.set_flag(
            "wire_dtype", os.environ.get("PBOX_WIRE_DTYPE", "bf16")
        )
        # PBOX_BOUNDARY_PIPELINE=0 benches the sequential boundary (the
        # r05-and-earlier shape: sync end_pass, then load, then finalize)
        # so captures can ablate the pipelined handoff against it
        _config.set_flag(
            "boundary_pipeline",
            int(os.environ.get("PBOX_BOUNDARY_PIPELINE", "1")),
        )
        pipelined = bool(_config.get_flag("boundary_pipeline"))

        # next pass's input, written up front: the pipelined boundary kicks
        # its load into the background BEFORE the timed region so read/
        # premerge/prefetch overlap warmup + training (the overlap the
        # supervisor's prefetch kick provides in the day loop)
        files2, _ = write_files(
            tmpdir, rng, reuse_pool=key_pool, prefix="p2", pv=pv
        )
        if pipelined:
            ds.set_filelist(files2)
            ds.preload_into_memory()

        if pv:
            # join phase: pv feeds don't wrap, so warm with one full epoch
            # (compile + resident upload) and time two more over the pass
            t0 = time.perf_counter()
            trainer.prepare_pass(ds)
            trainer.train_pass(ds)
            warmup_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(2):
                out = trainer.train_pass(ds, profile=profile)
            train_s = time.perf_counter() - t0
            # count REAL instances (ghost/pad slots carry ins_weight 0 and
            # train nothing) so the join-phase number is comparable to the
            # flat headline, not inflated by pv padding
            timed_samples = 2 * ds.memory_data_size()
        else:
            t0 = time.perf_counter()
            trainer.prepare_pass(ds, n_batches=TRAIN_BATCHES)
            warm = max(4, int(_config.get_flag("resident_scan_batches")))
            trainer.train_pass(ds, n_batches=warm)
            # reported so the steady-state headline can't be mistaken for
            # cold-start: this is the resident upload + XLA compile + first
            # chunk (the reference's first-pass warmup is the same shape)
            warmup_s = time.perf_counter() - t0

            t0 = time.perf_counter()
            out = trainer.train_pass(
                ds, n_batches=TRAIN_BATCHES, profile=profile
            )
            train_s = time.perf_counter() - t0
            timed_samples = TRAIN_BATCHES * BATCH

        # pass boundary, measured as the HANDOFF BLOCKING TIME: how long
        # end_pass + the next begin_pass actually stall the trainer. The
        # pipelined boundary dispatches EndPass to a worker and adopts the
        # background-staged load (premerge + host prefetch already done),
        # so the stall shrinks to the dispatch + the splice/assemble that
        # genuinely must run on the handoff. The sequential ablation
        # (PBOX_BOUNDARY_PIPELINE=0) measures the r05 shape: sync end_pass
        # + sync load + full finalize.
        pass1_keys = int(ds.stats.keys)
        preload_join_s = 0.0
        t0 = time.perf_counter()
        if pipelined:
            ds.end_pass_async(trainer.trained_table_device())
            writeback_s = time.perf_counter() - t0  # dispatch only
            t0 = time.perf_counter()
            # load time not in boundary_s (r05 didn't count it either);
            # reported separately — near zero when the overlap worked
            ds.wait_preload_done()
            preload_join_s = time.perf_counter() - t0
        else:
            ds.end_pass(trainer.trained_table_device())
            writeback_s = time.perf_counter() - t0
            ds.set_filelist(files2)
            ds.load_into_memory()
        t0 = time.perf_counter()
        ds.begin_pass(round_to=512)
        finalize2_s = time.perf_counter() - t0
        pass2_keys = int(ds.ws.n_keys)
        # leave the 2nd pass clean: flush carried rows, close it out
        ds.end_pass(None)
        table.drain_pending()

    sps = timed_samples / train_s
    extra = {}
    if profile:
        # per-stage attribution (TrainFilesWithProfiler parity) — table to
        # stderr so stdout stays one JSON line for the driver
        prof = out.get("profile", {})
        extra["profile"] = prof
        print("stage breakdown (s):", file=sys.stderr)
        for k, v in prof.items():
            print(f"  {k:18s} {v:8.3f}", file=sys.stderr)
        for k, v in (("load", load_s), ("finalize", finalize_s), ("train", train_s)):
            print(f"  {k + '_total':18s} {v:8.3f}", file=sys.stderr)
    result = {
        **extra,
        "metric": (
            "deepfm_join_phase_samples_per_sec_per_chip"
            if pv
            else "deepfm_e2e_train_samples_per_sec_per_chip"
        ),
        "value": round(sps, 1),
        "unit": "samples/s/chip",
        "vs_baseline": round(sps / BASELINE_PER_CHIP, 3),
        "train_pass_s": round(train_s, 3),
        "load_s": round(load_s, 3),
        "finalize_s": round(finalize_s, 3),
        "writeback_s": round(writeback_s, 3),
        "finalize2_s": round(finalize2_s, 3),
        "boundary_s": round(writeback_s + finalize2_s, 3),
        "preload_join_s": round(preload_join_s, 3),
        "boundary_pipeline": int(pipelined),
        # per-stage boundary attribution (utils/monitor gauges set by the
        # feed stage, finalize, and the end_pass worker)
        "boundary_stages": {
            k: round(float(STAT_GET(f"boundary.{k}")), 4)
            for k in (
                "premerge_s", "prefetch_pull_s", "dedup_s", "pull_s",
                "splice_s", "writeback_s", "writeback_hidden_s",
                "overlap_hidden_s",
            )
        },
        # writer-pool writeback internals (table.writeback.* gauges from
        # PassWorkingSet.writeback + the native io counters published at
        # end_pass): pool size, chunk pipeline wait vs hidden seconds,
        # and the spill stage writers' gather/fwrite split
        "writeback_stages": {
            k: round(float(STAT_GET(f"table.writeback.{k}")), 4)
            for k in (
                "threads", "chunks", "push_s", "wait_s", "hidden_s",
                "spill_gather_s", "spill_fwrite_s", "prepass_read_s",
                "stage_flushes", "stage_bytes",
            )
        },
        # distribution view of the same stages (obs histograms): the
        # gauges above are last-pass values, these are across-the-run
        # count/mean/p50/p99 for every STAT_OBSERVE'd series
        "distributions": {
            name: hist.summary((0.5, 0.99))
            for name, hist in sorted(_all_histograms().items())
        },
        "warmup_s": round(warmup_s, 3),
        # persistent-compile-cache counters: a cold run shows hits == 0,
        # the next identical run shows hits > 0 and a smaller warmup_s
        "compile_cache": compilecache.stats(),
        # bytes actually crossing the boundary wire this run (STAT
        # counters at the ops/wire_quant choke points) + the compiled ICI
        # a2a payload — the measured side of the wire_dtype claims
        "wire": {
            "wire_dtype": str(_config.get_flag("wire_dtype")),
            "fetch_rows": int(STAT_GET("wire.fetch_rows_total")),
            "fetch_bytes": int(STAT_GET("wire.fetch_bytes_total")),
            "fetch_fp32_bytes": int(STAT_GET("wire.fetch_fp32_bytes_total")),
            "send_rows": int(STAT_GET("wire.send_rows_total")),
            "send_bytes": int(STAT_GET("wire.send_bytes_total")),
            "send_fp32_bytes": int(STAT_GET("wire.send_fp32_bytes_total")),
            "ici_wire_dtype": str(_config.get_flag("ici_wire_dtype")),
            "a2a_payload_bytes": int(STAT_GET("wire.a2a_payload_bytes")),
            "a2a_fp32_bytes": int(STAT_GET("wire.a2a_fp32_bytes")),
            "a2a_dtype_bits": int(STAT_GET("wire.a2a_dtype_bits")),
            # adaptive ICI wire (hot rows bf16, cold tail int8): per-bucket
            # hot-slot bound the compiled collective used, plus the pass's
            # hotness census and how many hot keys overflowed into int8
            "a2a_hot_slots": int(STAT_GET("wire.a2a_hot_slots")),
            "ici_hot_keys": int(STAT_GET("wire.ici_hot_keys")),
            "ici_hot_overflow_keys": int(STAT_GET("wire.ici_hot_overflow_keys")),
            # host plane (PBTX v3 frame choke point + working-set
            # exchange rounds, ops/host_codec.py): actual bytes shipped
            # vs what the raw v2 framing would have shipped
            "host_wire_codec": bool(_config.get_flag("host_wire_codec")),
            "host_bytes_sent": int(STAT_GET("wire.host_bytes_sent")),
            "host_raw_bytes_sent": int(STAT_GET("wire.host_raw_bytes_sent")),
            "host_bytes_recv": int(STAT_GET("wire.host_bytes_recv")),
            "host_raw_bytes_recv": int(STAT_GET("wire.host_raw_bytes_recv")),
            "ws_req_bytes": int(STAT_GET("wire.ws_req_bytes")),
            "ws_req_raw_bytes": int(STAT_GET("wire.ws_req_raw_bytes")),
            "ws_rep_bytes": int(STAT_GET("wire.ws_rep_bytes")),
            "ws_rep_raw_bytes": int(STAT_GET("wire.ws_rep_raw_bytes")),
        },
        # which kernel plan routed pull/push this run, and how often it
        # chose pallas (ops/kernel_plan.py; regenerate with
        # tools/tune_kernels.py)
        "kernel_plan": {
            "source": _plan_source(),
            "selects": int(STAT_GET("kernel_plan.selects")),
            "selects_pallas": int(STAT_GET("kernel_plan.selects_pallas")),
        },
        # elastic membership (parallel/membership.py): ownership epoch,
        # fleet size and lifetime join commits — a single-process bench
        # leaves all three gauges at zero; the elastic soaks
        # (chaos_probe --kill-rank / --join-rank) move these
        "membership": {
            "epoch": int(STAT_GET("membership.epoch")),
            "live_ranks": int(STAT_GET("membership.live_ranks")),
            "joins_total": int(STAT_GET("membership.joins_total")),
        },
        # serving plane (serve/): miss ladder + device hot tier + the SLO
        # latency series — a pure-training bench leaves these at zero; the
        # serving soaks (tools/serve_soak.py [--device-tier]) move them
        "serve": {
            "key_misses": int(STAT_GET("serve.key_misses")),
            "device_tier_rows": int(STAT_GET("serve.device_tier_rows")),
            "device_tier_builds": int(STAT_GET("serve.device_tier_builds")),
            "device_tier_hits": int(STAT_GET("serve.device_tier_hits")),
            "device_tier_misses": int(STAT_GET("serve.device_tier_misses")),
            "device_tier_hit_rate": round(
                STAT_GET("serve.device_tier_hits")
                / max(
                    1.0,
                    STAT_GET("serve.device_tier_hits")
                    + STAT_GET("serve.device_tier_misses"),
                ),
                4,
            ),
            "lb_rerouted": int(STAT_GET("serve.lb_rerouted")),
            "request_ms": (
                _all_histograms()["serve.request_ms"].summary((0.5, 0.99))
                if "serve.request_ms" in _all_histograms()
                else None
            ),
        },
        # pass-prepare pad sweep (native pbx_block_stats counter sweep):
        # must stay a small fraction of train_pass_s at any pass size
        "prepare_s": round(getattr(trainer, "last_prepare_s", -1.0), 3),
        "pass2_keys": pass2_keys,
        "pass_keys": pass1_keys,
        "native_store": native_store,
        "device": device.as_dict(),
        "auc": round(out["auc"], 4),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
