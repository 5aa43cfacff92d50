"""Chaos probe: longer seeded fault-injection schedules through the
PassSupervisor, as a command-line soak.

tests/test_chaos.py pins one 3-pass schedule in tier-1; this probe runs
configurable multi-day schedules with probabilistic flakes layered over
deterministic crash windows, and reports the incident log plus an
equality check against a clean twin run. Exit code 0 iff the injected
run completes AND matches the clean run bitwise.

Usage:
  JAX_PLATFORMS=cpu python tools/chaos_probe.py \
      [--days N] [--passes N] [--rows N] [--seed N] \
      [--fs-flake-prob P] [--step-faults N] [--save-faults N] [--json]

``--corrupt-rate P`` switches to the data-poisoning soak: every data line
is corrupted with iid probability P (a seeded token flip that defeats both
parser tiers), the supervisor runs the dirty schedule under
``on_poisoned='degrade'``, and the run must (a) quarantine EXACTLY the
injected lines — ``data.quarantine.bad_lines_total`` delta == injected
count — and (b) finish bitwise-equal to a clean twin over the pre-cleaned
filelist (the same files with the corrupted lines removed):

  JAX_PLATFORMS=cpu python tools/chaos_probe.py --corrupt-rate 0.05 \
      [--days N] [--passes N] [--rows N] [--seed N] [--json]

``--distributed N`` switches to the multi-rank soak instead: an N-rank
in-process cluster (threads, real localhost TCP) runs ``--passes``
shuffled distributed passes — ins_id shuffle through TcpShuffleRouter,
working-set key exchange through DistributedWorkingSet, deterministic
train + writeback — under seeded ``transport.send`` /
``transport.recv_frame`` faults, and the run must be bitwise-equal
(row assignment, host tables, predictions) to a fault-free twin:

  JAX_PLATFORMS=cpu python tools/chaos_probe.py --distributed 3 \
      [--passes N] [--rows N] [--seed N] [--send-flake-prob P] [--json]

``--ici-wire`` is the frequency-adaptive wire A/B: four mesh-trainer days
over the SAME zipf-keyed day (4 virtual devices, embedx_dim=16) in fp32 /
bf16 / adaptive / adaptive-with-ablation-off, reporting the compiled
``wire.a2a_payload_bytes`` per mode plus AUC. Green iff the adaptive
payload is >=2x under fp32 and below uniform bf16, the adaptive day is
AUC-neutral vs fp32 (|delta| <= 0.02), hotness engaged, and the ablation
day matches fp32 bitwise:

  JAX_PLATFORMS=cpu python tools/chaos_probe.py --ici-wire \\
      [--passes N] [--rows N] [--seed N] [--json]

``--kill-rank R`` is the elastic-membership soak: an N-rank supervised
day (``--ranks``, default 4) loses rank R at the top of pass 1; the
survivors run the membership verdict round, adopt the dead rank's shard
ranges from its last checkpoint, revert the in-flight pass and finish
the day — and the final ownership-filtered digest plus per-pass global
AUC must be bitwise-equal to a FRESH (N-1)-rank run of the same day:

  JAX_PLATFORMS=cpu python tools/chaos_probe.py --kill-rank 1 \
      [--ranks N] [--passes N] [--rows N] [--seed N] [--json]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

S, B = 4, 16


def make_schema():
    from paddlebox_tpu.data import SlotInfo, SlotSchema

    return SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(S)],
        label_slot="label",
    )


def write_day_files(tmpdir, date, n_passes, rows, seed):
    rng = np.random.default_rng(seed)
    files = []
    for p in range(n_passes):
        path = os.path.join(tmpdir, f"{date}-{p}.txt")
        lo = 1 + 40 * p
        with open(path, "w") as f:
            for _ in range(rows):
                parts = [f"1 {float(rng.integers(0, 2))}"]
                for _s in range(S):
                    k = int(rng.integers(1, 3))
                    parts.append(
                        f"{k} "
                        + " ".join(str(v) for v in rng.integers(lo, lo + 160, k))
                    )
                f.write(" ".join(parts) + "\n")
        files.append(path)
    return files


def build_supervisor(ckpt_root, on_poisoned=None):
    import jax
    import optax

    from paddlebox_tpu.data import BoxPSDataset
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.table import (
        HostSparseTable,
        SparseOptimizerConfig,
        ValueLayout,
    )
    from paddlebox_tpu.train import (
        CheckpointManager,
        CTRTrainer,
        PassSupervisor,
        RetryPolicy,
        TrainStepConfig,
    )

    opt = SparseOptimizerConfig(
        embedx_threshold=0.0, show_clk_decay=0.97, shrink_threshold=0.0
    )
    layout = ValueLayout(embedx_dim=4)
    table = HostSparseTable(layout, opt, n_shards=2, seed=0)
    ds = BoxPSDataset(make_schema(), table, batch_size=B, shuffle_mode="none")
    model = DeepFM(
        num_slots=S, feat_width=layout.pull_width, embedx_dim=4, hidden=(8,)
    )
    cfg = TrainStepConfig(
        num_slots=S, batch_size=B, layout=layout, sparse_opt=opt,
        auc_buckets=100,
    )
    tr = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-2))
    tr.init_params(jax.random.PRNGKey(0))
    sup = PassSupervisor(
        ds, tr, checkpoint=CheckpointManager(ckpt_root),
        retry=RetryPolicy(backoff_s=0.0, sleep=lambda s: None),
        round_to=8, on_poisoned=on_poisoned,
    )
    return table, tr, sup


def final_state(table, tr):
    import jax

    k = np.sort(table.keys())
    v = table.pull_or_create(k)
    dense = [
        np.asarray(x) for x in jax.tree.flatten((tr.params, tr.opt_state))[0]
    ]
    return k, v, dense


def run_schedule(tmpdir, tag, days, rules, on_poisoned=None):
    from paddlebox_tpu.utils.faultinject import inject

    table, tr, sup = build_supervisor(
        os.path.join(tmpdir, f"ckpt-{tag}"), on_poisoned=on_poisoned
    )
    t0 = time.perf_counter()
    with inject(*rules) as plan:
        for date, files in days:
            sup.run_day(date, [[f] for f in files])
    wall = time.perf_counter() - t0
    return table, tr, sup, plan, wall


def corrupt_day_files(files, out_dirty, out_clean, rate, seed):
    """Write a dirty twin (iid token flips at ``rate``) and a pre-cleaned
    twin (the corrupted lines REMOVED) of each file. Every flip replaces a
    random token with a non-numeric one, so both parser tiers reject the
    line. Returns (dirty_files, clean_files, n_corrupted)."""
    rng = np.random.default_rng(seed + 77)
    dirty_files, clean_files, n_bad = [], [], 0
    for path in files:
        lines = open(path).read().splitlines()
        dirty, clean = [], []
        for ln in lines:
            if rng.random() < rate:
                toks = ln.split(" ")
                toks[int(rng.integers(0, len(toks)))] = (
                    "!x%04x" % int(rng.integers(0, 1 << 16))
                )
                dirty.append(" ".join(toks))
                n_bad += 1
            else:
                dirty.append(ln)
                clean.append(ln)
        base = os.path.basename(path)
        dp = os.path.join(out_dirty, base)
        cp = os.path.join(out_clean, base)
        # scratch split files, consumed by this same process
        # pbox-lint: disable=IO004
        with open(dp, "w") as f:
            f.write("\n".join(dirty) + "\n")
        # pbox-lint: disable=IO004
        with open(cp, "w") as f:
            f.write("\n".join(clean) + "\n" if clean else "")
        dirty_files.append(dp)
        clean_files.append(cp)
    return dirty_files, clean_files, n_bad


def run_corrupt(args):
    """Data-poisoning soak: dirty schedule under on_poisoned='degrade'
    vs a clean twin over the pre-cleaned filelist. Exit 0 iff the
    quarantine counters account for every injected line AND the final
    state is bitwise-equal."""
    from paddlebox_tpu import config
    from paddlebox_tpu.utils.monitor import STAT_GET

    config.set_flag("fs_open_backoff_s", 0.0)
    with tempfile.TemporaryDirectory() as tmpdir:
        dirty_days, clean_days, injected = [], [], 0
        for d in range(args.days):
            date = f"202601{d + 1:02d}"
            src = os.path.join(tmpdir, f"src-{d}")
            dd = os.path.join(tmpdir, f"dirty-{d}")
            cd = os.path.join(tmpdir, f"cleaned-{d}")
            for p in (src, dd, cd):
                os.makedirs(p)
            files = write_day_files(
                src, date, args.passes, args.rows, args.seed + d
            )
            df, cf, nb = corrupt_day_files(
                files, dd, cd, args.corrupt_rate, args.seed + d
            )
            dirty_days.append((date, df))
            clean_days.append((date, cf))
            injected += nb

        table_c, tr_c, sup_c, _, wall_c = run_schedule(
            tmpdir, "clean", clean_days, ()
        )
        before = STAT_GET("data.quarantine.bad_lines_total")
        table_i, tr_i, sup_i, _, wall_i = run_schedule(
            tmpdir, "dirty", dirty_days, (), on_poisoned="degrade"
        )
        quarantined = int(STAT_GET("data.quarantine.bad_lines_total") - before)

        k_c, v_c, d_c = final_state(table_c, tr_c)
        k_i, v_i, d_i = final_state(table_i, tr_i)
        equal = (
            np.array_equal(k_i, k_c)
            and np.array_equal(v_i, v_c)
            and len(d_i) == len(d_c)
            and all(np.array_equal(a, b) for a, b in zip(d_i, d_c))
        )
        counts_match = quarantined == injected
        report = {
            "mode": "corrupt-soak",
            "corrupt_rate": args.corrupt_rate,
            "days": args.days,
            "passes_per_day": args.passes,
            "injected_bad_lines": injected,
            "quarantined_bad_lines": quarantined,
            "counts_match": counts_match,
            "degrade_incidents": sum(
                1 for i in sup_i.incidents if i.kind == "data_poisoned"
            ),
            "incidents": [i.as_dict() for i in sup_i.incidents],
            "bitwise_equal_to_clean": bool(equal),
            "wall_clean_s": round(wall_c, 2),
            "wall_injected_s": round(wall_i, 2),
        }
        print(json.dumps(report, indent=None if args.json else 2))
        return 0 if (equal and counts_match) else 1


def run_serve(args):
    """Serving-chain corruption smoke (``--serve``): a follower tailing a
    live publish stream must SKIP a corrupted delta with an alarm — same
    version served, bitwise-same scores — and catch up once the publisher
    repairs it. Exercises the deep per-file CRC gate: the corrupted byte
    lives inside a shard npz, so the watermark's manifest-CRC pin still
    matches and only verify_snapshot can catch it.

      JAX_PLATFORMS=cpu python tools/chaos_probe.py --serve [--json]
    """
    import serve_soak

    from paddlebox_tpu.data.parser import parse_line
    from paddlebox_tpu.serve import table_source, version_source
    from paddlebox_tpu.utils.monitor import STAT_GET

    with tempfile.TemporaryDirectory() as tmpdir:
        root = os.path.join(tmpdir, "ckpt")
        table, ds, cfg, trainer, mgr = serve_soak.make_stack(root)
        fol, scorer = serve_soak.make_follower(root, cfg)
        rng = np.random.default_rng(args.seed)
        date = serve_soak.DATE

        p0 = os.path.join(tmpdir, "pass-0.txt")
        lines = serve_soak.write_pass_file(rng, p0, args.rows, 1)
        probe = [parse_line(ln, serve_soak.SCHEMA) for ln in lines[:16]]

        def one_pass(lo, path=None):
            if path is None:
                path = os.path.join(tmpdir, f"pass-{lo}.txt")
                serve_soak.write_pass_file(rng, path, args.rows, lo)
            ds.set_filelist([path])
            ds.load_into_memory()
            ds.begin_pass(round_to=8)
            trainer.train_pass(ds)
            ds.end_pass(trainer.trained_table_device())
            table.drain_pending()

        def follower_scores(v):
            return scorer.score_records(
                probe, serve_soak.SCHEMA,
                version_source(serve_soak.LAYOUT, v), v.params, v.opt_state,
            )

        one_pass(1, path=p0)
        mgr.save_base(date, table, trainer)
        one_pass(120)
        mgr.save_delta(date, table, trainer)
        assert fol.poll_once()
        v1 = fol.version()
        good = follower_scores(v1)

        # publish delta-0002, then flip one byte inside a shard npz
        one_pass(260)
        mgr.save_delta(date, table, trainer)
        delta_dir = os.path.join(root, date, "delta-0002")
        victim = next(
            os.path.join(delta_dir, n)
            for n in sorted(os.listdir(delta_dir)) if n.endswith(".npz")
        )
        original = open(victim, "rb").read()
        # deliberate corruption of a published delta (raw is the point)
        # pbox-lint: disable=IO004
        with open(victim, "wb") as f:  # same size, one byte flipped
            f.write(original[:20] + bytes([original[20] ^ 0xFF]) + original[21:])

        skipped_before = STAT_GET("serve.corrupt_skipped")
        applied_corrupt = fol.poll_once()
        v_after = fol.version()
        scores_after = follower_scores(v_after)
        skipped = int(STAT_GET("serve.corrupt_skipped") - skipped_before)
        held = (
            not applied_corrupt
            and v_after is v1
            and np.array_equal(scores_after, good)
            and skipped >= 1
        )

        # deliberate in-place repair (raw is the point)
        # pbox-lint: disable=IO004
        with open(victim, "wb") as f:  # publisher repairs the delta
            f.write(original)
        caught_up = fol.poll_once()
        v2 = fol.version()
        ref = scorer.score_records(
            probe, serve_soak.SCHEMA,
            table_source(serve_soak.LAYOUT, table),
            trainer.params, trainer.opt_state,
        )
        recovered = (
            caught_up
            and v2.delta_idx == 2
            and np.array_equal(follower_scores(v2), ref)
        )

    ok = held and recovered
    report = {
        "mode": "serve",
        "corrupt_delta_skipped": skipped,
        "served_idx_during_corruption": v_after.delta_idx,
        "scores_held_bitwise": bool(held),
        "caught_up_after_repair": bool(caught_up),
        "final_served_idx": v2.delta_idx,
        "parity_after_repair_bitwise": bool(recovered),
        "ok": bool(ok),
    }
    print(json.dumps(report, indent=None if args.json else 2))
    return 0 if ok else 1


def run_stream(args):
    """Streaming-plane fault sweep (``--stream``): seeded faults on ALL
    THREE streaming sites — ``stream.tail_read`` (read error holds the
    cursor, zero loss), ``stream.cut_publish`` (kill in the durable-intent
    window, restart replays the spool exactly once), and ``ckpt.compact``
    (kill mid-fold leaves the old chain servable; the healed retry folds
    bitwise). Every site must FIRE, and the final table must be
    bitwise-identical to an uninterrupted clean twin over the same
    records.

      JAX_PLATFORMS=cpu python tools/chaos_probe.py --stream [--json]
    """
    import serve_soak

    from paddlebox_tpu.table import HostSparseTable
    from paddlebox_tpu.train.stream import StreamSupervisor
    from paddlebox_tpu.train.supervisor import HealthGates, PassSupervisor
    from paddlebox_tpu.utils.faultinject import InjectedFault, fail_nth, inject
    from paddlebox_tpu.utils.monitor import STAT_GET

    date = serve_soak.DATE
    chunks = 4

    def digest(table):
        k = np.sort(table.keys())
        v = table.pull_or_create(k)
        h = hashlib.sha256()
        h.update(np.ascontiguousarray(k).tobytes())
        h.update(np.ascontiguousarray(v).tobytes())
        return h.hexdigest()

    def build(root, stream_dir, resume=False):
        table, ds, cfg, trainer, mgr = serve_soak.make_stack(root)
        sup = PassSupervisor(
            ds, trainer, checkpoint=mgr,
            gates=HealthGates(auc_min_history=99),
        )
        if resume:
            mgr.resume(table, trainer)  # before recovery replays the spool
        st = StreamSupervisor(
            sup, stream_dir, date, pattern="*.txt", compact_every=0,
        )
        return table, trainer, mgr, st

    def append(stream_dir, rng, lo):
        lines = []
        for _ in range(args.rows):
            keys = rng.integers(lo, lo + 200, 4)
            lines.append(
                f"1 {float(keys[0] % 2)} " + " ".join(f"1 {k}" for k in keys)
            )
        # the upstream appender the tailer follows
        # pbox-lint: disable=IO004
        with open(os.path.join(stream_dir, "events.txt"), "a") as f:
            f.write("\n".join(lines) + "\n")

    fired = {}
    with tempfile.TemporaryDirectory() as tmpdir:
        clean_root = os.path.join(tmpdir, "clean-ckpt")
        clean_stream = os.path.join(tmpdir, "clean-stream")
        root = os.path.join(tmpdir, "ckpt")
        stream_dir = os.path.join(tmpdir, "stream")
        os.makedirs(clean_stream)
        os.makedirs(stream_dir)

        rng = np.random.default_rng(args.seed)
        clean_table, _, _, clean_st = build(clean_root, clean_stream)
        for c in range(chunks):
            append(clean_stream, rng, 1 + c * 120)
            clean_st.step()
        want = digest(clean_table)

        rng = np.random.default_rng(args.seed)  # same records, faulted leg
        table, trainer, mgr, st = build(root, stream_dir)

        # site 1: a transient read error holds the cursor — the healed
        # retry consumes the SAME bytes (latency, never records)
        append(stream_dir, rng, 1)
        with inject(fail_nth("stream.tail_read", 1)) as plan:
            no_cut = st.step()  # read swallowed, nothing consumed
            fired["stream.tail_read"] = plan.failures("stream.tail_read")
        tail_held = no_cut is None
        st.step()  # healed: the chunk cuts now

        # site 2: kill in the durable-intent window; the restart stack
        # must replay the spool exactly once
        append(stream_dir, rng, 121)
        replays0 = STAT_GET("stream.replays")
        with inject(fail_nth("stream.cut_publish", 1)) as plan:
            try:
                st.step()
                cut_killed = False
            except InjectedFault:
                cut_killed = True
            fired["stream.cut_publish"] = plan.failures("stream.cut_publish")
        table, trainer, mgr, st = build(root, stream_dir, resume=True)
        replayed = int(STAT_GET("stream.replays") - replays0)

        for c in range(2, chunks):
            append(stream_dir, rng, 1 + c * 120)
            st.step()

        # site 3: kill mid-fold — the cursor never names a torn fold, so
        # the old chain resumes bitwise; the healed retry folds bitwise
        with inject(fail_nth("ckpt.compact", 2)) as plan:
            try:
                mgr.compact(
                    date,
                    HostSparseTable(
                        serve_soak.LAYOUT, serve_soak.OPT, n_shards=4, seed=0
                    ),
                )
                compact_killed = False
            except InjectedFault:
                compact_killed = True
            fired["ckpt.compact"] = plan.failures("ckpt.compact")
        from paddlebox_tpu.train import CheckpointManager

        t_held = HostSparseTable(
            serve_soak.LAYOUT, serve_soak.OPT, n_shards=4, seed=0
        )
        CheckpointManager(root).resume(t_held)
        held_bitwise = digest(t_held) == digest(table)
        folded = mgr.compact(
            date,
            HostSparseTable(
                serve_soak.LAYOUT, serve_soak.OPT, n_shards=4, seed=0
            ),
        ) is not None
        t_comp = HostSparseTable(
            serve_soak.LAYOUT, serve_soak.OPT, n_shards=4, seed=0
        )
        state = CheckpointManager(root).resume(t_comp)

        ok = (
            all(n >= 1 for n in fired.values())
            and tail_held
            and cut_killed
            and replayed == 1
            and compact_killed
            and held_bitwise
            and folded
            and int(state.get("compact") or 0) == chunks - 1
            and digest(table) == want
            and digest(t_comp) == want
        )
        report = {
            "mode": "stream",
            "sites_fired": fired,
            "tail_read_held_cursor": bool(tail_held),
            "cut_publish_killed": bool(cut_killed),
            "spool_replays": replayed,
            "compact_killed": bool(compact_killed),
            "old_chain_held_bitwise": bool(held_bitwise),
            "healed_fold_published": bool(folded),
            "compact_covers": int(state.get("compact") or 0),
            "final_bitwise_vs_clean": bool(digest(table) == want),
            "compacted_resume_bitwise": bool(digest(t_comp) == want),
            "ok": bool(ok),
        }
    print(json.dumps(report, indent=None if args.json else 2))
    return 0 if ok else 1


def run_serve_shard(args):
    """Mesh-sharded tier crash probe (``--serve-shard``): a follower with
    the device scoring tier ON takes an injected crash mid-tier-build
    (fault site ``serve.tier_build``) while applying a fresh delta. The
    FLT008 contract under test: the commit aborts whole — the previously
    served version (object identity, its tier, its scores) is untouched
    and no partial tier is ever visible — and the healed retry lands the
    same delta bitwise with the tier rebuilt.

      JAX_PLATFORMS=cpu python tools/chaos_probe.py --serve-shard [--json]
    """
    import serve_soak

    from paddlebox_tpu import config
    from paddlebox_tpu.data.parser import parse_line
    from paddlebox_tpu.serve import table_source, version_source
    from paddlebox_tpu.utils.faultinject import InjectedFault, fail_once, inject

    prev = {
        n: config.get_flag(n)
        for n in ("device_scoring_tier", "device_tier_hot_show")
    }
    config.set_flag("device_scoring_tier", "on")
    config.set_flag("device_tier_hot_show", 0.0)  # every published row is hot
    try:
        with tempfile.TemporaryDirectory() as tmpdir:
            root = os.path.join(tmpdir, "ckpt")
            table, ds, cfg, trainer, mgr = serve_soak.make_stack(root)
            fol, scorer = serve_soak.make_follower(root, cfg)
            rng = np.random.default_rng(args.seed)
            date = serve_soak.DATE

            p0 = os.path.join(tmpdir, "pass-0.txt")
            lines = serve_soak.write_pass_file(rng, p0, args.rows, 1)
            probe = [parse_line(ln, serve_soak.SCHEMA) for ln in lines[:16]]

            def one_pass(lo, path=None):
                if path is None:
                    path = os.path.join(tmpdir, f"pass-{lo}.txt")
                    serve_soak.write_pass_file(rng, path, args.rows, lo)
                ds.set_filelist([path])
                ds.load_into_memory()
                ds.begin_pass(round_to=8)
                trainer.train_pass(ds)
                ds.end_pass(trainer.trained_table_device())
                table.drain_pending()

            def follower_scores(v):
                return scorer.score_records(
                    probe, serve_soak.SCHEMA,
                    version_source(serve_soak.LAYOUT, v), v.params, v.opt_state,
                )

            one_pass(1, path=p0)
            mgr.save_base(date, table, trainer)
            assert fol.poll_once()
            v0 = fol.version()
            tier0 = v0.device_tier
            tier_on = tier0 is not None and tier0.n_rows > 0
            good = follower_scores(v0)

            one_pass(120)
            mgr.save_delta(date, table, trainer)
            with inject(fail_once("serve.tier_build")) as plan:
                crashed = False
                try:
                    fol.poll_once()
                except InjectedFault:
                    crashed = True
                v_mid = fol.version()
                held = (
                    crashed
                    and v_mid is v0
                    and v_mid.device_tier is tier0
                    and np.array_equal(follower_scores(v_mid), good)
                )
                # healed retry inside the same plan (fault budget spent):
                # staging re-apply is idempotent, the tier rebuilds
                caught_up = fol.poll_once()
            fired = plan.failures("serve.tier_build")
            v1 = fol.version()
            ref = scorer.score_records(
                probe, serve_soak.SCHEMA,
                table_source(serve_soak.LAYOUT, table),
                trainer.params, trainer.opt_state,
            )
            recovered = (
                caught_up
                and v1.delta_idx == 1
                and v1.device_tier is not None
                and v1.device_tier.n_rows > 0
                and np.array_equal(follower_scores(v1), ref)
            )
    finally:
        for n, v in prev.items():
            config.set_flag(n, v)

    ok = tier_on and held and recovered and fired == 1
    report = {
        "mode": "serve-shard",
        "tier_on_base": bool(tier_on),
        "tier_build_faults_fired": int(fired),
        "old_version_held_bitwise": bool(held),
        "healed_retry_caught_up": bool(caught_up),
        "final_served_idx": v1.delta_idx,
        "final_tier_rows": 0 if v1.device_tier is None else v1.device_tier.n_rows,
        "parity_after_heal_bitwise": bool(recovered),
        "ok": bool(ok),
    }
    print(json.dumps(report, indent=None if args.json else 2))
    return 0 if ok else 1


def run_serve_fleet(args):
    """Fleet churn soak under injected serve faults (``--serve-fleet``):
    the full networked day — N followers over one shared stage, follower
    kill + drain/admit + rejoin during concurrent publishes — run with
    faults firing at all three serve sites (a lost request, a torn stage
    fetch, a dropped drain command). The acceptance gate is unchanged:
    zero client-visible failures, bitwise parity live and offline, drain
    honored, single disk fetch per publish — the client's retry/hedge
    budget and the stager's idempotent retry must absorb every fault.

      JAX_PLATFORMS=cpu python tools/chaos_probe.py --serve-fleet [--json]
    """
    import serve_soak

    from paddlebox_tpu.utils.faultinject import fail_nth, inject

    with tempfile.TemporaryDirectory() as tmpdir:
        with inject(
            fail_nth("serve.request_recv", 5),
            fail_nth("serve.request_recv", 40),
            fail_nth("serve.fleet_stage", 2),
            fail_nth("serve.drain", 1),
        ) as plan:
            report = serve_soak.run_fleet_soak(
                tmpdir,
                n_followers=max(2, args.ranks - 1),
                # the churn script (kill@2, drain@3, admit+rejoin@4) needs
                # at least one clean publish after the rejoin
                passes=max(args.passes, 6),
                rows=args.rows,
                qps=30.0,
                probe_n=32,
            )
    faults = {
        "serve.request_recv": plan.failures("serve.request_recv"),
        "serve.fleet_stage": plan.failures("serve.fleet_stage"),
        "serve.drain": plan.failures("serve.drain"),
    }
    ok = report["ok"] and all(n > 0 for n in faults.values())
    report = {
        "mode": "serve-fleet",
        "faults_fired": faults,
        "soak": report,
        "ok": bool(ok),
    }
    print(json.dumps(report, indent=None if args.json else 2))
    return 0 if ok else 1


def run_proto_check(args):
    """Membership-protocol model check (``--proto-check``): explore the
    bounded elastic state machine (deaths, joins and no-votes injectable
    at every step) to a fixpoint, require zero invariant violations, and
    require every deliberately broken protocol variant to be caught on
    exactly the invariant it breaks — the checker demonstrates it can
    fail before its clean pass counts.

      python tools/chaos_probe.py --proto-check [--json]
    """
    import proto_check

    clean = proto_check.Checker(
        ranks=min(args.ranks, 3), deaths=1, joins=1, nos=1, max_epochs=2
    ).run()
    variants = {}
    ok = clean.complete and clean.ok
    for name in sorted(proto_check.BROKEN):
        inv, _desc, bounds = proto_check.BROKEN[name]
        res = proto_check.Checker(broken=name, **bounds).run()
        caught = bool(res.violations) and all(
            v["invariant"] == inv for v in res.violations
        )
        variants[name] = {
            "invariant": inv,
            "caught": caught,
            "states": res.states,
        }
        ok = ok and caught
    report = {
        "mode": "proto-check",
        "clean": clean.as_dict(),
        "broken": variants,
        "ok": bool(ok),
    }
    print(json.dumps(report, indent=None if args.json else 2))
    return 0 if ok else 1


def _ici_zipf_day(tmpdir, n_passes, rows, seed):
    """A zipf-keyed day: a small hot set dominates the traffic, the long
    tail shows up once or twice — the distribution the adaptive wire is
    built for. Labels are learnable so AUC is meaningful."""
    rng = np.random.default_rng(seed)
    files = []
    n_keys = 300
    for p in range(n_passes):
        path = os.path.join(tmpdir, f"zipf-{p}.txt")
        with open(path, "w") as f:
            for _ in range(rows):
                keys = np.minimum(rng.zipf(1.3, S), n_keys)
                keys = keys + np.arange(S) * n_keys  # per-slot key spaces
                label = 1.0 if (keys % 7 == 0).any() else 0.0
                parts = [f"1 {label}"] + [f"1 {k}" for k in keys]
                f.write(" ".join(parts) + "\n")
        files.append(path)
    return files


def run_ici_wire(args):
    """A/B the frequency-adaptive ICI wire against the uniform modes.

    Four mesh-trainer days over the SAME zipf day (4 virtual devices,
    embedx_dim=16): fp32, bf16, adaptive, and adaptive with the
    ici_wire_adaptive ablation off. Gates: the compiled a2a payload must
    shrink >=2x vs fp32 and below uniform bf16, the adaptive day must stay
    AUC-neutral vs fp32 (|delta| <= 0.02), hotness must actually engage
    (hot keys > 0 once shows accumulate), and the ablation day must finish
    bitwise-identical to fp32.
    """
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    import optax

    from paddlebox_tpu import config
    from paddlebox_tpu.data import BoxPSDataset
    from paddlebox_tpu.models import DeepFM
    from paddlebox_tpu.parallel import make_mesh
    from paddlebox_tpu.table import (
        HostSparseTable,
        SparseOptimizerConfig,
        ValueLayout,
    )
    from paddlebox_tpu.train import CTRTrainer, TrainStepConfig
    from paddlebox_tpu.utils.monitor import STAT_GET

    n_dev = 4
    config.set_flag("ici_hot_frac", 0.125)
    config.set_flag("ici_hot_show", 3.0)

    def day(mode, adaptive_on, files):
        config.set_flag("ici_wire_dtype", mode)
        config.set_flag("ici_wire_adaptive", adaptive_on)
        layout = ValueLayout(embedx_dim=16)
        opt = SparseOptimizerConfig(
            embedx_threshold=0.0, show_clk_decay=0.98, shrink_threshold=0.0
        )
        table = HostSparseTable(layout, opt, n_shards=n_dev, seed=0)
        plan = make_mesh(n_dev)
        ds = BoxPSDataset(
            make_schema(), table, batch_size=B, n_mesh_shards=n_dev,
            shuffle_mode="none",
        )
        model = DeepFM(
            num_slots=S, feat_width=layout.pull_width,
            embedx_dim=layout.embedx_dim, hidden=(16,),
        )
        cfg = TrainStepConfig(
            num_slots=S, batch_size=B // n_dev, layout=layout,
            sparse_opt=opt, auc_buckets=100, axis_name=plan.axis,
        )
        tr = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-2), plan=plan)
        tr.init_params(jax.random.PRNGKey(0))
        overflow0 = int(STAT_GET("wire.ici_hot_overflow_keys"))
        auc = float("nan")
        for f in files:
            ds.set_filelist([f])
            ds.load_into_memory()
            ds.begin_pass(round_to=8)
            out = tr.train_pass(ds)
            auc = float(out["auc"])
            ds.end_pass(tr.trained_table())
        keys = np.sort(table.keys())
        from paddlebox_tpu.ops import wire_quant

        # wire.ici_hot_keys is a gauge (STAT_SET at ws finalize) — a leg
        # that never engages the adaptive wire would read the previous
        # leg's stale value
        engaged = wire_quant.ici_adaptive_engaged()
        return {
            "auc": auc,
            "payload_bytes": int(STAT_GET("wire.a2a_payload_bytes")),
            "fp32_bytes": int(STAT_GET("wire.a2a_fp32_bytes")),
            "dtype_bits": int(STAT_GET("wire.a2a_dtype_bits")),
            "hot_keys": int(STAT_GET("wire.ici_hot_keys")) if engaged else 0,
            "hot_overflow": int(STAT_GET("wire.ici_hot_overflow_keys"))
            - overflow0,
            "table": (keys, table.pull_or_create(keys)),
        }

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmpdir:
        files = _ici_zipf_day(tmpdir, args.passes, args.rows, args.seed)
        legs = {
            "fp32": day("fp32", True, files),
            "bf16": day("bf16", True, files),
            "adaptive": day("adaptive", True, files),
            "ablation": day("adaptive", False, files),
        }
    wall = time.perf_counter() - t0

    kf, vf = legs["fp32"].pop("table")
    ko, vo = legs["ablation"].pop("table")
    legs["bf16"].pop("table")
    legs["adaptive"].pop("table")
    ablation_bitwise = bool(
        np.array_equal(kf, ko) and np.array_equal(vf, vo)
    )
    pay = {m: legs[m]["payload_bytes"] for m in legs}
    ratio_fp32 = _ratio(legs["adaptive"]["fp32_bytes"], pay["adaptive"])
    auc_delta = abs(legs["adaptive"]["auc"] - legs["fp32"]["auc"])
    ok = (
        ratio_fp32 >= 2.0
        and pay["adaptive"] < pay["bf16"]
        and auc_delta <= 0.02
        and legs["adaptive"]["hot_keys"] > 0
        and ablation_bitwise
        and legs["ablation"]["payload_bytes"] == pay["fp32"]
    )
    report = {
        "probe": "ici_wire",
        "passes": args.passes,
        "rows": args.rows,
        "seed": args.seed,
        "devices": n_dev,
        "legs": {
            m: {k: v for k, v in r.items() if k != "table"}
            for m, r in legs.items()
        },
        "payload_ratio_fp32_over_adaptive": round(ratio_fp32, 3),
        "auc_delta_adaptive_vs_fp32": round(auc_delta, 5),
        "adaptive_below_bf16": bool(pay["adaptive"] < pay["bf16"]),
        "ablation_bitwise_fp32": ablation_bitwise,
        "wall_s": round(wall, 2),
        "ok": bool(ok),
    }
    print(json.dumps(report, indent=None if args.json else 2))
    return 0 if ok else 1


def _dist_free_ports(n):
    import socket

    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _dist_rank_records(rank, rows, seed, schema, pass_idx):
    from paddlebox_tpu.data.record_store import ColumnarRecords
    from paddlebox_tpu.data.slot_record import SlotRecord

    rng = np.random.default_rng(seed * 1009 + rank * 31 + pass_idx)
    recs = []
    for i in range(rows + 4 * rank):  # unequal loads across ranks
        keys, offs = [], [0]
        for _s in range(S):
            nk = int(rng.integers(1, 4))
            keys.extend(int(k) for k in rng.integers(1, 800, nk))
            offs.append(offs[-1] + nk)
        recs.append(
            SlotRecord(
                u64_values=np.array(keys, np.uint64),
                u64_offsets=np.array(offs, np.uint32),
                f_values=np.array([float(rng.integers(0, 2))], np.float32),
                f_offsets=np.array([0, 1], np.uint32),
                ins_id=f"p{pass_idx}-r{rank}-{i:05d}",
            )
        )
    return ColumnarRecords.from_records(recs, schema)


def _dist_soak_once(n_ranks, passes, rows, seed, rules, trace_dir=None):
    """One N-rank in-process soak under the given fault rules. Returns the
    per-rank observable digest the equality check compares. With
    ``trace_dir`` each rank records into its OWN Profiler (pid=rank) and
    exports ``trace-<rank>.json`` there — the merge-traces input."""
    import threading

    from paddlebox_tpu.data import SlotInfo, SlotSchema
    from paddlebox_tpu.data.dataset import shuffle_route_store
    from paddlebox_tpu.data.record_store import ColumnarRecords
    from paddlebox_tpu.obs.trace_context import trace_span
    from paddlebox_tpu.parallel.transport import TcpShuffleRouter, TcpTransport
    from paddlebox_tpu.table import (
        HostSparseTable,
        SparseOptimizerConfig,
        ValueLayout,
    )
    from paddlebox_tpu.table.dist_ws import DistributedWorkingSet
    from paddlebox_tpu.utils.faultinject import inject
    from paddlebox_tpu.utils.trace import Profiler

    schema = SlotSchema(
        [SlotInfo("label", type="float", dense=True, dim=1)]
        + [SlotInfo(f"s{i}") for i in range(S)],
        label_slot="label",
        parse_ins_id=True,
    )
    profilers = None
    if trace_dir is not None:
        profilers = []
        for r in range(n_ranks):
            pr = Profiler()
            pr.enable()
            pr.set_process(r)
            profilers.append(pr)
    eps = [f"127.0.0.1:{p}" for p in _dist_free_ports(n_ranks)]
    tps = [
        TcpTransport(
            r, eps, timeout=60.0,
            profiler=profilers[r] if profilers else None,
        )
        for r in range(n_ranks)
    ]
    routers = [TcpShuffleRouter(t) for t in tps]
    layout = ValueLayout(embedx_dim=4)
    tables = [
        HostSparseTable(
            layout, SparseOptimizerConfig(embedx_threshold=0.0),
            n_shards=2, seed=0,
        )
        for _ in range(n_ranks)
    ]
    results = [None] * n_ranks
    errors = []

    def worker(rank):
        t = tps[rank]
        digest = []
        for p in range(passes):
            # the span context rides outbound PBTX frames (when
            # transport_trace_frames is on), so every rank's deliver
            # instants share this rank's trace_id — the merge evidence
            with trace_span(f"pass-{p}"):
                store = _dist_rank_records(rank, rows, seed, schema, p)
                dest = shuffle_route_store(store, n_ranks, "ins_id", seed=seed)
                routers[rank].exchange(
                    rank,
                    [store.select(np.nonzero(dest == d)[0])
                     for d in range(n_ranks)],
                )
                got = [c for c in routers[rank].collect(rank) if len(c)]
                mine = ColumnarRecords.concat(got)
                ws = DistributedWorkingSet(t, n_ranks, pass_id=p)
                ws.add_keys(mine.u64_values)
                dev = ws.finalize(tables[rank], round_to=8)
                dev = dev * 1.01 + 0.25  # deterministic "training"
                ws.writeback(dev)
                rows_of = ws.lookup(mine.u64_values)
                digest.append(
                    dict(
                        n_records=len(mine),
                        capacity=ws.capacity,
                        rows=rows_of,
                        sorted_keys=ws.sorted_keys,
                    )
                )
                t.barrier(f"probe-pass-{p}")
        keys = np.sort(tables[rank].keys())
        return dict(
            digest=digest,
            host_keys=keys,
            host_vals=tables[rank].pull_or_create(keys),
        )

    def wrap(r):
        try:
            results[r] = worker(r)
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errors.append((r, e))

    t0 = time.perf_counter()
    try:
        with inject(*rules) as plan:
            threads = [
                threading.Thread(target=wrap, args=(r,))
                for r in range(n_ranks)
            ]
            for th in threads:
                th.start()
            for th in threads:
                th.join(300)
    finally:
        for t in tps:
            t.close()
    if errors:
        raise errors[0][1]
    if profilers is not None:
        for r, pr in enumerate(profilers):
            pr.export_chrome_trace(os.path.join(trace_dir, f"trace-{r}.json"))
    return results, plan, time.perf_counter() - t0


_WIRE_COUNTERS = (
    "wire.host_bytes_sent",
    "wire.host_raw_bytes_sent",
    "wire.host_bytes_recv",
    "wire.host_raw_bytes_recv",
    "wire.ws_req_bytes",
    "wire.ws_req_raw_bytes",
    "wire.ws_rep_bytes",
    "wire.ws_rep_raw_bytes",
)


def _wire_snapshot():
    from paddlebox_tpu.utils.monitor import STAT_GET

    return {k: int(STAT_GET(k)) for k in _WIRE_COUNTERS}


def _wire_delta(before, after):
    return {k: after[k] - before[k] for k in _WIRE_COUNTERS}


def _ratio(num, den):
    return round(num / den, 2) if den else None


def _digests_equal(a, b, n):
    equal = True
    for r in range(n):
        c, f = a[r], b[r]
        equal &= np.array_equal(c["host_keys"], f["host_keys"])
        equal &= np.array_equal(c["host_vals"], f["host_vals"])
        for dc, df in zip(c["digest"], f["digest"]):
            equal &= dc["n_records"] == df["n_records"]
            equal &= dc["capacity"] == df["capacity"]
            equal &= np.array_equal(dc["rows"], df["rows"])
            equal &= np.array_equal(dc["sorted_keys"], df["sorted_keys"])
    return bool(equal)


def _flight_recorder_smoke(inc_dir):
    """Provoke a REAL mid-collective peer death and check the flight
    recorder left an incident bundle: rank 1 stops beating, rank 0's
    barrier must raise PeerDeadError, and the dump hook on _take_all must
    land exactly one ``incident-*.json`` in ``inc_dir``."""
    from paddlebox_tpu import config
    from paddlebox_tpu.parallel.transport import PeerDeadError, TcpTransport

    saved = {
        n: config.get_flag(n)
        for n in ("transport_peer_dead_s", "obs_incident_dir")
    }
    config.set_flag("transport_peer_dead_s", 0.6)
    config.set_flag("obs_incident_dir", inc_dir)
    eps = [f"127.0.0.1:{p}" for p in _dist_free_ports(2)]
    tps = [TcpTransport(r, eps, timeout=30.0) for r in range(2)]
    raised = False
    try:
        tps[0].send(1, "fr-smoke", b"x")
        assert tps[1].recv("fr-smoke", 0, timeout=5.0) == b"x"
        deadline = time.monotonic() + 5.0
        while tps[0].peer_status(1) != "alive":
            assert time.monotonic() < deadline, "peers never connected"
            time.sleep(0.01)
        tps[1].close()  # rank 1 dies mid-run: no more heartbeats
        try:
            tps[0].barrier("fr-smoke-dead", timeout=30.0)
        except PeerDeadError:
            raised = True  # expected: detector names the dead rank
    finally:
        for t in tps:
            t.close()
        for name, v in saved.items():
            config.set_flag(name, v)
    bundles = sorted(
        f for f in os.listdir(inc_dir) if f.startswith("incident-")
    ) if os.path.isdir(inc_dir) else []
    return raised, bundles


def run_distributed(args):
    from paddlebox_tpu import config
    from paddlebox_tpu.utils.faultinject import fail_nth, fail_prob
    from paddlebox_tpu.utils.monitor import STAT_GET

    import obs_report

    config.set_flag("transport_heartbeat_s", 0.05)
    config.set_flag("transport_backoff_s", 0.005)
    # fault budget (times=) below the per-send retry budget: exhaustion is
    # impossible by construction, every injected schedule must heal
    config.set_flag("transport_send_retries", 6)
    n = args.distributed

    # soak 1: clean, codec on (the default wire)
    config.set_flag("host_wire_codec", True)
    w0 = _wire_snapshot()
    clean, _, wall_c = _dist_soak_once(n, args.passes, args.rows, args.seed, ())
    codec_wire = _wire_delta(w0, _wire_snapshot())

    # soak 2: faulted, codec on — send/recv flakes plus decode faults at
    # the new wire.host_decode site (a corrupt-after-CRC inflate kills the
    # connection; resync must replay exactly-once). This soak also runs
    # with per-rank profilers AND the PBTX trace-context frame extension
    # on: tracing must survive the fault schedule, and the exported
    # traces must merge into one timeline with cross-rank trace_id pairs.
    rules = [
        fail_prob("transport.send", args.send_flake_prob,
                  seed=args.seed + 1, times=6),
        fail_nth("transport.recv_frame", 7 + args.seed % 5, times=1),
        fail_nth("transport.recv_frame", 23 + args.seed % 7, times=1),
        fail_nth("wire.host_decode", 2 + args.seed % 3, times=1),
        fail_nth("wire.host_decode", 9 + args.seed % 5, times=1),
    ]
    with tempfile.TemporaryDirectory(prefix="chaos-traces-") as trace_dir:
        config.set_flag("transport_trace_frames", True)
        try:
            faulted, plan, wall_i = _dist_soak_once(
                n, args.passes, args.rows, args.seed, rules,
                trace_dir=trace_dir,
            )
        finally:
            config.set_flag("transport_trace_frames", False)
        merge = obs_report.merge_traces(
            [os.path.join(trace_dir, f"trace-{r}.json") for r in range(n)],
            os.path.join(trace_dir, "merged.json"),
        )

    # soak 3: clean, raw ablation — same results, more bytes; the
    # cross-soak host_bytes_sent ratio is the measured compression win
    config.set_flag("host_wire_codec", False)
    w0 = _wire_snapshot()
    try:
        raw, _, wall_r = _dist_soak_once(
            n, args.passes, args.rows, args.seed, ()
        )
    finally:
        config.set_flag("host_wire_codec", True)
    raw_wire = _wire_delta(w0, _wire_snapshot())

    # flight-recorder smoke: real peer death -> incident bundle on disk
    with tempfile.TemporaryDirectory() as inc_dir:
        fr_raised, fr_bundles = _flight_recorder_smoke(inc_dir)

    equal = _digests_equal(clean, faulted, n)
    equal_raw = _digests_equal(clean, raw, n)
    trace_ok = (
        len(merge["process_rows"]) == n
        and merge["cross_rank_trace_ids"] >= 1
    )
    fr_ok = fr_raised and len(fr_bundles) >= 1
    report = {
        "mode": "distributed",
        "ranks": n,
        "passes": args.passes,
        "faults_injected": {
            site: plan.failures(site)
            for site in (
                "transport.send", "transport.recv_frame", "wire.host_decode",
            )
        },
        "transport_stats": {
            k: STAT_GET(k)
            for k in (
                "transport.send_retries",
                "transport.frames_resent",
                "transport.reconnects",
                "transport.dup_frames_dropped",
                "transport.decode_errors",
            )
        },
        "host_wire": {
            "codec": codec_wire,
            "raw": raw_wire,
            # ≥2x is the ROADMAP item 2 gate: actual frame bytes, codec
            # soak vs raw-ablation soak of the identical schedule
            "host_bytes_ratio_raw_over_codec": _ratio(
                raw_wire["wire.host_bytes_sent"],
                codec_wire["wire.host_bytes_sent"],
            ),
            # per-exchange-round ratios inside the codec soak: raw-
            # equivalent bytes over encoded bytes
            "ws_req_ratio": _ratio(
                codec_wire["wire.ws_req_raw_bytes"],
                codec_wire["wire.ws_req_bytes"],
            ),
            "ws_rep_ratio": _ratio(
                codec_wire["wire.ws_rep_raw_bytes"],
                codec_wire["wire.ws_rep_bytes"],
            ),
            # frame-level ratio inside the codec soak (what v2 framing
            # would have shipped over what v3 shipped)
            "frame_ratio": _ratio(
                codec_wire["wire.host_raw_bytes_sent"],
                codec_wire["wire.host_bytes_sent"],
            ),
        },
        "trace_merge": {
            "process_rows": merge["process_rows"],
            "events": merge["events"],
            "trace_ids": merge["trace_ids"],
            "cross_rank_trace_ids": merge["cross_rank_trace_ids"],
            "trace_frames_sent": int(STAT_GET("transport.trace_frames_sent")),
            "trace_frames_recv": int(STAT_GET("transport.trace_frames_recv")),
            "ok": trace_ok,
        },
        "flight_recorder": {
            "peer_dead_raised": fr_raised,
            "incident_bundles": len(fr_bundles),
            "ok": fr_ok,
        },
        "bitwise_equal_to_clean": equal,
        "bitwise_equal_raw_vs_codec": equal_raw,
        "wall_clean_s": round(wall_c, 2),
        "wall_injected_s": round(wall_i, 2),
        "wall_raw_s": round(wall_r, 2),
    }
    print(json.dumps(report, indent=None if args.json else 2))
    return 0 if equal and equal_raw and trace_ok and fr_ok else 1


class _ProbeRankKilled(BaseException):
    """Escapes every supervisor except-Exception tier, like a real death."""


_ELASTIC_MESH = 8


def _elastic_records(seed, pass_idx, n_records):
    """One pass's GLOBAL record stream — identical for every membership;
    routing (record i -> sorted(live)[i % n_live]) decides who trains it."""
    rng = np.random.default_rng(1000 * seed + pass_idx)
    pool = rng.integers(1, 160, 4096).astype(np.uint64)
    recs = []
    for _ in range(n_records):
        nk = int(rng.integers(1, 4))
        keys = np.unique(rng.choice(pool, nk))
        recs.append((keys, float(rng.integers(0, 2))))
    return recs


def _elastic_mk_sup(rank, tps, root, seed, n_records, recorder, kill_at=None):
    from types import SimpleNamespace

    from paddlebox_tpu.parallel.membership import OwnershipMap
    from paddlebox_tpu.table import (
        HostSparseTable,
        SparseOptimizerConfig,
        ValueLayout,
    )
    from paddlebox_tpu.table.dist_ws import DistributedWorkingSet
    from paddlebox_tpu.train.checkpoint import CheckpointManager, rank_root
    from paddlebox_tpu.train.supervisor import (
        ElasticConfig,
        HealthGates,
        PassSupervisor,
        RetryPolicy,
    )

    table = HostSparseTable(
        ValueLayout(embedx_dim=2), SparseOptimizerConfig(embedx_threshold=0.0),
        n_shards=2, seed=0,
    )

    class _DS:
        """Dataset double over a REAL table + DistributedWorkingSet (the
        same harness tests/test_elastic.py pins in tier-1)."""

        def __init__(self):
            self.transport = tps[rank]
            self.table = table
            self.n_mesh_shards = _ELASTIC_MESH
            self.ownership = None
            self.pass_epoch = 0
            self._in_pass = False
            self.pass_idx = -1
            self.ws = None
            self.dev = None
            self.my_records = []

        def set_date(self, date):
            pass

        def set_filelist(self, files):
            self._files = list(files)

        def load_into_memory(self):
            self.pass_idx = int(self._files[0].rsplit("-", 1)[1])

        def _omap(self):
            return self.ownership or OwnershipMap.even(
                self.n_mesh_shards, self.transport.n_ranks
            )

        def begin_pass(self, round_to=8, enable_revert=True, trainer=None):
            live = list(self._omap().live_ranks)
            recs = _elastic_records(seed, self.pass_idx, n_records)
            me = self.transport.rank
            self.my_records = [
                rec for i, rec in enumerate(recs)
                if live[i % len(live)] == me
            ]
            ws = DistributedWorkingSet(
                self.transport, self.n_mesh_shards, pass_id=self.pass_idx,
                epoch=self.pass_epoch, ownership=self._omap(),
            )
            for keys, _ in self.my_records:
                ws.add_keys(keys)
            self.dev = ws.finalize(self.table, round_to=8)
            self.ws = ws
            self._in_pass = True

        def end_pass(self, table_, shrink=True):
            self.ws.writeback(self.dev)
            self._in_pass = False

        def revert_pass(self):
            # rows were only CREATED in finalize (deterministic init),
            # never trained: dropping the device slice reverts the pass
            self.ws = None
            self.dev = None
            self._in_pass = False
            self.pass_epoch += 1

    ds = _DS()

    def train_pass(_ds, n_batches=None):
        if kill_at is not None and ds.pass_idx == kill_at:
            ds.transport.close()
            raise _ProbeRankKilled()
        ds.dev = ds.dev * np.float32(1.01) + np.float32(0.25)
        preds, labels = [], []
        for keys, label in ds.my_records:
            rows = ds.ws.lookup(keys).astype(np.int64)
            preds.append(((int(rows.sum()) + ds.pass_idx) % 97) / 97.0)
            labels.append(label)
        recorder[(rank, ds.pass_idx)] = (
            np.array(preds, np.float32), np.array(labels, np.float32),
        )
        return {"batches": 1.0, "nan_batches": 0.0, "auc": 0.5}

    tr = SimpleNamespace(
        params=None,
        prepare_pass=lambda _ds, n: None,
        train_pass=train_pass,
        trained_table=lambda: None,
        init_params=lambda *a, **k: None,
        load_dense=lambda path: None,
        save_dense=lambda path: np.savez(path, z=np.zeros(1, np.float32)),
        _state=None,
        _state_ws=None,
    )
    sup = PassSupervisor(
        ds, tr,
        checkpoint=CheckpointManager(rank_root(root, rank)),
        gates=HealthGates(auc_min_history=99),
        retry=RetryPolicy(max_retries=2, backoff_s=0.0, sleep=lambda s: None),
        round_to=8,
        transport=tps[rank],
        elastic=ElasticConfig(shared_root=root, member_timeout=5.0),
    )
    return sup, ds


def _probe_run_threads(fn, n, join_s=300.0):
    """Run fn(rank) on n threads; each rank's state (supervisor, table,
    transport) is thread-confined — fn(r) only ever touches rank r's
    objects. Returns (results, errors)."""
    import threading

    results, errors = [None] * n, []

    def _wrap(r):
        try:
            results[r] = fn(r)
        except BaseException as e:  # noqa: BLE001 - re-raised by caller
            errors.append((r, e))

    threads = [threading.Thread(target=_wrap, args=(r,)) for r in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(join_s)
    return results, errors


def _elastic_run_day(n, root, seed, n_records, passes, recorder,
                     kill_rank=None, kill_at=None):
    from paddlebox_tpu.parallel.transport import TcpTransport

    eps = [f"127.0.0.1:{p}" for p in _dist_free_ports(n)]
    tps = [TcpTransport(r, eps, timeout=60.0) for r in range(n)]
    sups = [
        _elastic_mk_sup(
            r, tps, root, seed, n_records, recorder,
            kill_at=(kill_at if r == kill_rank else None),
        )[0]
        for r in range(n)
    ]
    files = [[f"pass-{p}"] for p in range(passes)]

    def day(r):
        try:
            return sups[r].run_day("20260101", files)
        except _ProbeRankKilled:
            return "killed"

    t0 = time.perf_counter()
    try:
        results, errors = _probe_run_threads(day, n)
    finally:
        for t in tps:
            t.close()
    if errors:
        raise errors[0][1]
    return sups, results, time.perf_counter() - t0


def _elastic_merged_digest(sups, ranks):
    """Ownership-filtered global digest: every key exactly once, under its
    CURRENT owner."""
    from paddlebox_tpu.table.sparse_table import key_to_shard

    keys_parts, row_parts = [], []
    for r in ranks:
        sup = sups[r]
        lo, hi = sup.ds._omap().range_of(sup.coord.transport.rank)
        k = np.sort(sup.table.keys())
        sh = key_to_shard(k, _ELASTIC_MESH)
        k = k[(sh >= lo) & (sh < hi)]
        keys_parts.append(k)
        row_parts.append(sup.table.pull_or_create(k))
    keys = np.concatenate(keys_parts)
    rows = np.concatenate(row_parts)
    order = np.argsort(keys, kind="stable")
    assert len(keys) == len(np.unique(keys)), "ownership ranges overlap"
    return keys[order], rows[order]


def _elastic_pass_auc(recorder, p):
    import jax.numpy as jnp

    from paddlebox_tpu.metrics.auc import auc_compute, auc_init, auc_update

    entries = [v for (r, pp), v in sorted(recorder.items()) if pp == p]
    preds = np.concatenate([e[0] for e in entries])
    labels = np.concatenate([e[1] for e in entries])
    state = auc_update(auc_init(1000), jnp.asarray(preds), jnp.asarray(labels))
    return np.asarray(auc_compute(state))


def _elastic_run_day_rejoin(n, root, seed, n_records, passes, recorder,
                            join_rank):
    """N-rank day where ``join_rank`` dies at the top of pass 1 and a
    successor incarnation of the SAME rank rejoins mid-day. The rejoin
    waits until every survivor has INSTALLED the shrink (ownership epoch
    >= 1) — the earliest announce point that cannot mask the old
    incarnation's silence from the failure detector — so the join lands
    with the most day left to train."""
    from paddlebox_tpu.parallel.transport import TcpTransport

    eps = [f"127.0.0.1:{p}" for p in _dist_free_ports(n)]
    tps = [TcpTransport(r, eps, timeout=60.0) for r in range(n)]
    sups = [
        _elastic_mk_sup(
            r, tps, root, seed, n_records, recorder,
            kill_at=(1 if r == join_rank else None),
        )[0]
        for r in range(n)
    ]
    files = [[f"pass-{p}"] for p in range(passes)]
    survivors = [r for r in range(n) if r != join_rank]

    def day(r):
        if r != join_rank:
            return sups[r].run_day("20260101", files)
        try:
            sups[r].run_day("20260101", files)
            raise AssertionError("join rank was not killed")
        except _ProbeRankKilled:
            pass
        deadline = time.monotonic() + 120.0
        while not all(
            sups[s].ds.ownership is not None
            and sups[s].ds.ownership.epoch >= 1
            for s in survivors
        ):
            if time.monotonic() >= deadline:
                raise AssertionError("survivors never installed the shrink")
            time.sleep(0.02)
        tps[r] = TcpTransport(r, eps, timeout=60.0)
        sups[r] = _elastic_mk_sup(r, tps, root, seed, n_records, recorder)[0]
        return sups[r].join_day(files, timeout=120.0)

    t0 = time.perf_counter()
    try:
        results, errors = _probe_run_threads(day, n)
    finally:
        for t in tps:
            t.close()
    if errors:
        raise errors[0][1]
    return sups, results, time.perf_counter() - t0


def run_join_rank(args):
    """Elastic grow soak (``--join-rank=R``): an N-rank supervised day
    loses rank R at the top of pass 1 (shrink, epoch 1); a successor
    incarnation of the same rank announces once the shrunk fleet has
    installed the shrink, catches up from the published chains, receives
    its carved ranges through stage-then-commit migration and the fleet
    flips to epoch 2 — and the final ownership-filtered digest plus
    per-pass global AUC must be bitwise-equal to a FRESH fixed-size
    N-rank run of the same day. Exit 0 iff every gate holds.

      JAX_PLATFORMS=cpu python tools/chaos_probe.py --join-rank 1 \\
          --passes 5 [--json]
    """
    import glob as globmod

    from paddlebox_tpu import config
    from paddlebox_tpu.train.checkpoint import (
        rank_root,
        read_watermark,
        validate_watermark,
    )
    from paddlebox_tpu.utils.monitor import STAT_GET

    n, join_rank, passes = args.ranks, args.join_rank, args.passes
    if not (0 <= join_rank < n):
        print(f"--join-rank must be in [0, {n})", file=sys.stderr)
        return 2
    if passes < 4:
        print("--passes must be >= 4 (the kill, the shrink and the "
              "rejoin all land mid-day)", file=sys.stderr)
        return 2
    n_records = args.rows
    saved = {
        name: config.get_flag(name)
        for name in (
            "transport_heartbeat_s", "transport_backoff_s",
            "transport_send_retries", "transport_peer_dead_s",
        )
    }
    config.set_flag("transport_heartbeat_s", 0.05)
    config.set_flag("transport_backoff_s", 0.005)
    config.set_flag("transport_send_retries", 6)
    joins_before = STAT_GET("membership.joins_total")
    try:
        with tempfile.TemporaryDirectory() as tmpdir:
            # the elastic day: rank R dies at pass 1, rejoins mid-day
            config.set_flag("transport_peer_dead_s", 0.6)
            rec_e = {}
            root_e = os.path.join(tmpdir, "elastic")
            sups_e, res_e, wall_e = _elastic_run_day_rejoin(
                n, root_e, args.seed, n_records, passes, rec_e,
                join_rank=join_rank,
            )
            config.set_flag("transport_peer_dead_s", 60.0)
            survivors = [r for r in range(n) if r != join_rank]
            finished_ok = all(
                isinstance(res_e[r], list) and len(res_e[r]) == passes
                for r in survivors
            )
            rejoined_passes = (
                len(res_e[join_rank])
                if isinstance(res_e[join_rank], list) else -1
            )
            epochs = [
                sups_e[r].ds.ownership.epoch
                if sups_e[r].ds.ownership is not None else 0
                for r in range(n)
            ]
            live_after = (
                list(sups_e[0].ds.ownership.live_ranks)
                if sups_e[0].ds.ownership is not None else []
            )
            kinds_surv = sorted({
                i.kind for r in survivors for i in sups_e[r].incidents
            })
            joiner_kinds = sorted({
                i.kind for i in sups_e[join_rank].incidents
            })
            bundles = sum(
                len(globmod.glob(os.path.join(
                    rank_root(root_e, r), "obs", "incidents",
                    "incident-*.json",
                )))
                for r in range(n)
            )
            wm = read_watermark(rank_root(root_e, join_rank))
            validate_watermark(wm)
            wm_epoch = int(wm["ownership_epoch"])
            wm_live = list(wm.get("live_ranks", []))

            # the reference: a FRESH fixed-size N-rank run of the same day
            rec_f = {}
            sups_f, res_f, wall_f = _elastic_run_day(
                n, os.path.join(tmpdir, "fresh"), args.seed,
                n_records, passes, rec_f,
            )
            fresh_ok = all(
                isinstance(r, list) and len(r) == passes for r in res_f
            )
            ek, ev = _elastic_merged_digest(sups_e, list(range(n)))
            fk, fv = _elastic_merged_digest(sups_f, list(range(n)))
            digest_equal = bool(
                np.array_equal(ek, fk) and np.array_equal(ev, fv)
            )
            auc_equal = all(
                np.array_equal(
                    _elastic_pass_auc(rec_e, p), _elastic_pass_auc(rec_f, p)
                )
                for p in range(passes)
            )
    finally:
        for name, v in saved.items():
            config.set_flag(name, v)

    joins = int(STAT_GET("membership.joins_total") - joins_before)
    ok = (
        finished_ok and fresh_ok and rejoined_passes >= 1
        and all(e == 2 for e in epochs) and live_after == list(range(n))
        and wm_epoch == 2 and wm_live == list(range(n))
        and "rank_death" in kinds_surv and "rank_join" in kinds_surv
        and "rank_join" in joiner_kinds
        and joins >= n and bundles >= 1
        and digest_equal and auc_equal
    )
    report = {
        "mode": "join-rank",
        "ranks": n,
        "join_rank": join_rank,
        "kill_at_pass": 1,
        "passes": passes,
        "records_per_pass": n_records,
        "survivors_finished": bool(finished_ok),
        "rejoined_trained_passes": rejoined_passes,
        "ownership_epoch_after": epochs[0] if epochs else None,
        "live_ranks_after": live_after,
        "watermark_ownership_epoch": wm_epoch,
        "watermark_live_ranks": wm_live,
        "membership_joins": joins,
        "incident_kinds": sorted(set(kinds_surv) | set(joiner_kinds)),
        "incident_bundles": bundles,
        "digest_keys": int(len(ek)),
        "bitwise_equal_to_fresh_grown_run": digest_equal,
        "auc_equal_per_pass": bool(auc_equal),
        "wall_elastic_s": round(wall_e, 2),
        "wall_fresh_s": round(wall_f, 2),
        "ok": bool(ok),
    }
    print(json.dumps(report, indent=None if args.json else 2))
    return 0 if ok else 1


def run_kill_rank(args):
    """Elastic-membership soak (``--kill-rank=R``): an N-rank supervised
    day loses rank R mid-pass; survivors agree on the shrunk membership,
    adopt the dead rank's shard ranges from its checkpoint, revert the
    in-flight pass and finish the day — and the final ownership-filtered
    sparse digest AND per-pass global AUC must be bitwise-equal to a
    FRESH (N-1)-rank run of the same day. Exit 0 iff every gate holds.

      JAX_PLATFORMS=cpu python tools/chaos_probe.py --kill-rank 1 [--json]
    """
    import glob as globmod

    from paddlebox_tpu import config
    from paddlebox_tpu.train.checkpoint import (
        rank_root,
        read_watermark,
        validate_watermark,
    )
    from paddlebox_tpu.utils.monitor import STAT_GET

    n, kill_rank, passes, kill_at = args.ranks, args.kill_rank, args.passes, 1
    if not (0 <= kill_rank < n):
        print(f"--kill-rank must be in [0, {n})", file=sys.stderr)
        return 2
    if passes < 2:
        print("--passes must be >= 2 (the kill lands mid-day)",
              file=sys.stderr)
        return 2
    n_records = args.rows
    saved = {
        name: config.get_flag(name)
        for name in (
            "transport_heartbeat_s", "transport_backoff_s",
            "transport_send_retries", "transport_peer_dead_s",
        )
    }
    config.set_flag("transport_heartbeat_s", 0.05)
    config.set_flag("transport_backoff_s", 0.005)
    config.set_flag("transport_send_retries", 6)
    adopts_before = STAT_GET("membership.adopts")
    try:
        with tempfile.TemporaryDirectory() as tmpdir:
            # the elastic day: N ranks, one dies at the top of pass 1
            config.set_flag("transport_peer_dead_s", 0.6)
            rec_e = {}
            root_e = os.path.join(tmpdir, "elastic")
            sups_e, res_e, wall_e = _elastic_run_day(
                n, root_e, args.seed, n_records, passes, rec_e,
                kill_rank=kill_rank, kill_at=kill_at,
            )
            config.set_flag("transport_peer_dead_s", 60.0)
            survivors = [r for r in range(n) if r != kill_rank]
            killed_ok = res_e[kill_rank] == "killed"
            finished_ok = all(
                isinstance(res_e[r], list) and len(res_e[r]) == passes
                for r in survivors
            )
            epochs = [
                sups_e[r].ds.ownership.epoch
                if sups_e[r].ds.ownership is not None else 0
                for r in survivors
            ]
            kinds = sorted({
                i.kind for r in survivors for i in sups_e[r].incidents
            })
            bundles = sum(
                len(globmod.glob(os.path.join(
                    rank_root(root_e, r), "obs", "incidents",
                    "incident-*.json",
                )))
                for r in survivors
            )
            wm = read_watermark(rank_root(root_e, survivors[0]))
            validate_watermark(wm)
            wm_epoch = int(wm["ownership_epoch"])

            # the reference: a FRESH (N-1)-rank run of the same day
            rec_f = {}
            sups_f, res_f, wall_f = _elastic_run_day(
                n - 1, os.path.join(tmpdir, "fresh"), args.seed,
                n_records, passes, rec_f,
            )
            fresh_ok = all(
                isinstance(r, list) and len(r) == passes for r in res_f
            )
            ek, ev = _elastic_merged_digest(sups_e, survivors)
            fk, fv = _elastic_merged_digest(sups_f, list(range(n - 1)))
            digest_equal = bool(
                np.array_equal(ek, fk) and np.array_equal(ev, fv)
            )
            auc_equal = all(
                np.array_equal(
                    _elastic_pass_auc(rec_e, p), _elastic_pass_auc(rec_f, p)
                )
                for p in range(passes)
            )
    finally:
        for name, v in saved.items():
            config.set_flag(name, v)

    adopts = int(STAT_GET("membership.adopts") - adopts_before)
    ok = (
        killed_ok and finished_ok and fresh_ok
        and all(e == 1 for e in epochs) and wm_epoch == 1
        and "rank_death" in kinds and bundles >= len(survivors)
        and adopts >= 1 and digest_equal and auc_equal
    )
    report = {
        "mode": "kill-rank",
        "ranks": n,
        "killed_rank": kill_rank,
        "kill_at_pass": kill_at,
        "passes": passes,
        "records_per_pass": n_records,
        "survivors": survivors,
        "survivors_finished": bool(finished_ok),
        "ownership_epoch_after": epochs[0] if epochs else None,
        "watermark_ownership_epoch": wm_epoch,
        "membership_adopts": adopts,
        "incident_kinds": kinds,
        "incident_bundles": bundles,
        "digest_keys": int(len(ek)),
        "bitwise_equal_to_fresh_shrunk_run": digest_equal,
        "auc_equal_per_pass": bool(auc_equal),
        "wall_elastic_s": round(wall_e, 2),
        "wall_fresh_s": round(wall_f, 2),
        "ok": bool(ok),
    }
    print(json.dumps(report, indent=None if args.json else 2))
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--days", type=int, default=2)
    ap.add_argument("--passes", type=int, default=3, help="passes per day")
    ap.add_argument("--rows", type=int, default=64, help="rows per pass file")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fs-flake-prob", type=float, default=0.05,
                    help="iid flake probability at fs.open_read")
    ap.add_argument("--step-faults", type=int, default=2,
                    help="poisoned device steps across the schedule")
    ap.add_argument("--save-faults", type=int, default=2,
                    help="torn checkpoint-save windows across the schedule")
    ap.add_argument("--distributed", type=int, default=0, metavar="N",
                    help="soak an N-rank in-process cluster under seeded "
                         "transport faults instead of the single-rank "
                         "supervisor schedule")
    ap.add_argument("--send-flake-prob", type=float, default=0.15,
                    help="iid flake probability at transport.send "
                         "(--distributed mode)")
    ap.add_argument("--kill-rank", type=int, default=None, metavar="R",
                    help="elastic-membership soak: an N-rank supervised "
                         "day loses rank R mid-pass; survivors must adopt "
                         "its shard ranges and finish bitwise-equal to a "
                         "fresh (N-1)-rank run of the same day")
    ap.add_argument("--join-rank", type=int, default=None, metavar="R",
                    help="elastic grow soak: rank R dies at pass 1 "
                         "(shrink), a successor incarnation rejoins once "
                         "the survivors installed the shrink (grow, epoch "
                         "2), and the day must finish bitwise-equal to a "
                         "fresh fixed-size N-rank run")
    ap.add_argument("--ranks", type=int, default=4,
                    help="cluster size for the --kill-rank / --join-rank "
                         "soaks")
    ap.add_argument("--corrupt-rate", type=float, default=0.0, metavar="P",
                    help="iid per-line data corruption probability; "
                         "switches to the quarantine/degrade soak "
                         "(single-rank only)")
    ap.add_argument("--native-sanitize", action="store_true",
                    help="memory-safety soak instead: rebuild the native "
                         "tier under ASan+UBSan and replay the native test "
                         "files against the instrumented library "
                         "(tools/native_sanitize.py, full set)")
    ap.add_argument("--tsan", action="store_true",
                    help="with --native-sanitize: ThreadSanitizer mode — "
                         "rebuild with -fsanitize=thread and replay the "
                         "parallel-writeback suites (writer-pool race "
                         "coverage)")
    ap.add_argument("--serve", action="store_true",
                    help="serving-chain corruption smoke: a follower must "
                         "skip a corrupted published delta with an alarm, "
                         "keep serving the last good version bitwise, and "
                         "catch up once the delta is repaired")
    ap.add_argument("--serve-fleet", action="store_true",
                    help="fleet churn soak under injected serve faults: "
                         "the networked serving day (kill + drain/admit + "
                         "rejoin over a shared stage) with lost requests, "
                         "a torn stage fetch, and a dropped drain command "
                         "injected — zero client-visible failures and "
                         "bitwise parity must survive all of it")
    ap.add_argument("--serve-shard", action="store_true",
                    help="mesh-sharded tier crash probe: a follower with "
                         "the device scoring tier on takes an injected "
                         "crash mid-tier-build (serve.tier_build) — the "
                         "old version must keep serving bitwise with no "
                         "partial tier, and the healed retry must land "
                         "the delta bitwise with the tier rebuilt")
    ap.add_argument("--ici-wire", action="store_true",
                    help="A/B the frequency-adaptive ICI wire: mesh-trainer "
                         "days over one zipf-keyed day in fp32 / bf16 / "
                         "adaptive / ablation, gating the >=2x payload cut "
                         "vs fp32, adaptive < bf16, AUC neutrality, and the "
                         "off-ablation bitwise match")
    ap.add_argument("--proto-check", action="store_true",
                    help="model-check the bounded elastic membership "
                         "protocol instead: the clean model must reach a "
                         "fixpoint with zero invariant violations and "
                         "every broken variant must be caught on its "
                         "invariant (tools/proto_check.py)")
    ap.add_argument("--stream", action="store_true",
                    help="streaming-plane fault sweep: seeded faults on "
                         "stream.tail_read, stream.cut_publish and "
                         "ckpt.compact (all must fire), recovery bitwise "
                         "vs an uninterrupted clean twin")
    ap.add_argument("--json", action="store_true", help="machine output only")
    args = ap.parse_args(argv)

    if args.native_sanitize:
        import native_sanitize

        return native_sanitize.main(["--tsan"] if args.tsan else [])
    if args.proto_check:
        return run_proto_check(args)
    if args.ici_wire:
        return run_ici_wire(args)
    if args.serve_shard:
        return run_serve_shard(args)
    if args.serve_fleet:
        return run_serve_fleet(args)
    if args.stream:
        return run_stream(args)
    if args.serve:
        return run_serve(args)
    if args.join_rank is not None:
        return run_join_rank(args)
    if args.kill_rank is not None:
        return run_kill_rank(args)
    if args.distributed:
        return run_distributed(args)
    if args.corrupt_rate > 0:
        return run_corrupt(args)

    from paddlebox_tpu import config
    from paddlebox_tpu.utils.faultinject import fail_nth, fail_prob
    from paddlebox_tpu.utils.monitor import STAT_GET

    config.set_flag("fs_open_backoff_s", 0.0)
    with tempfile.TemporaryDirectory() as tmpdir:
        days = []
        for d in range(args.days):
            date = f"202601{d + 1:02d}"
            days.append(
                (date, write_day_files(
                    tmpdir, date, args.passes, args.rows, args.seed + d))
            )

        # clean twin (an empty plan counts hits so fault schedules can be
        # sized relative to the real hit volume)
        table_c, tr_c, sup_c, probe, wall_c = run_schedule(
            tmpdir, "clean", days, ()
        )
        n_steps = probe.hits("step.device")
        n_saves = probe.hits("checkpoint.save")

        rng = np.random.default_rng(args.seed)
        rules = [fail_prob("fs.open_read", args.fs_flake_prob,
                           seed=args.seed, times=None)]
        for h in sorted(rng.choice(
                np.arange(2, max(3, n_steps)), size=min(args.step_faults,
                max(1, n_steps - 2)), replace=False).tolist()):
            rules.append(fail_nth("step.device", int(h)))
        for h in sorted(rng.choice(
                np.arange(2, max(3, n_saves)), size=min(args.save_faults,
                max(1, n_saves - 2)), replace=False).tolist()):
            rules.append(fail_nth("checkpoint.save", int(h)))

        table_i, tr_i, sup_i, plan, wall_i = run_schedule(
            tmpdir, "inj", days, rules
        )

        k_c, v_c, d_c = final_state(table_c, tr_c)
        k_i, v_i, d_i = final_state(table_i, tr_i)
        equal = (
            np.array_equal(k_i, k_c)
            and np.array_equal(v_i, v_c)
            and len(d_i) == len(d_c)
            and all(np.array_equal(a, b) for a, b in zip(d_i, d_c))
        )
        report = {
            "days": args.days,
            "passes_per_day": args.passes,
            "faults_injected": {
                site: plan.failures(site)
                for site in ("fs.open_read", "step.device", "checkpoint.save")
            },
            "incidents": [i.as_dict() for i in sup_i.incidents],
            "stat_faults_injected": STAT_GET("faults_injected"),
            "bitwise_equal_to_clean": bool(equal),
            "wall_clean_s": round(wall_c, 2),
            "wall_injected_s": round(wall_i, 2),
        }
        print(json.dumps(report if args.json else report, indent=None if args.json else 2))
        return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main())
