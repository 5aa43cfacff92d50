"""Round-6 verify drive: full user flow through public imports on CPU.

1. slot-format file -> parse -> working set -> finalize -> train loop
   (AUC must rise, loss must fall) -> writeback -> save/reload equality
2. carried boundary with eager flush + INJECTED flush failure: the error
   must surface at the next pass boundary, the carrier must stay owed,
   and a retried drain must land the carried values in the checkpoint
3. error probes: zero-count slot line, unknown ws key
4. the persistent compile cache (fixed .jax_cache/ in the checkout, or
   $JAX_COMPILATION_CACHE_DIR) serves a repeated program from disk
5. static gates: the full three-root pbox-lint scan must exit 0 with the
   empty baseline, and the native tier must replay clean under ASan+UBSan
   (quick set; skips green on images without g++)
"""
import os, sys, tempfile
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import optax

from paddlebox_tpu.data import BoxPSDataset, SlotInfo, SlotSchema
from paddlebox_tpu.data.parser import parse_line
from paddlebox_tpu.models import DeepFM
from paddlebox_tpu.table import HostSparseTable, SparseOptimizerConfig, ValueLayout
from paddlebox_tpu.train import CTRTrainer, TrainStepConfig
from paddlebox_tpu import config

S = 4
rng = np.random.default_rng(7)

def write_file(path, n=2000):
    # fixture writer: path is this run's scratch space
    # pbox-lint: disable=IO004
    with open(path, "w") as f:
        for _ in range(n):
            keys = rng.integers(1, 500, S)
            label = 1.0 if (keys % 7 == 0).any() else 0.0  # learnable
            f.write(f"1 {label} " + " ".join(f"1 {k}" for k in keys) + "\n")

schema = SlotSchema(
    [SlotInfo("label", type="float", dense=True, dim=1)]
    + [SlotInfo(f"s{i}") for i in range(S)],
    label_slot="label",
)
layout = ValueLayout(embedx_dim=8)
opt_cfg = SparseOptimizerConfig(embedx_threshold=0.0)

# --- 1. full flow -------------------------------------------------------
tmp = tempfile.mkdtemp()
f1 = os.path.join(tmp, "p1.txt"); write_file(f1)
table = HostSparseTable(layout, opt_cfg, n_shards=4, seed=0)
ds = BoxPSDataset(schema, table, batch_size=256, shuffle_mode="none")
ds.set_filelist([f1]); ds.load_into_memory(); ds.begin_pass(round_to=64)
model = DeepFM(S, layout.pull_width, layout.embedx_dim, hidden=(32,))
cfg = TrainStepConfig(num_slots=S, batch_size=256, layout=layout,
                      sparse_opt=opt_cfg, auc_buckets=1000)
tr = CTRTrainer(model, cfg, dense_opt=optax.adam(1e-2))
tr.init_params(jax.random.PRNGKey(0))
out1 = tr.train_pass(ds)
tr.train_pass(ds)
out2 = tr.train_pass(ds)  # three passes: ~0.52 -> 0.71 -> 0.89 on this seed
assert out2["auc"] > 0.75, f"AUC did not rise: {out2}"
assert out2["loss"] < out1["loss"], (out1["loss"], out2["loss"])
print(f"[1] train ok: auc {out1['auc']:.3f} -> {out2['auc']:.3f}, "
      f"loss {out1['loss']:.4f} -> {out2['loss']:.4f}")

# --- 2. carried boundary + injected flush failure ----------------------
config.set_flag("enable_carried_table", 1)
config.set_flag("carried_eager_flush", 0)  # drain manually for injection
ds.end_pass(tr.trained_table_device())  # builds a carrier (no transfer)
assert table._pending_carriers, "carrier not registered"

# inject: make the NEXT drain fail once
orig_push = table.push
calls = {"n": 0}
def bad_push(keys, vals):
    calls["n"] += 1
    raise OSError("injected push IO error")
table.push = bad_push
try:
    table.drain_pending()
    raised = False
# the except IS the assertion: the injected error must surface here
# pbox-lint: disable=EXC007
except OSError:
    raised = True
table.push = orig_push
assert raised and calls["n"] == 1, "injected failure did not surface"
assert table._pending_carriers, "FAILED drain dropped the carrier (ADVICE bug)"
n = table.drain_pending()
assert n > 0, "retry drain flushed nothing"
print(f"[2] drain durability ok: carrier survived failed flush, retry wrote {n} keys")

# eager-flush thread error surfacing: store an error as the thread would
f2 = os.path.join(tmp, "p2.txt"); write_file(f2)
ds.set_filelist([f2]); ds.load_into_memory()
ds._eager_flush_error = RuntimeError("boom")
try:
    ds.begin_pass(round_to=64)
    print("[2b] FAIL: pending flush error not raised"); sys.exit(1)
except RuntimeError as e:
    assert "carrier flush failed" in str(e), e
print("[2b] eager-flush error surfaces at pass boundary")
# error consumed on raise; the real pass proceeds and closes out clean
ds.begin_pass(round_to=64)
tr.train_pass(ds)
probe_keys = ds.ws.sorted_keys[:50].copy()
ws_ref = ds.ws
ds.end_pass(tr.trained_table_device())
table.drain_pending()

# save/reload equality
sd = os.path.join(tmp, "base")
table.save_base(sd)
t2 = HostSparseTable(layout, opt_cfg, n_shards=4, seed=0)
t2.load(sd)
np.testing.assert_allclose(
    table.pull_or_create(probe_keys), t2.pull_or_create(probe_keys), rtol=1e-6
)
print("[3] save/reload row equality ok")

# --- error probes -------------------------------------------------------
try:
    parse_line("0 1.0 1 5", schema); print("FAIL zero-count"); sys.exit(1)
except ValueError:
    pass
try:
    ws_ref.lookup(np.array([999999999], dtype=np.uint64)); print("FAIL lookup"); sys.exit(1)
except KeyError as e:
    assert "999999999" in str(e)
print("[4] error probes ok")

# --- 6. persistent compile cache: a repeated program is a disk hit -------
# (placed by utils/compilecache's rule; the directory persists, so the
# first compile is a miss only on a cold checkout — the gate is the hit)
from paddlebox_tpu.utils import compilecache

cc_dir = compilecache.enable()
assert cc_dir is not None, "compile_cache_dir=off in the environment?"
h0 = compilecache.stats()["hits"]
x = jnp.arange(512.0)
float(jax.jit(lambda v: (v * 3.0 + 1.0).sum())(x))  # compiles or loads
float(jax.jit(lambda v: (v * 3.0 + 1.0).sum())(x))  # same HLO, new fn: disk hit
s_warm = compilecache.stats()
assert s_warm["hits"] > h0, s_warm
assert s_warm["entries"] > 0, s_warm
compilecache.disable()
print(f"[6] compile cache ok: {s_warm['hits'] - h0} warm hit(s), "
      f"{s_warm['entries']} entr(ies) in {cc_dir}")

# --- 8. publish-while-serve soak (the serving tentpole, short) ----------
# Trains a 3-pass day publishing base+deltas while a follower tails and
# serves; the gate is bitwise parity between follower scores and
# trainer-direct scores at every applied delta (docs/SERVING.md).
import serve_soak

with tempfile.TemporaryDirectory() as soak_dir:
    report = serve_soak.run_soak(soak_dir, passes=3, rows=200, qps=25.0, probe_n=16)
assert report["ok"], report
assert report["parity"]["checked"] == 3 and not report["parity"]["mismatched"]
print(f"[8] serve soak ok: {report['requests']} req @ {report['achieved_qps']} qps, "
      f"p50={report['latency']['p50_ms']:.1f}ms p99={report['latency']['p99_ms']:.1f}ms, "
      f"parity bitwise at {report['parity']['checked']} deltas")

# --- 8b. obs plane: selfcheck + flight-recorder smoke -------------------
# obs_report --selfcheck smokes the whole telemetry read/write path
# (histogram quantiles, metric-series round trip, incident bundle,
# 2-rank trace merge with a shared trace_id) in a subprocess; then an
# in-process flight-recorder dump proves THIS process's ring has the
# spans the sections above recorded.
import subprocess

_here = os.path.dirname(os.path.abspath(__file__))
r = subprocess.run(
    [sys.executable, os.path.join(_here, "obs_report.py"), "--selfcheck"],
    capture_output=True, text=True, timeout=300)
assert r.returncode == 0, f"obs selfcheck red:\n{r.stdout}{r.stderr}"
from paddlebox_tpu.obs.flight_recorder import FLIGHT_RECORDER
import json as _json

_inc_dir = os.path.join(tmp, "incidents")
FLIGHT_RECORDER.note_incident("verify_drive_smoke", {"section": "8b"})
_bundle_path = FLIGHT_RECORDER.dump("verify_drive_smoke", dir_path=_inc_dir)
assert _bundle_path is not None and os.path.exists(_bundle_path)
with open(_bundle_path) as _f:
    _bundle = _json.load(_f)
assert any(i["kind"] == "verify_drive_smoke" for i in _bundle["incidents"])
assert _bundle["spans"], "flight recorder saw no spans from the run above"
print(f"[8b] obs plane ok: selfcheck green, incident bundle has "
      f"{len(_bundle['spans'])} span(s) + {len(_bundle['incidents'])} incident(s)")

# --- 9. static gates: lint + native sanitize ----------------------------
# the same commands CI runs, end to end: whole-repo lint (default roots,
# empty baseline) and the ASan+UBSan quick replay of the native tier
r = subprocess.run([sys.executable, os.path.join(_here, "run_lint.py")],
                   capture_output=True, text=True, timeout=600)
assert r.returncode == 0, f"lint gate red:\n{r.stdout}{r.stderr}"
san = subprocess.run(
    [sys.executable, os.path.join(_here, "native_sanitize.py"), "--quick"],
    capture_output=True, text=True, timeout=900)
assert san.returncode == 0, f"sanitize replay red:\n{san.stdout}{san.stderr}"
san_line = san.stdout.strip().splitlines()[-1] if san.stdout.strip() else ""
print(f"[9] static gates ok: lint clean (empty baseline); {san_line}")

# --- 10. elastic membership: kill-rank soak + committed artifact --------
# The --kill-rank soak runs a 4-rank supervised day, kills rank 1 mid-
# pass, and requires the survivors' final digest + per-pass AUC to be
# bitwise-equal to a fresh 3-rank run; SOAK_ELASTIC.json is the committed
# record of that gate and must agree with a live re-run.
_soak_path = os.path.join(os.path.dirname(_here), "SOAK_ELASTIC.json")
assert os.path.exists(_soak_path), "SOAK_ELASTIC.json missing from the repo"
with open(_soak_path) as _f:
    _soak = _json.load(_f)
assert _soak["ok"] and _soak["bitwise_equal_to_fresh_shrunk_run"], _soak
assert _soak["auc_equal_per_pass"] and _soak["ownership_epoch_after"] == 1, _soak
r = subprocess.run(
    [sys.executable, os.path.join(_here, "chaos_probe.py"),
     "--kill-rank", "1", "--json"],
    capture_output=True, text=True, timeout=600)
assert r.returncode == 0, f"kill-rank soak red:\n{r.stdout}{r.stderr}"
_live = _json.loads(r.stdout.strip().splitlines()[-1])
assert _live["ok"] and _live["bitwise_equal_to_fresh_shrunk_run"], _live
print(f"[10] elastic membership ok: rank {_live['killed_rank']} killed "
      f"mid-pass, {len(_live['survivors'])} survivors adopted "
      f"{_live['membership_adopts']} range(s), epoch -> "
      f"{_live['ownership_epoch_after']}, digest+AUC bitwise vs fresh run")

# --- 11. frequency-adaptive ICI wire: A/B soak + committed artifact -----
# The --ici-wire leg trains the SAME zipf day under fp32 / bf16 /
# adaptive / ablation-off and gates the >=2x compiled-payload cut vs
# fp32, adaptive below uniform bf16, AUC neutrality, and the off-
# ablation bitwise match; SOAK_ICIWIRE.json is the committed record of
# that gate and must agree with a live re-run.
_iwsoak_path = os.path.join(os.path.dirname(_here), "SOAK_ICIWIRE.json")
assert os.path.exists(_iwsoak_path), "SOAK_ICIWIRE.json missing from the repo"
with open(_iwsoak_path) as _f:
    _iw = _json.load(_f)
assert _iw["ok"] and _iw["ablation_bitwise_fp32"], _iw
assert _iw["payload_ratio_fp32_over_adaptive"] >= 2.0, _iw
assert _iw["adaptive_below_bf16"] and _iw["auc_delta_adaptive_vs_fp32"] <= 0.02, _iw
r = subprocess.run(
    [sys.executable, os.path.join(_here, "chaos_probe.py"),
     "--ici-wire", "--json"],
    capture_output=True, text=True, timeout=600)
assert r.returncode == 0, f"ici-wire soak red:\n{r.stdout}{r.stderr}"
_iwl = _json.loads(r.stdout.strip().splitlines()[-1])
assert _iwl["ok"] and _iwl["ablation_bitwise_fp32"], _iwl
assert _iwl["payload_ratio_fp32_over_adaptive"] >= 2.0, _iwl
print(f"[11] adaptive ICI wire ok: payload cut "
      f"{_iwl['payload_ratio_fp32_over_adaptive']}x vs fp32, below bf16, "
      f"AUC delta {_iwl['auc_delta_adaptive_vs_fp32']}, "
      f"{_iwl['legs']['adaptive']['hot_keys']} hot key(s), ablation bitwise")
# --- 12. elastic grow: join-rank soak + committed artifact --------------
# The --join-rank soak kills rank 1 at pass 1 (shrink, epoch 1), rejoins
# a successor incarnation once the survivors installed the shrink (grow,
# epoch 2), and requires the final 4-rank digest + per-pass AUC to be
# bitwise-equal to a fresh fixed-size 4-rank run; the "join" block of
# SOAK_ELASTIC.json v2 is the committed record of that gate and must
# agree with a live re-run.
assert _soak.get("version", 1) >= 2 and "join" in _soak, \
    "SOAK_ELASTIC.json must be v2 with a join block"
_join = _soak["join"]
assert _join["ok"] and _join["bitwise_equal_to_fresh_grown_run"], _join
assert _join["auc_equal_per_pass"] and _join["ownership_epoch_after"] == 2, _join
assert _join["rejoined_trained_passes"] >= 1, _join
r = subprocess.run(
    [sys.executable, os.path.join(_here, "chaos_probe.py"),
     "--join-rank", "1", "--passes", "5", "--json"],
    capture_output=True, text=True, timeout=600)
assert r.returncode == 0, f"join-rank soak red:\n{r.stdout}{r.stderr}"
_jl = _json.loads(r.stdout.strip().splitlines()[-1])
assert _jl["ok"] and _jl["bitwise_equal_to_fresh_grown_run"], _jl
assert _jl["auc_equal_per_pass"] and _jl["ownership_epoch_after"] == 2, _jl
print(f"[12] elastic grow ok: rank {_jl['join_rank']} killed at pass "
      f"{_jl['kill_at_pass']}, rejoined and trained "
      f"{_jl['rejoined_trained_passes']} pass(es), epoch -> "
      f"{_jl['ownership_epoch_after']}, {_jl['membership_joins']} join "
      f"commit(s), digest+AUC bitwise vs fresh fixed-size run")
# --- 13. protocol verification: incremental lint + model check ----------
# The incremental lint path (--changed resolves context modules whole-
# program but reports only on the diff) must stay exit-0, and the
# bounded membership-protocol model must explore its state space to a
# fixpoint with zero invariant violations while a deliberately broken
# variant is caught on its invariant — the checker proves itself able
# to fail before its clean pass counts for anything.
r = subprocess.run(
    [sys.executable, os.path.join(_here, "run_lint.py"), "--changed"],
    capture_output=True, text=True, timeout=300)
assert r.returncode == 0, f"incremental lint red:\n{r.stdout}{r.stderr}"
r = subprocess.run(
    [sys.executable, os.path.join(_here, "proto_check.py"),
     "--ranks", "3", "--deaths", "1", "--joins", "1", "--nos", "1",
     "--max-epochs", "2", "--json"],
    capture_output=True, text=True, timeout=300)
assert r.returncode == 0, f"proto-check red:\n{r.stdout}{r.stderr}"
_pcl = _json.loads(r.stdout)
assert _pcl["complete"] and not _pcl["violations"] and _pcl["states"] > 0, _pcl
r = subprocess.run(
    [sys.executable, os.path.join(_here, "proto_check.py"),
     "--broken", "nonatomic_commit"],
    capture_output=True, text=True, timeout=300)
assert r.returncode == 1 and "VIOLATION I4" in r.stdout, \
    f"broken protocol variant not caught:\n{r.stdout}{r.stderr}"
print(f"[13] protocol verification ok: incremental lint clean, model "
      f"fixpoint {_pcl['states']} states / {_pcl['transitions']} "
      f"transitions with zero violations, broken variant caught on I4")
# --- 14. serving fleet under churn + injected faults --------------------
# The networked serving day: N followers over one shared stage, a
# follower killed, another drained and readmitted, the killed rank
# rejoining as a new incarnation — all during concurrent publishes and
# with faults injected at the three serve sites (lost request, torn
# stage fetch, dropped drain command). The gate mirrors the committed
# SOAK_SERVEFLEET.json headline: zero client-visible failures, bitwise
# parity on every served version, drain honored, and a single disk
# fetch per publish independent of fleet size.
r = subprocess.run(
    [sys.executable, os.path.join(_here, "chaos_probe.py"),
     "--serve-fleet", "--json"],
    capture_output=True, text=True, timeout=600)
assert r.returncode == 0, f"serve-fleet soak red:\n{r.stdout}{r.stderr}"
_sf = _json.loads(r.stdout.strip().splitlines()[-1])
assert _sf["ok"] and _sf["soak"]["ok"], _sf
assert all(n > 0 for n in _sf["faults_fired"].values()), _sf
_sk = _sf["soak"]
assert not _sk["client_errors"] and _sk["live_parity"]["mismatched"] == 0, _sk
assert _sk["drained_rank_served_during_window"] == 0, _sk
_committed = os.path.join(_here, os.pardir, "SOAK_SERVEFLEET.json")
if os.path.exists(_committed):
    with open(_committed) as _f:  # pbox-lint: disable=IO004
        _ref = _json.load(_f)
    assert _ref["ok"] and not _ref["client_errors"], \
        "committed SOAK_SERVEFLEET.json records a red run"
print(f"[14] serve fleet ok: {_sk['fleet']} followers, "
      f"{_sk['requests']} requests / 0 failures under kill+drain+rejoin, "
      f"{_sk['hedges']} hedge(s), faults fired {_sf['faults_fired']}, "
      f"live parity {_sk['live_parity']['checked']}/0 mismatched, "
      f"{_sk['stage_fetches']} stage fetches for {_sk['passes']} passes")
# --- 15. mesh-sharded scoring: device-tier A/B + crash probe ------------
# The --device-tier A/B runs the SAME serving day host-only and with the
# device-resident hot tier on, requiring bitwise parity inside each leg
# AND between them (the off ablation is bitwise-identical), plus the
# lookup microbench at hit rate >= 0.9; SOAK_SERVESHARD.json is the
# committed record of the full-size gate and must itself be green. The
# --serve-shard probe then crashes a follower mid-tier-build
# (serve.tier_build) and requires the old version to keep serving
# bitwise with no partial tier, the healed retry landing bitwise.
_ss_path = os.path.join(os.path.dirname(_here), "SOAK_SERVESHARD.json")
assert os.path.exists(_ss_path), "SOAK_SERVESHARD.json missing from the repo"
with open(_ss_path) as _f:
    _ss = _json.load(_f)
assert _ss["ok"] and _ss["ablation_bitwise_identical"] and _ss["tier_used"], _ss
assert _ss["lookup_bench"]["bitwise_equal"], _ss["lookup_bench"]
assert _ss["lookup_bench"]["hit_rate"] >= 0.9, _ss["lookup_bench"]
assert (
    _ss["lookup_bench"]["tier_keys_per_s"] >= _ss["lookup_bench"]["host_keys_per_s"]
), _ss["lookup_bench"]
with tempfile.TemporaryDirectory() as ab_dir:
    _ab = serve_soak.run_device_tier_ab(
        ab_dir, passes=3, rows=200, qps=25.0, probe_n=16,
        bench_rows=120_000, bench_hot=16_384, bench_batch=4096, bench_iters=8,
    )
assert _ab["host_leg"]["ok"] and _ab["tier_leg"]["ok"], _ab
assert _ab["ablation_bitwise_identical"] and _ab["tier_used"], _ab
assert _ab["lookup_bench"]["bitwise_equal"], _ab["lookup_bench"]
# the short-form bench is too small to re-gate throughput; the committed
# full-size artifact above carries that claim
r = subprocess.run(
    [sys.executable, os.path.join(_here, "chaos_probe.py"),
     "--serve-shard", "--json"],
    capture_output=True, text=True, timeout=600)
assert r.returncode == 0, f"serve-shard probe red:\n{r.stdout}{r.stderr}"
_sp = _json.loads(r.stdout.strip().splitlines()[-1])
assert _sp["ok"] and _sp["old_version_held_bitwise"], _sp
assert _sp["tier_build_faults_fired"] == 1 and _sp["parity_after_heal_bitwise"], _sp
print(f"[15] mesh-sharded scoring ok: A/B ablation bitwise over "
      f"{_ab['passes']} passes (tier {_ab['tier_leg']['device_tier']['hits']} "
      f"hit(s)), committed bench {_ss['lookup_bench']['speedup']}x at hit rate "
      f"{_ss['lookup_bench']['hit_rate']} on {_ss['platform']}, crash probe "
      f"held old version bitwise and healed to tier of "
      f"{_sp['final_tier_rows']} row(s)")
# --- 16. streaming micro-passes: freshness SLO + crash sweep ------------
# The streaming day: a tail-following supervisor cuts micro-passes on a
# time budget, publishes minute-level deltas through the watermark, and
# folds the chain hourly so follower catch-up stays O(tail). The gate
# mirrors the committed SOAK_STREAM.json headline — the supervisor is
# KILLED in both cut_publish crash windows mid-soak and the restart
# recovers exactly-once (one spool replay, one retrain skip, digest
# bitwise vs an uninterrupted twin) while a follower serves concurrently.
# The freshness SLO is then gated through obs_report over the run's own
# metric series (the --json verdicts are asserted PASS explicitly:
# NODATA must not slip through the exit code), and the --stream probe
# must fire ALL THREE streaming fault sites.
_st_path = os.path.join(os.path.dirname(_here), "SOAK_STREAM.json")
assert os.path.exists(_st_path), "SOAK_STREAM.json missing from the repo"
with open(_st_path) as _f:
    _sm = _json.load(_f)
assert _sm["ok"] and _sm["bitwise"] and len(_sm["kills"]) == 2, _sm
assert _sm["recovery"] == {"replays": 1, "replays_skipped": 1}, _sm
assert _sm["freshness_s"]["count"] > 0, _sm
assert _sm["catchup"]["fresh_follower_applies"] == _sm["catchup"]["bound"], _sm
with tempfile.TemporaryDirectory() as st_dir:
    _stk = serve_soak.run_stream_soak(
        st_dir, cuts=6, rows=100, compact_every=3, qps=20.0, probe_n=16)
    assert _stk["ok"] and _stk["bitwise"], _stk
    r = subprocess.run(
        [sys.executable, os.path.join(_here, "obs_report.py"),
         os.path.join(_stk["ckpt_root"], "obs"),
         "--slo", "serve.freshness_s:p99<=60", "--json"],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, f"freshness SLO gate red:\n{r.stdout}{r.stderr}"
    _slo = _json.loads(r.stdout.strip().splitlines()[-1])["slo"]
    assert _slo and all(v["verdict"] == "PASS" for v in _slo), _slo
r = subprocess.run(
    [sys.executable, os.path.join(_here, "chaos_probe.py"),
     "--stream", "--json"],
    capture_output=True, text=True, timeout=600)
assert r.returncode == 0, f"stream probe red:\n{r.stdout}{r.stderr}"
_stp = _json.loads(r.stdout.strip().splitlines()[-1])
assert _stp["ok"], _stp
assert set(_stp["sites_fired"]) == {
    "stream.tail_read", "stream.cut_publish", "ckpt.compact"}, _stp
assert all(n >= 1 for n in _stp["sites_fired"].values()), _stp
print(f"[16] streaming plane ok: {_stk['cuts']} cuts with 2 kills "
      f"recovered exactly-once (bitwise), compact covers "
      f"{_stk['chain']['compact_covers']} of {_stk['chain']['chain_len']} "
      f"links, catch-up {_stk['catchup']['fresh_follower_applies']} "
      f"applies (bound {_stk['catchup']['bound']}), freshness p99 "
      f"{_slo[0]['value']:.2f}s <= 60s over {_stk['freshness_s']['count']} "
      f"commits, probe fired {_stp['sites_fired']}")
print("VERIFY DRIVE PASS")
