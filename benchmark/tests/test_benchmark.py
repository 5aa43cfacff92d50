"""Tests of the benchmark itself; run by hand and in rehearsal on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, control, gen, program, run as bench_run, trace_reduce
from benchmark.tests import toy
from benchmark.work import dcn as work_dcn, deepfm as work_deepfm, sparse as work_sparse

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = bench_run.load_json("BENCHMARK.json")
KINDS = [("dcn_multislot", "pass_fill.dcn"), ("deepfm_criteo", "pass_fill.deepfm")]


# ---- trace reduction ---------------------------------------------------------

def test_union_self_times_and_gaps_on_a_hand_made_trace():
    ops = [("z", 0.5, 0.6), ("while", 1.0, 5.0), ("a", 1.0, 2.0), ("b", 2.5, 4.0), ("c", 6.0, 7.0),
           ("c", 7.5, 7.6)]
    trace = {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [("jit_superstep(1)", 1.0, 5.0), ("jit_superstep(1)", 6.0, 7.0),
                                 ("jit_superstep(1)", 7.5, 7.6)]}},  # cut short by the trace's end
        "spans": [("traced", 0.0, 10.0), ("train_pass", 0.0, 10.0), ("pause", 5.2, 5.9)]}
    assert trace_reduce.union([(s, e) for _, s, e in ops], 0.7, 7.2) == [(1.0, 5.0), (6.0, 7.0)]
    assert trace_reduce.self_times(ops, 0.7, 7.2) == pytest.approx(
        {"while": 1.5, "a": 1.0, "b": 1.5, "c": 1.0})
    red = trace_reduce.reduce(trace, module="superstep")
    # one whole period: the first program and the gap behind it, to the second's start
    assert red["n_modules"] == 1 and red["window"] == (1.0, 6.0)
    assert red["busy_s"] == pytest.approx(4.0) and red["window_s"] == pytest.approx(5.0)
    assert red["idle_gaps"] == [["pause", pytest.approx(1.0)]]
    whole = trace_reduce.reduce(trace)  # the traced span: lead-in and tail are idle too
    assert whole["window_s"] == pytest.approx(10.0) and whole["busy_s"] == pytest.approx(5.2)
    assert whole["idle_gaps"][0] == ["train_pass", pytest.approx(2.4)]


def test_reduction_of_the_recorded_chip_trace():
    """small.xplane.pb: four runs of a program named superstep on a v5e with a
    20 ms sleep after each, recorded through the harness's Tracer (PR 25)."""
    trace = trace_reduce.load(os.path.join(HERE, "data", "small.xplane.pb"))
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert {"traced", "train_pass", "dispatch", "pause"} <= {s[0] for s in trace["spans"]}
    red = trace_reduce.reduce(trace, module="superstep")
    # the device's clock runs a millisecond ahead of the host's here, so the
    # first run starts before the traced span and is not counted as complete
    assert len(next(iter(trace["devices"].values()))["modules"]) == 4
    mods = red["modules"]
    assert len(mods) == 3 and red["n_modules"] == 2  # two whole periods
    assert red["window_s"] == pytest.approx(mods[-1][0] - mods[0][0])
    # the device ran only inside the programs, and nearly all of each
    inside = sum(e - s for s, e in mods[:-1])
    assert 0.9 * inside <= red["busy_s"] <= inside * (1 + 1e-9)
    # two gaps of a 20 ms sleep each, named by the span that covered them
    long_gaps = [g for g in red["idle_gaps"] if g[1] > 0.015]
    assert [g[0] for g in long_gaps] == ["pause", "pause"]
    assert all(0.02 < g[1] < 0.025 for g in long_gaps)
    assert sum(v for _, v in red["device_ops"]) <= red["busy_s"] * (1 + 1e-6)


# ---- work functions ----------------------------------------------------------

def test_flops_and_bytes_against_hand_counts():
    dcn = bench_run.load_json("benchmark", "configs", "dcn_multislot.json")
    d = 108 * 67
    fwd = 2 * (d * 512 + 512 * 256 + 256 * 128) + 3 * 5 * d + 2 * (128 + d)
    assert d == 7236 and work_dcn.flops_per_sample(dcn) == 3 * fwd
    fm = bench_run.load_json("benchmark", "configs", "deepfm_criteo.json")
    d = 39 * 19
    fwd = 2 * (d * 512 + 512 * 256 + 256 * 128) + 2 * 128 + (4 * 39 * 16 + 2 * 16) + 39
    assert d == 741 and work_deepfm.flops_per_sample(fm) == 3 * fwd
    # 1000 distinct rows: read 67 columns, read and write 69, 4 bytes each
    assert work_sparse.bytes_per_step(dcn, 1000) == 4 * 1000 * (67 + 2 * 69)
    assert work_sparse.bytes_per_step(fm, 1000) == 4 * 1000 * (19 + 2 * 21)


# ---- generator ---------------------------------------------------------------

def test_generator_draw_row_count_and_line_format():
    mix = bench_run.load_json("benchmark", "traffic", "pass_fill.dcn.json")
    mix = {**mix, "train_records": 8192, "fill_records": 4096, "n_files": 3}
    S, seed = 45, 2**31 + 7
    sizes = gen.field_sizes(mix, S)
    assert list(sizes[39:]) == list(sizes[:6])  # the fields repeat over the slots
    with tempfile.TemporaryDirectory() as d:
        files, keys, labels = gen.make_pass(d, mix, S, seed)
        lines = [ln for f in files for ln in open(f).read().splitlines()]
    assert len(lines) == len(keys) == 12288
    for ln, row, lab in zip(lines[:50], keys[:50], labels[:50]):
        assert ln == f"1 {lab}.0 1 " + " 1 ".join(map(str, row.tolist()))
    assert keys.min() >= gen.KEY_BASE and keys.max() < 10**13
    train, fill = keys[:8192], keys[8192:]
    # every fill key occurs once: rows = records x slots (within 1%; here exactly)
    assert abs(len(np.unique(fill)) - fill.size) <= 0.01 * fill.size
    assert fill.min() >= gen.FILL_BASE > train.max()
    # each slot draws from a key space of its own, as many values as its field has
    lo = gen.KEY_BASE + np.arange(S, dtype=np.uint64) * np.uint64(gen.FIELD_SPAN)
    assert np.all(train >= lo) and np.all(train < lo + np.uint64(gen.FIELD_SPAN))
    per_slot = np.array([len(np.unique(train[:, f])) for f in range(S)])
    assert np.all(per_slot <= sizes)
    assert per_slot[sizes <= 14].tolist() == sizes[sizes <= 14].tolist()
    # exponent 1 over N values: P(rank 1) = ln 2 / ln(N + 1); the expected
    # number of distinct values in n draws follows from the same law
    for f in (13, 14, 20):  # 39,884,406, 39,043 and 1,543 values
        n_f, n = float(sizes[f]), 8192
        ranks = gen.draw_ranks(np.random.default_rng(5), n, sizes[f:f + 1], 1.0)[:, 0]
        assert ranks.min() >= 1 and ranks.max() <= n_f
        assert np.mean(ranks == 1) == pytest.approx(np.log(2) / np.log(n_f + 1), rel=0.1)
        k = np.arange(1, int(n_f) + 1, dtype=np.float64) if n_f < 1e6 else None
        if k is not None:
            p = np.log1p(1 / k) / np.log(n_f + 1)
            want = np.sum(1 - (1 - p) ** n)
            assert len(np.unique(ranks)) == pytest.approx(want, rel=0.05)
    # another exponent is the same law: P(rank 1) = (2^a - 1) / ((N+1)^a - 1), a = 1 - s
    ranks = gen.draw_ranks(np.random.default_rng(6), 20000, np.array([1000]), 1.2)[:, 0]
    assert np.mean(ranks == 1) == pytest.approx((2 ** -0.2 - 1) / (1001 ** -0.2 - 1), rel=0.05)
    again = gen.make_pass(None, mix, S, seed)
    assert np.array_equal(again[1], keys) and again[0] == [None] * 3
    assert not np.array_equal(gen.make_pass(None, mix, S, seed + 1)[1], keys)


def test_generated_files_parse_through_load_into_memory():
    c = toy.cell()
    with tempfile.TemporaryDirectory() as d:
        files, keys, labels = gen.make_pass(d, c["mix"], c["cfg"]["num_slots"], c["seed"])
        _, ds = program.make_dataset(c["cfg"], c["seed"])
        ds.set_filelist(files)
        ds.load_into_memory()
        ds.begin_pass()
    assert ds.store is not None, "native tier not loaded"
    assert np.array_equal(ds.ws.sorted_keys, np.unique(keys))
    idx = np.stack(list(ds.batch_indices(4)))
    assert np.array_equal(idx.ravel(), np.arange(idx.size))  # file order, no shuffle
    assert np.array_equal(np.asarray(ds.store.u64_values).reshape(keys.shape)[:256], keys[:256])


# ---- the command -------------------------------------------------------------

def test_command_refuses_without_a_tpu_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        SPEC["command"] + ["--workload", SPEC["workloads"][0]["name"], "--seed", "1",
                           "--seconds", "1", "--trace", "0"],
        cwd=bench_run.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "not started" in p.stderr


def test_every_cell_resolves_to_files_that_exist():
    for w in SPEC["workloads"]:
        cell = bench_run.resolve(SPEC, w["name"])
        assert cell["limits"] and all(lim >= 0 for lim in cell["limits"].values())
        assert os.path.exists(os.path.join(
            bench_run.HERE, "drivers", cell["mix"]["driver"] + ".py"))
    for m in SPEC["per_layer"]:
        assert os.path.exists(os.path.join(bench_run.HERE, "layer_metrics", m["name"] + ".py"))


# ---- correct: the comparison, its control and the planted faults -------------

@pytest.mark.parametrize("config,traffic", KINDS)
def test_program_agrees_with_reference_and_bf16_control_does_not(config, traffic):
    cell = toy.cell(config, traffic)
    result = bench_run.run_cell(cell, SPEC, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["checks"]["counter_gap"][0] == 0
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    ok = []
    for seed in (11, 12, 13):
        ctl = control.readings(cell, seed)
        ok.append(ctl["bf16"]["correct"])
        assert not ctl["half_batch"]["correct"] and ctl["half_batch"]["fails"]
    assert not any(ok), "the reference in bfloat16 passed the comparison"


def _broken(fault: str):
    """The program's step builder with a fault planted under the timed path."""
    from paddlebox_tpu.train import resident_step

    sound = resident_step.make_train_step

    def make(*args, **kw):
        step = sound(*args, **kw)

        def faulty(state, batch):
            if fault == "half_batch":  # second half left out, mean over the rest
                n = batch["labels"].shape[0]
                w = (jnp.arange(n) < n // 2).astype(jnp.float32)
                return step(state, {**batch, "ins_weight": w})
            new, metrics = step(state, batch)  # "state_unchanged"
            return state._replace(auc=new.auc, step=new.step), metrics

        return faulty

    return make


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_run_over_a_broken_step_is_not_correct(monkeypatch, fault):
    from paddlebox_tpu.train import resident_step

    monkeypatch.setattr(resident_step, "make_train_step", _broken(fault))
    result = bench_run.run_cell(toy.cell(seed=77), SPEC, require_tpu=False)
    assert not result["correct"], result["checks"]
    failing = {k for k, (v, lim) in result["checks"].items() if not v <= lim}
    assert "counter_gap" in failing
    if fault == "state_unchanged":
        assert result["checks"]["sparse_delta_gap"][0] == pytest.approx(1.0)


def test_a_compilation_inside_the_window_fails_the_run(monkeypatch):
    from benchmark import spans

    monkeypatch.setattr(spans.Recorder, "compiles_between",
                        lambda self, t0, t1: [(t0, 0.25, "jit(anything)")])
    result = bench_run.run_cell(toy.cell(seed=78), SPEC, require_tpu=False)
    assert not result["correct"] and result["failed"] == result["attempted"] > 0


def test_the_chip_readings_of_control_and_fault_fail_the_cells_own_limits():
    """control_readings.jsonl: what ``benchmark.control``'s comparison read on
    a v5e at each cell's own widths and batches (PR 25, fourteen seeds a cell),
    the reference in bfloat16 and with half of every batch left out in the
    program's place."""
    seen = set()
    for ln in open(os.path.join(HERE, "data", "control_readings.jsonl")):
        r = json.loads(ln)
        limits = bench_run.load_json("benchmark", "limits", r["workload"] + ".json")
        correct, checks = compare.judge(r["values"], limits)
        assert not correct, (r, checks)
        # the two steady numbers each catch both, on every seed
        assert all(checks[k][0] > 10 * checks[k][1] for k in ("early_loss_gap", "counter_gap")), r
        seen.add((r["workload"], r["control"]))
    assert seen == {(w["name"], c) for w in SPEC["workloads"] for c in ("bf16", "half_batch")}
