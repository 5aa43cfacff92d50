"""The readers that came with the program's named scopes, on a recorded pair
from the chip: ``scoped.xplane.pb``, the toy cell (6 slots x embedx 8, batch
64) traced through the harness on a v5e, and ``scoped.scopes.json``, the
program registry's dump of that process (PR 26).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import run as bench_run, scope_times, trace_reduce
from benchmark.tests import toy
from benchmark.work import sparse as work_sparse, sparse_split

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = bench_run.load_json("BENCHMARK.json")
NEW = ["batch_assembly_device_ms", "pull_device_ms", "model_device_ms", "push_device_ms",
       "unscoped_device_pct", "pull_hbm_pct", "push_hbm_pct", "small_programs_per_superstep",
       "begin_pass_pull_s", "prepare_resolve_s"]
DISTINCT_ROWS = 250.125  # the recorded run's distinct_rows_per_step ("bench: run" line)


@pytest.fixture()
def run(monkeypatch):
    """What ``run_cell`` hands a reader, rebuilt from the recorded pair."""
    with open(os.path.join(HERE, "data", "scoped.scopes.json")) as f:
        programs = json.load(f)
    (name,) = [n for n in programs if n.startswith(scope_times.PROGRAM)]
    monkeypatch.setattr(scope_times, "program", lambda: dict(programs[name], name=name))
    trace = trace_reduce.load(os.path.join(HERE, "data", "scoped.xplane.pb"))
    cell = toy.cell(trace=True)
    return {
        "trace": trace, "reduced": trace_reduce.reduce(trace, module="superstep"),
        "scan_batches": 8, "cell": cell, "distinct_rows_per_step": DISTINCT_ROWS,
        "peaks": bench_run.load_json("benchmark", "peaks.json")["TPU v5 lite"],
        # the spans' totals live in the process that ran: two of the recorded run's
        "program_spans": {"boundary.pull": {"count": 1, "seconds": 0.0165},
                          "resident.resolve_rows": {"count": 1, "seconds": 0.0014}},
    }


def test_every_new_reader_returns_a_number_on_the_recorded_toy(run, capsys):
    values = {n: bench_run.read_layer_metric(n, run) for n in NEW}
    assert all(isinstance(v, float) for v in values.values()), values
    step_ms = bench_run.read_layer_metric("step_device_ms", run)
    parts = [values[n] for n in NEW[:4]] + [values["unscoped_device_pct"] / 100 * step_ms]
    assert all(p >= 0 for p in parts) and sum(parts) == pytest.approx(step_ms, rel=0.02)
    assert values["batch_assembly_device_ms"] > values["model_device_ms"] > 0
    assert 0 < values["unscoped_device_pct"] < 10
    assert 0 < values["pull_hbm_pct"] < 100 and 0 < values["push_hbm_pct"] < 100
    # one line of scopes, printed once however many readers ask
    out = capsys.readouterr().out
    assert out.count("bench: device_scopes ") == 1
    scopes = json.loads(out.split("bench: device_scopes ", 1)[1].splitlines()[0])
    assert scopes["program"]["name"] == "superstep/train/8x64"
    assert scopes["ms_per_step"]["build_batch/ragged_rows"] > 0
    assert "pull/table_gather" in scopes["ms_per_step"]  # wherever XLA left the one gather
    assert "push/table_gather" not in scopes["ms_per_step"]


def test_small_programs_are_the_toys_slices_between_supersteps(run):
    """A superstep's 8 batches x 4 metrics are sliced one by one, two small
    programs a slice; every second superstep here ends a train_pass call,
    whose tail and the next call's opening add theirs."""
    mods = next(iter(run["trace"]["devices"].values()))["modules"]
    lo, hi = run["reduced"]["window"]
    small = [n for n, s, _ in mods if lo <= s < hi and "superstep" not in n]
    per = bench_run.read_layer_metric("small_programs_per_superstep", run)
    assert per == len(small) / run["reduced"]["n_modules"]
    assert 8 * 4 * 2 <= per < 8 * 4 * 2 + 16


def test_without_the_programs_map_the_scope_readers_return_nothing(run, monkeypatch, capsys):
    """A commit before the scopes keeps no registry and no totals: every
    reader that needs them returns None, and no line is printed."""
    monkeypatch.setattr(scope_times, "program", lambda: None)
    run["program_spans"] = None
    for n in NEW:
        value = bench_run.read_layer_metric(n, run)
        assert (value is None) == (n != "small_programs_per_superstep"), (n, value)
    assert "bench: device_scopes" not in capsys.readouterr().out


def test_pull_and_push_bytes_add_up_to_the_steps():
    for kind in ("dcn_multislot", "deepfm_criteo"):
        cfg = bench_run.load_json("benchmark", "configs", kind + ".json")
        assert (sparse_split.pull_bytes(cfg, 1000) + sparse_split.push_bytes(cfg, 1000)
                == work_sparse.bytes_per_step(cfg, 1000))


def test_every_new_metric_is_declared_for_both_cells_with_a_reader():
    cells = [w["name"] for w in SPEC["workloads"]]
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    for n in NEW:
        assert declared[n]["workloads"] == cells
        assert os.path.exists(os.path.join(bench_run.HERE, "layer_metrics", n + ".py"))
    assert [m["name"] for m in SPEC["per_layer"]][-len(NEW):] == NEW  # appended, in this order


def test_obs_report_reads_the_same_pair_and_names_the_gaps_by_the_programs_spans():
    """``tools/obs_report.py --device-trace``: the operator's view of a kept
    trace, over the same reduction; the harness's ``load`` drops the ``pbx:``
    spans this one keeps."""
    import importlib.util

    path = os.path.join(bench_run.ROOT, "tools", "obs_report.py")
    mod_spec = importlib.util.spec_from_file_location("obs_report", path)
    obs_report = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(obs_report)
    rep = obs_report.device_trace_report(os.path.join(HERE, "data", "scoped.xplane.pb"))
    assert rep["programs"] == {"superstep/train/8x64": 2861}
    assert sum(rep["scope_s"].values()) == pytest.approx(rep["busy_s"], rel=1e-6)
    assert next(iter(rep["scope_s"])) == "build_batch/ragged_rows"
    assert {g[0] for g in rep["idle_gaps"]} <= {
        "train_pass.tail", "train_pass.open", "superstep_consume", "superstep_dispatch",
        "resident_prepare", "train_pass", "traced"}
    assert "superstep_consume" in {g[0] for g in rep["idle_gaps"]}
