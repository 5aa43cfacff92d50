"""The readers that came with the program's account of what its scopes leave
out (PR 37): on a trace written by hand, and on the recorded pair from the
chip (``scoped.xplane.pb`` / ``scoped.scopes.json``, PR 26) with an account
made up for its unscoped instructions (a dump of that build has none).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import run as bench_run, scope_times, trace_reduce, unscoped_times
from benchmark.tests import toy

HERE = os.path.dirname(os.path.abspath(__file__))
SPEC = bench_run.load_json("BENCHMARK.json")
KINDS = ["unscoped_stack_device_ms", "unscoped_cast_device_ms", "unscoped_copy_device_ms",
         "unscoped_other_device_ms"]
NEW = KINDS + ["superstep_peak_memory_gb"]


def _op(name, start_ms, ms, opcode="fusion"):
    return (f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)", 1e-3 * start_ms, 1e-3 * (start_ms + ms))


def _written_by_hand():
    """Four executions of a superstep of 2 steps, 10 ms apart; each runs a
    loop (1.5 ms of its own) around one instruction of every kind, one in a
    scope of a group, one in a scope of no group and one the account does
    not list; between two executions another program's operation."""
    ops, modules = [], []
    for k in range(4):
        t = 10.0 * k + 1.0
        modules.append(("jit_superstep(1)", 1e-3 * t, 1e-3 * (t + 8.0)))
        ops += [_op("while.1", t, 8.0, "while"),
                _op("dynamic-slice_bitcast_fusion.1", t + 0.5, 2.0), _op("convert.1", t + 2.5, 0.5, "convert"),
                _op("copy.1", t + 3.0, 1.0, "copy"), _op("copy.2", t + 4.0, 0.25, "copy"),
                _op("reduce-window_fusion.1", t + 4.25, 0.75), _op("fusion.9", t + 5.0, 1.5),
                _op("fusion.10", t + 6.5, 0.25), _op("unlisted.1", t + 6.75, 0.25)]
        modules.append(("jit_mean", 1e-3 * (t + 8.5), 1e-3 * (t + 8.75)))
        ops.append(_op("fusion.1", t + 8.5, 0.25))  # another program's: not this one's fusion.1
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
             "spans": [("traced", 0.0, 0.041)]}
    prog = {
        "name": "superstep/train/2x8", "fun_name": "superstep", "instructions": 9,
        "scopes": {"while.1": "", "dynamic-slice_bitcast_fusion.1": "", "convert.1": "", "copy.1": "",
                   "copy.2": "", "reduce-window_fusion.1": "", "fusion.9": "model/mla/scores",
                   "fusion.10": "somewhere/else", "unlisted.1": "", "fusion.1": "pull/expand"},
        "unscoped": {"dynamic-slice_bitcast_fusion.1": ["stack", "model/moe/experts"],
                     "convert.1": ["cast", "model/moe/experts"], "copy.1": ["copy", "model/mla/scores"],
                     "copy.2": ["copy", ""],
                     "reduce-window_fusion.1": ["other", "build_batch/ragged_rows"]},
        "memory": {"peak_bytes": 9_586_000_000, "temp_bytes": 8_916_000_000},
    }
    return trace, prog


def _run(trace, scan_batches):
    return {"trace": trace, "reduced": trace_reduce.reduce(trace, module="superstep"),
            "scan_batches": scan_batches, "cell": toy.cell(trace=True),
            "peaks": bench_run.load_json("benchmark", "peaks.json")["TPU v5 lite"]}


def test_the_four_kinds_are_what_the_hand_written_trace_holds(monkeypatch, capsys):
    trace, prog = _written_by_hand()
    monkeypatch.setattr(scope_times, "program", lambda: dict(prog))
    run = _run(trace, 2)
    assert run["reduced"]["n_modules"] == 2  # whole periods of the second and third executions
    got = {n: bench_run.read_layer_metric(n, run) for n in NEW}
    # ms a step, two steps an execution: the loop's own 1.5 ms, the unlisted
    # instruction, the scope of no group and the other program's all count as other
    assert got == pytest.approx({
        "unscoped_stack_device_ms": 1.0, "unscoped_cast_device_ms": 0.25,
        "unscoped_copy_device_ms": 0.625,
        "unscoped_other_device_ms": 0.375 + 0.75 + 0.125 + 0.125 + 0.125,
        "superstep_peak_memory_gb": 9.586}, abs=1e-9)
    st = scope_times.of(run)
    assert sum(got[n] for n in KINDS) == pytest.approx(st["unscoped_ms"], abs=1e-9)
    assert st["groups"]["model"] == pytest.approx(0.75, abs=1e-9)
    out = capsys.readouterr().out
    assert out.count("bench: unscoped_ops ") == 1  # once, however many readers ask
    line = json.loads(out.split("bench: unscoped_ops ", 1)[1].splitlines()[0])
    assert line["steps"] == 4 and line["unscoped_ms"] == pytest.approx(st["unscoped_ms"], abs=1e-4)
    assert line["by_kind"] == {"stack": 1.0, "cast": 0.25, "copy": 0.625, "other": 1.5}
    assert list(line["labels"].items())[:3] == [
        ("stack|model/moe/experts", 1.0), ("other|", 0.75 + 0.125), ("copy|model/mla/scores", 0.5)]
    assert line["labels"]["other|" + scope_times.OTHER] == 0.125
    assert line["labels"]["other|somewhere/else"] == 0.125 and line["labels"]["copy|"] == 0.125
    assert line["instructions"][0] == [1.0, "stack", "model/moe/experts",
                                       "%dynamic-slice_bitcast_fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)"]
    assert [i[1] for i in line["instructions"]] == ["stack", "copy", "other", "cast", "copy"]


@pytest.fixture()
def recorded(monkeypatch):
    """The recorded pair, its unscoped instructions given a kind by turns."""
    with open(os.path.join(HERE, "data", "scoped.scopes.json")) as f:
        programs = json.load(f)
    (name,) = [n for n in programs if n.startswith(scope_times.PROGRAM)]
    prog = dict(programs[name], name=name)
    bare = [n for n, s in prog["scopes"].items() if not s]
    prog["unscoped"] = {n: [unscoped_times.KINDS[i % 4], ("pull/expand", "model", "")[i % 3]]
                        for i, n in enumerate(bare) if i % 7}  # every seventh: not listed
    monkeypatch.setattr(scope_times, "program", lambda: dict(prog))
    return _run(trace_reduce.load(os.path.join(HERE, "data", "scoped.xplane.pb")), 8), prog


def test_the_four_kinds_add_up_to_the_unscoped_time_of_the_recorded_toy(recorded, capsys):
    run, _ = recorded
    values = [bench_run.read_layer_metric(n, run) for n in KINDS]
    st = scope_times.of(run)
    assert all(isinstance(v, float) and v > 0 for v in values), values
    assert sum(values) == pytest.approx(st["unscoped_ms"], abs=1e-9)
    step_ms = bench_run.read_layer_metric("step_device_ms", run)
    assert sum(values) == pytest.approx(
        bench_run.read_layer_metric("unscoped_device_pct", run) / 100 * st["total_ms"], abs=1e-9)
    assert sum(values) + sum(st["groups"].values()) == pytest.approx(step_ms, rel=0.02)
    assert bench_run.read_layer_metric("superstep_peak_memory_gb", run) is None  # it recorded none
    line = json.loads(capsys.readouterr().out.split("bench: unscoped_ops ", 1)[1].splitlines()[0])
    assert len(line["labels"]) == 12 and len(line["instructions"]) == 8
    assert all(len(i[3]) <= 120 and i[3].startswith("%") for i in line["instructions"])


def test_a_program_without_the_account_gives_nothing_and_changes_nothing(recorded, monkeypatch, capsys):
    """The parent commit under this PR's benchmark files: the five readers
    give no line, and what the scopes' readers read is what they read."""
    run, prog = recorded
    with_account = scope_times.of(dict(run))
    old = {k: v for k, v in prog.items() if k not in ("unscoped", "memory")}
    monkeypatch.setattr(scope_times, "program", lambda: dict(old))
    capsys.readouterr()
    bare = {k: v for k, v in run.items() if k not in ("scope_times", "unscoped_times")}
    assert [bench_run.read_layer_metric(n, bare) for n in NEW] == [None] * 5
    assert "bench: unscoped_ops" not in capsys.readouterr().out
    assert scope_times.of(bare) == with_account
    monkeypatch.setattr(scope_times, "program", lambda: None)  # a commit before the scopes
    assert [bench_run.read_layer_metric(n, dict(bare, scope_times=None)) for n in NEW] == [None] * 5


def test_every_new_metric_is_declared_for_all_five_cells_with_a_reader():
    cells = [w["name"] for w in SPEC["workloads"]]
    declared = {m["name"]: m for m in SPEC["per_layer"]}
    assert len(cells) >= 5
    for n in NEW:
        assert set(declared[n]["workloads"]) >= set(cells[:5]), n
        assert declared[n]["moves"] == "train_samples_per_s" and declared[n]["better"] == "lower"
        assert os.path.exists(os.path.join(bench_run.HERE, "layer_metrics", n + ".py"))
    assert {declared[n]["layer"] for n in KINDS} == {"Step body"}
    assert {declared[n]["source"] for n in KINDS} == {"device_trace"} and declared[NEW[-1]]["unit"] == "GB"
    assert (declared[NEW[-1]]["layer"], declared[NEW[-1]]["source"]) == ("Device", "program_counter")


def test_obs_report_prints_the_same_account_under_its_scope_table(tmp_path, capsys):
    import importlib.util
    import shutil

    path = os.path.join(bench_run.ROOT, "tools", "obs_report.py")
    mod_spec = importlib.util.spec_from_file_location("obs_report", path)
    obs_report = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(obs_report)
    trace_path = str(tmp_path / "kept.xplane.pb")
    shutil.copy(os.path.join(HERE, "data", "scoped.xplane.pb"), trace_path)
    with open(os.path.join(HERE, "data", "scoped.scopes.json")) as f:
        programs = json.load(f)
    # a dump of a build before the account: the report is what it was
    assert obs_report.device_trace_report(
        os.path.join(HERE, "data", "scoped.xplane.pb"))["unscoped_s"] is None
    (prog,) = programs.values()
    bare = [n for n, s in prog["scopes"].items() if not s]
    prog["unscoped"] = {n: [unscoped_times.KINDS[i % 4], "model"] for i, n in enumerate(bare)}
    prog["memory"] = {"peak_bytes": 2_000_000_000, "temp_bytes": 1_500_000_000}
    with open(str(tmp_path / "kept.scopes.json"), "w") as f:
        json.dump(programs, f)
    rep = obs_report.device_trace_report(trace_path)
    kinds = rep["unscoped_s"]["kinds"]
    assert list(kinds) == list(unscoped_times.KINDS) and all(v > 0 for v in kinds.values())
    # the scope table's "(no scope)" row is the account's sum
    assert sum(kinds.values()) == pytest.approx(rep["scope_s"][""], rel=1e-9)
    assert sum(rep["unscoped_s"]["labels"].values()) == pytest.approx(sum(kinds.values()), rel=1e-9)
    assert obs_report.main(["--device-trace", trace_path]) == 0
    out = capsys.readouterr().out
    assert "outside every named scope, by kind:" in out and "stack|model" in out
    assert "memory of superstep/train/8x64: peak_bytes 2.000 GB, temp_bytes 1.500 GB" in out
    assert out.index("(no scope)") < out.index("outside every named scope") < out.index("longest idle gaps")
