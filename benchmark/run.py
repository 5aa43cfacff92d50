"""Entry point of the benchmark.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by the names in
``BENCHMARK.json``: the configuration's file, ``benchmark/traffic/<mix>.json``
(which names its window driver under ``benchmark/drivers/``; the driver runs
the set-up and the window and hands back the comparison that decides
``correct``, to be run once the program's state is freed),
``benchmark/limits/<workload>.json`` and, for a traced run, one reader per
per-layer metric under ``benchmark/layer_metrics/``. The last line of
standard output is the result object; the numbers compared, each beside its
limit, are the last lines of standard error and the result's last key.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def resolve(spec: dict, workload: str) -> dict:
    """The cell's files, by the names in the benchmark's specification."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; there are {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    return {
        "workload": workload, "chips": int(w["chips"]),
        "cfg": load_json(conf["file"]),
        "mix": load_json("benchmark", "traffic", w["traffic"] + ".json"),
        "limits": load_json("benchmark", "limits", workload + ".json"),
    }


def metrics_of(spec: dict, section: str, workload: str) -> list:
    return [m for m in spec[section] if workload in m.get("workloads", [workload])]


def read_layer_metric(name: str, run: dict):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location("layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run)


def run_cell(cell: dict, spec: dict, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object."""
    import jax

    from paddlebox_tpu.utils import compilecache

    from benchmark import compare, spans
    from benchmark.drivers import common

    dev = jax.devices()[0]
    if require_tpu and (dev.platform != "tpu" or jax.device_count() < cell["chips"]):
        print(f"bench: needs {cell['chips']} TPU chip(s); found {jax.device_count()} x "
              f"{dev.platform} - not started", file=sys.stderr)
        raise SystemExit(2)
    peaks = load_json("benchmark", "peaks.json")
    if require_tpu and dev.device_kind not in peaks:
        raise SystemExit(f"no peaks for device kind {dev.device_kind!r} in benchmark/peaks.json")
    compilecache.enable()
    rec = spans.Recorder()
    driver = importlib.import_module("benchmark.drivers." + cell["mix"]["driver"])
    run = driver.run(cell, rec)
    common.release()
    t_window = run["t_window"][0]
    run.update(cell=cell, rec=rec, peaks=peaks.get(dev.device_kind),
               setup_s=t_window - T_START)

    t0 = time.perf_counter()
    numbers = run.pop("check")()  # the driver's own comparison: {name: number}
    correct, checks = compare.judge(numbers, cell["limits"])
    correct = correct and run["failed"] == 0
    print(f"bench: reference and comparison took {time.perf_counter() - t0:.1f} s", flush=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": run["attempted"], "failed": run["failed"]}
    if cell["trace"]:
        from benchmark import trace_reduce

        red = trace_reduce.reduce(run["trace"], module=run.get("trace_module"))
        run["reduced"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        wanted = metrics_of(spec, "per_layer", cell["workload"])
    else:
        wanted = metrics_of(spec, "end_to_end", cell["workload"])
    end_to_end = {**run["end_to_end"], "setup_s": run["setup_s"]}
    metrics = {}
    for m in wanted:
        value = read_layer_metric(m["name"], run) if cell["trace"] else end_to_end[m["name"]]
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    info = {k: run[k] for k in ("steps", "calls", "window_s", "warm_step_s", "call_start_s", "loss",
                                "auc", "keys_in_pass", "table_rows", "distinct_rows_per_step",
                                "ids_per_step")
            if k in run}
    info["not_compared"] = {k: v for k, v in numbers.items() if k not in cell["limits"]}
    info["spans"] = {n: round(rec.seconds(n), 3) for n in dict.fromkeys(s[0] for s in rec.spans)}
    info["slow_jax_events"] = rec.slow
    info["compile_cache"] = compilecache.stats()
    print("bench: run " + json.dumps(info), flush=True)
    result["checks"] = checks
    for name, (value, limit) in checks.items():
        print(f"bench: check {name} {value:.6g} limit {limit:.6g}", file=sys.stderr)
    print(f"bench: failed {run['failed']} of {run['attempted']} correct {result['correct']}",
          file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_json("BENCHMARK.json")
    cell = resolve(spec, args.workload)
    cell.update(seed=args.seed, seconds=args.seconds, trace=bool(args.trace))
    result = run_cell(cell, spec)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
