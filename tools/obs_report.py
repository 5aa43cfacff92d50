#!/usr/bin/env python
"""Render a run's telemetry into per-pass tables, SLO verdicts, and a
merged cross-rank trace.

The obs plane (docs/OBSERVABILITY.md) writes three artifact kinds:
rank-tagged metric-series JSONL (MetricsWriter), per-rank chrome traces
(Profiler.export_chrome_trace), and incident bundles (FlightRecorder).
This CLI is the read side for all three:

  # per-pass table + SLO verdicts over a metrics dir (ckpt/<root>/obs)
  python tools/obs_report.py <obs_dir> [--rank R]
      [--slo serve.latency_ms:p99<=50 ...] [--json]

  # fuse N ranks' chrome traces into ONE timeline (one process row per
  # rank; cross-rank sends share a trace_id via the PBTX frame extension)
  python tools/obs_report.py --merge-traces out.json rank0.json rank1.json ...

  # a jax.profiler device trace under the program's own names: device time
  # by named scope, the time outside every scope by kind and by the scope it
  # serves, and the trainer's span over each of the longest idle gaps
  # (SCOPES.json is REGISTRY.dump()'s file; default <trace>.scopes.json)
  python tools/obs_report.py --device-trace TRACE.xplane.pb [SCOPES.json]

  # self-contained smoke of histogram/series/recorder/merge (verify drive)
  python tools/obs_report.py --selfcheck

Exit code: 0 on success AND every SLO verdict PASS; 1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# metric series: per-pass tables
# ---------------------------------------------------------------------------


def load_series(obs_dir: str, rank: Optional[int] = None) -> List[dict]:
    """All parsed series records under ``obs_dir`` (one writer per rank),
    ordered by (rank, seq). ``rank`` filters to a single writer."""
    from paddlebox_tpu.obs.metrics_writer import read_series, series_ranks

    ranks = [rank] if rank is not None else series_ranks(obs_dir)
    out: List[dict] = []
    for r in ranks:
        out.extend(read_series(obs_dir, rank=r))
    out.sort(key=lambda rec: (rec.get("rank", 0), rec.get("seq", 0)))
    return out


def _pass_records(records: Sequence[dict]) -> List[dict]:
    return [r for r in records if str(r.get("label", "")).startswith("pass")]


def _table_columns(passes: Sequence[dict], max_cols: int = 6) -> List[str]:
    """The most interesting delta counters across the pass records: ranked
    by peak magnitude so the table stays readable on any workload."""
    peak: Dict[str, float] = {}
    for rec in passes:
        for name, v in (rec.get("deltas") or {}).items():
            peak[name] = max(peak.get(name, 0.0), abs(float(v)))
    ranked = sorted(peak, key=lambda n: (-peak[n], n))
    return sorted(ranked[:max_cols])


def render_pass_table(records: Sequence[dict]) -> str:
    """Fixed-width per-pass table: one row per pass snapshot, columns are
    the top delta counters plus wall time between snapshots."""
    passes = _pass_records(records)
    if not passes:
        return "(no pass-boundary snapshots found)"
    cols = _table_columns(passes)
    header = ["rank", "seq", "label", "dt_s"] + cols
    rows: List[List[str]] = []
    prev_t: Dict[int, float] = {}
    for rec in passes:
        rk = int(rec.get("rank", 0))
        t = float(rec.get("t", 0.0))
        dt = t - prev_t[rk] if rk in prev_t else 0.0
        prev_t[rk] = t
        deltas = rec.get("deltas") or {}
        rows.append(
            [str(rk), str(rec.get("seq", "")), str(rec.get("label", "")),
             f"{dt:.2f}"]
            + [_fmt_num(deltas.get(c)) for c in cols]
        )
    widths = [
        max(len(header[i]), *(len(r[i]) for r in rows))
        for i in range(len(header))
    ]
    lines = [
        "  ".join(h.ljust(widths[i]) for i, h in enumerate(header)),
        "  ".join("-" * w for w in widths),
    ]
    lines += ["  ".join(c.ljust(widths[i]) for i, c in enumerate(r))
              for r in rows]
    return "\n".join(lines)


def _fmt_num(v) -> str:
    if v is None:
        return "-"
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return f"{f:.4g}"


def summarize_histograms(records: Sequence[dict]) -> Dict[str, dict]:
    """Final (cumulative) histogram summaries per rank-merged name: the
    LAST record per rank carries the run's full distribution, so merge
    across ranks by re-accumulating the per-rank summaries' counts."""
    last_per_rank: Dict[int, dict] = {}
    for rec in records:
        last_per_rank[int(rec.get("rank", 0))] = rec
    merged: Dict[str, dict] = {}
    for rec in last_per_rank.values():
        for name, summ in (rec.get("histograms") or {}).items():
            cur = merged.get(name)
            if cur is None or summ.get("count", 0) >= cur.get("count", 0):
                # per-name: keep the widest view (quantiles are not
                # mergeable from summaries; ranks report independently)
                merged[name] = dict(summ, rank=rec.get("rank", 0))
    return merged


# ---------------------------------------------------------------------------
# SLO verdicts
# ---------------------------------------------------------------------------

_SLO_RE = re.compile(
    r"^(?P<name>[a-z0-9_.]+):(?P<field>[a-z0-9_]+)"
    r"(?P<op><=|>=)(?P<bound>[-+0-9.eE]+)$"
)


def parse_slo(spec: str) -> Tuple[str, str, str, float]:
    """'serve.latency_ms:p99<=50' -> (name, field, op, bound)."""
    m = _SLO_RE.match(spec.strip())
    if m is None:
        raise ValueError(
            f"bad --slo spec {spec!r} (want name:field<=bound or >=)"
        )
    return (m["name"], m["field"], m["op"], float(m["bound"]))


def slo_verdicts(
    hists: Dict[str, dict], specs: Sequence[str]
) -> List[dict]:
    """Evaluate each SLO spec against the final histogram summaries."""
    out = []
    for spec in specs:
        name, field, op, bound = parse_slo(spec)
        summ = hists.get(name)
        value = None if summ is None else summ.get(field)
        if value is None:
            verdict = "NODATA"
        elif op == "<=":
            verdict = "PASS" if float(value) <= bound else "FAIL"
        else:
            verdict = "PASS" if float(value) >= bound else "FAIL"
        out.append({
            "slo": spec, "metric": name, "field": field,
            "value": value, "bound": bound, "op": op, "verdict": verdict,
        })
    return out


# ---------------------------------------------------------------------------
# cross-rank trace merge
# ---------------------------------------------------------------------------


def merge_traces(paths: Sequence[str], out_path: str) -> dict:
    """Fuse per-rank chrome traces into one timeline.

    Ranks already occupy distinct pids (Profiler.set_process stamps
    pid=rank at export); colliding pids — two files exported without
    set_process — are remapped to keep one process row per input file.
    Cross-rank correlation: a trace_id riding the PBTX frame extension
    appears in the sender's ``transport:send`` instant and the receiver's
    ``transport:deliver`` instant; any trace_id seen under >=2 distinct
    pids is a confirmed cross-rank span pair.
    """
    events: List[dict] = []
    used_pids: set = set()
    ranks: List[dict] = []
    dropped_total = 0
    for path in paths:
        with open(path) as f:
            doc = json.load(f)
        evs = doc.get("traceEvents", [])
        other = doc.get("otherData", {})
        file_pids = sorted({e.get("pid", 0) for e in evs})
        remap: Dict[int, int] = {}
        for pid in file_pids:
            new = pid
            while new in used_pids:
                new += 1000  # keep rank digits readable after a remap
            remap[pid] = new
            used_pids.add(new)
        for e in evs:
            if remap.get(e.get("pid", 0), 0) != e.get("pid", 0):
                e = dict(e, pid=remap[e.get("pid", 0)])
            events.append(e)
        dropped_total += int(other.get("dropped_events", 0))
        ranks.append({
            "file": os.path.basename(path),
            "rank": other.get("rank"),
            "pids": sorted(remap.values()),
            "events": len(evs),
        })

    # cross-rank pairs: trace_id -> set of pids that logged it
    tid_pids: Dict[str, set] = {}
    for e in events:
        args = e.get("args") or {}
        tid = args.get("trace_id")
        if tid:
            tid_pids.setdefault(tid, set()).add(e.get("pid", 0))
    cross = sorted(t for t, pids in tid_pids.items() if len(pids) >= 2)

    events.sort(key=lambda e: (e.get("ts", 0), e.get("pid", 0)))
    merged = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "merged_from": [os.path.basename(p) for p in paths],
            "dropped_events": dropped_total,
            "cross_rank_trace_ids": len(cross),
        },
    }
    from paddlebox_tpu.utils.fs import atomic_write

    with atomic_write(out_path) as f:
        json.dump(merged, f)
    return {
        "out": out_path,
        "ranks": ranks,
        "process_rows": sorted(used_pids),
        "events": len(events),
        "trace_ids": len(tid_pids),
        "cross_rank_trace_ids": len(cross),
        "cross_rank_sample": cross[:5],
    }


# ---------------------------------------------------------------------------
# device trace under the program's names
# ---------------------------------------------------------------------------


def read_device_trace(path: str) -> dict:
    """Events of an ``.xplane.pb`` in the shape ``benchmark/trace_reduce.py``
    reduces: device operations and program executions per TPU plane, and
    the host's spans — the program's ``pbx:<name>`` annotations
    (utils/trace.py) and a harness's ``bench:<name>`` — each a
    (name, start_s, end_s)."""
    from jax.profiler import ProfileData

    def ev(e, name=None):
        return (name or e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)

    devices: Dict[str, dict] = {}
    spans: List[tuple] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [ev(e) for e in lines[name].events] if name in lines else []
                for key, name in (("ops", "XLA Ops"), ("modules", "XLA Modules"))
            }
        elif plane.name.startswith("/host:"):
            spans += [ev(e, e.name.split(":", 1)[1]) for ln in plane.lines for e in ln.events
                      if e.name.startswith(("pbx:", "bench:"))]
    return {"devices": devices, "spans": spans}


def device_trace_report(trace_path: str, scopes_path: Optional[str] = None) -> dict:
    """Device seconds by the program's named scopes over the whole trace,
    and its ten longest idle gaps under the innermost span that covered
    each; under them the time outside every scope by kind and by the
    scope it serves, and each program's own memory figures, where the dump
    has them. The reduction is the benchmark's own (``trace_reduce.reduce``,
    ``scope_times.scope_seconds``, ``unscoped_times.account``); this only
    reads the events and joins them with the program registry's dump."""
    from benchmark import scope_times, trace_reduce, unscoped_times

    if scopes_path is None:
        scopes_path = trace_path.removesuffix(".xplane.pb") + ".scopes.json"
    with open(scopes_path) as f:
        programs = json.load(f)
    trace = read_device_trace(trace_path)
    if not trace["devices"]:
        raise ValueError(f"{trace_path} has no /device:TPU plane")
    ops = [o for dev in trace["devices"].values() for o in dev["ops"]]
    window = (min(o[1] for o in ops), max(o[2] for o in ops))
    red = trace_reduce.reduce(trace, window=window)
    by_scope: Dict[str, float] = {}
    for prog in programs.values():
        for dev in trace["devices"].values():
            mods = sorted((s, e) for n, s, e in dev["modules"] if prog["fun_name"] in n)
            for scope, sec in scope_times.scope_seconds(
                    dev["ops"], mods, *window, prog["scopes"]).items():
                if scope != scope_times.OTHER:  # what ran outside this program's executions
                    by_scope[scope] = by_scope.get(scope, 0.0) + sec / len(trace["devices"])
    by_scope[scope_times.OTHER] = max(red["busy_s"] - sum(by_scope.values()), 0.0)
    # what the scopes leave out, by kind and by the scope it serves: the programs'
    # own account (a dump of a build before it has none)
    labelled: Dict[str, float] = {}
    for prog in programs.values():
        if prog.get("unscoped") is not None:
            for label, sec in unscoped_times.by_label(
                    trace, *window, prog, prog["fun_name"]).items():
                if label != scope_times.OTHER:
                    labelled[label] = labelled.get(label, 0.0) + sec
    account = unscoped_times.account(labelled) if labelled else None
    return {
        "window_s": red["window_s"], "busy_s": red["busy_s"],
        "programs": {n: p["instructions"] for n, p in programs.items()},
        "scope_s": dict(sorted(by_scope.items(), key=lambda kv: -kv[1])),
        "unscoped_s": account and {"kinds": account["kinds"], "labels": account["labels"]},
        "memory": {n: p["memory"] for n, p in programs.items() if p.get("memory")},
        "table_layout": {n: p["table_layout"] for n, p in programs.items()
                         if p.get("table_layout")},
        "idle_gaps": red["idle_gaps"],
    }


# ---------------------------------------------------------------------------
# selfcheck: exercised by tools/verify_drive.py
# ---------------------------------------------------------------------------


def selfcheck() -> int:
    """End-to-end smoke of the whole obs plane in a temp dir: histogram
    quantiles, metric-series round trip, flight-recorder dump, profiler
    export, and a 2-rank trace merge with a shared trace_id."""
    from paddlebox_tpu.obs.flight_recorder import FlightRecorder
    from paddlebox_tpu.obs.histogram import Histogram
    from paddlebox_tpu.obs.metrics_writer import MetricsWriter, read_series
    from paddlebox_tpu.obs.trace_context import TraceContext
    from paddlebox_tpu.utils.monitor import STAT_ADD
    from paddlebox_tpu.utils.trace import Profiler

    with tempfile.TemporaryDirectory() as tmp:
        # histogram: exact extrema, ordered quantiles
        h = Histogram()
        h.observe_many(float(v) for v in range(1, 1001))
        p50, p99 = h.quantiles((0.5, 0.99))
        assert h.count == 1000 and h.min == 1.0 and h.max == 1000.0
        assert 1.0 <= p50 <= p99 <= 1000.0, (p50, p99)

        # metric series: snapshot -> rotate-safe read back
        w = MetricsWriter(tmp, rank=0, interval_s=0.0)
        STAT_ADD("obs.selfcheck_ticks")
        w.snapshot("pass:0", extra={"auc": 0.5})
        w.snapshot("pass:1")
        recs = list(read_series(tmp, rank=0))
        assert [r["label"] for r in recs] == ["pass:0", "pass:1"], recs
        assert recs[0]["extra"]["auc"] == 0.5

        # flight recorder: incident bundle lands atomically
        fr = FlightRecorder(capacity=8)
        fr.note_span("selfcheck", "obs", 0.0, 1.0, {})
        fr.note_incident("selfcheck_incident", {"detail": "smoke"})
        path = fr.dump("selfcheck", dir_path=os.path.join(tmp, "inc"))
        assert path is not None and os.path.exists(path), path
        with open(path) as f:
            bundle = json.load(f)
        assert bundle["incidents"] and bundle["spans"], bundle

        # two profilers sharing one trace context -> merged cross-rank pair
        ctx = TraceContext.new()
        trace_paths = []
        for rank in range(2):
            prof = Profiler(max_events=64)
            prof.enable()
            prof.set_process(rank)
            prof.instant(
                "transport:send" if rank == 0 else "transport:deliver",
                dict(ctx.as_args()), category="transport",
            )
            tp = os.path.join(tmp, f"trace-{rank}.json")
            prof.export_chrome_trace(tp)
            trace_paths.append(tp)
        rep = merge_traces(trace_paths, os.path.join(tmp, "merged.json"))
        assert len(rep["process_rows"]) == 2, rep
        assert rep["cross_rank_trace_ids"] >= 1, rep

    print("OBS SELFCHECK PASS")
    return 0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("obs_dir", nargs="?", help="metrics dir (ckpt root/obs)")
    ap.add_argument("--rank", type=int, default=None,
                    help="restrict the table to one rank's series")
    ap.add_argument("--slo", action="append", default=[],
                    metavar="NAME:FIELD<=BOUND",
                    help="SLO over a final histogram summary, e.g. "
                         "serve.latency_ms:p99<=50 (repeatable)")
    ap.add_argument("--merge-traces", nargs="+", metavar="JSON",
                    help="OUT.json IN0.json IN1.json ... — fuse per-rank "
                         "chrome traces into one timeline")
    ap.add_argument("--device-trace", nargs="+", metavar="FILE",
                    help="TRACE.xplane.pb [SCOPES.json] — device time by the "
                         "program's named scopes, idle gaps by its spans")
    ap.add_argument("--selfcheck", action="store_true",
                    help="run the obs-plane smoke (verify drive gate)")
    ap.add_argument("--json", action="store_true", help="machine output")
    args = ap.parse_args(argv)

    if args.selfcheck:
        return selfcheck()

    if args.merge_traces:
        if len(args.merge_traces) < 2:
            ap.error("--merge-traces needs OUT.json plus >=1 input trace")
        rep = merge_traces(args.merge_traces[1:], args.merge_traces[0])
        print(json.dumps(rep, indent=None if args.json else 2))
        return 0

    if args.device_trace:
        if len(args.device_trace) > 2:
            ap.error("--device-trace takes TRACE.xplane.pb and at most SCOPES.json")
        rep = device_trace_report(*args.device_trace)
        if args.json:
            print(json.dumps(rep))
            return 0
        print(f"device busy {rep['busy_s']:.6f} s of {rep['window_s']:.6f} s; "
              f"programs: {rep['programs']}")
        for scope, sec in rep["scope_s"].items():
            print(f"  {100 * sec / rep['busy_s']:6.2f}%  {sec:.6f} s  {scope or '(no scope)'}")
        if rep["unscoped_s"]:
            print("outside every named scope, by kind:")
            for kind, sec in rep["unscoped_s"]["kinds"].items():
                print(f"  {100 * sec / rep['busy_s']:6.2f}%  {sec:.6f} s  {kind}")
            print("  the largest, by kind|the scope it serves:")
            for label, sec in list(rep["unscoped_s"]["labels"].items())[:12]:
                print(f"  {100 * sec / rep['busy_s']:6.2f}%  {sec:.6f} s  {label}")
        for name, memory in rep["memory"].items():
            print(f"memory of {name}: " + ", ".join(
                f"{k} {v / 1e9:.3f} GB" for k, v in memory.items()))
        for name, lay in rep["table_layout"].items():
            print(f"pass table of {name}: enters as {lay['in']}, leaves as {lay['out']}")
        print("longest idle gaps, by the span that covered each:")
        for span, sec in rep["idle_gaps"]:
            print(f"  {1e3 * sec:9.3f} ms  {span}")
        return 0

    if not args.obs_dir:
        ap.error("give an obs_dir, --merge-traces, --device-trace, or --selfcheck")
    records = load_series(args.obs_dir, rank=args.rank)
    if not records:
        print(f"no metric series under {args.obs_dir}", file=sys.stderr)
        return 1
    hists = summarize_histograms(records)
    verdicts = slo_verdicts(hists, args.slo)
    if args.json:
        print(json.dumps({
            "records": len(records),
            "passes": len(_pass_records(records)),
            "histograms": hists,
            "slo": verdicts,
        }))
    else:
        print(render_pass_table(records))
        if hists:
            print("\ndistributions (cumulative):")
            for name in sorted(hists):
                s = hists[name]
                print(f"  {name}: n={s.get('count')} p50={_fmt_num(s.get('p50'))} "
                      f"p90={_fmt_num(s.get('p90'))} "
                      f"p99={_fmt_num(s.get('p99'))} "
                      f"max={_fmt_num(s.get('max'))}")
        for v in verdicts:
            print(f"SLO {v['verdict']}: {v['slo']} (value={v['value']})")
    return 1 if any(v["verdict"] == "FAIL" for v in verdicts) else 0


if __name__ == "__main__":
    sys.exit(main())
