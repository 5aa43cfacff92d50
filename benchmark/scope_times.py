"""Device time by the program's own named scopes, and the program's host
spans: what the per-layer readers added with the scopes share.

The trace names a device operation by its HLO instruction; the program keeps,
per compiled program, the map from instruction to ``jax.named_scope`` path
(``paddlebox_tpu/obs/program_scopes.py``). Joined over the whole periods of
the traced superstep program (``run["reduced"]["window"]``) with
``trace_reduce.self_times``, they give milliseconds a step for every scope.
A program that keeps no such map (a commit before the scopes) gives ``None``
everywhere and no line.

Two lines are printed, once a run, before the result line:
``bench: device_scopes {...}`` and ``bench: program_spans {...}``.
"""

from __future__ import annotations

import bisect
import json
from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce

GROUPS = {  # the four per-layer groups, by top-level scope
    "batch_assembly": ("build_batch",),
    "pull": ("pull",),
    "model": ("seqpool_cvm", "model", "loss", "nan_guard", "dense_opt", "auc"),
    "push": ("push",),
}
PROGRAM = "superstep/train/"
OTHER = "(other programs)"


def instruction(event_name: str) -> str:
    """``%fusion.255 = s32[442368]{...} fusion(...)`` -> ``fusion.255``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def program() -> Optional[dict]:
    """The train superstep this process recorded last (its scope map and
    build seconds), or None."""
    try:
        from paddlebox_tpu.obs.program_scopes import REGISTRY
    except ImportError:
        return None
    names = [n for n in REGISTRY.names() if n.startswith(PROGRAM)]
    return dict(REGISTRY.get(names[-1]), name=names[-1]) if names else None


def scope_seconds(ops: List[Tuple[str, float, float]], modules: List[Tuple[float, float]],
                  lo: float, hi: float, scopes: Dict[str, str]) -> Dict[str, float]:
    """Self seconds by scope path inside [lo, hi]. Only operations that ran
    inside an execution of the mapped program (``modules``) are looked up:
    another program's ``%fusion.3`` is not this one's. Theirs is ``OTHER``;
    the mapped program's own without a scope, ``""``."""
    starts = [s for s, _ in modules]

    def inside(s: float) -> bool:
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and s < modules[i][1]

    own, rest = [], []
    for o in ops:
        (own if inside(o[1]) else rest).append(o)
    out: Dict[str, float] = {}
    for name, sec in trace_reduce.self_times(own, lo, hi).items():
        scope = scopes.get(instruction(name), "")
        # the push reads again the rows the pull gathered; XLA merges the two
        # gathers into one and keeps either's name: charged to the pull, which
        # is where the step first needs it
        scope = scope.replace("push/table_gather", "pull/table_gather")
        out[scope] = out.get(scope, 0.0) + sec
    other = sum(trace_reduce.self_times(rest, lo, hi).values())
    if other:
        out[OTHER] = other
    return out


def compute(trace: dict, reduced: dict, scopes: Dict[str, str], steps_per_program: int) -> dict:
    """Milliseconds a step: by scope path, by group, and what is in none of
    the four groups."""
    lo, hi = reduced["window"]
    steps = reduced["n_modules"] * steps_per_program
    by_scope: Dict[str, float] = {}
    for dev in trace["devices"].values():
        mods = [(s, e) for n, s, e in dev["modules"] if "superstep" in n]
        for scope, sec in scope_seconds(dev["ops"], sorted(mods), lo, hi, scopes).items():
            by_scope[scope] = by_scope.get(scope, 0.0) + sec / len(trace["devices"])
    ms = {k: 1e3 * v / steps for k, v in by_scope.items()}
    top: Dict[str, float] = {}
    for scope, v in ms.items():
        top[scope.split("/", 1)[0]] = top.get(scope.split("/", 1)[0], 0.0) + v
    groups = {g: sum(top.get(t, 0.0) for t in tops) for g, tops in GROUPS.items()}
    total = sum(ms.values())
    return {"steps": steps, "total_ms": total, "scopes": ms, "groups": groups,
            "unscoped_ms": total - sum(groups.values())}


def of(run: dict) -> Optional[dict]:
    """``compute`` over the run's trace and the program's map; computed and
    printed once, then kept on the run."""
    if "scope_times" not in run:
        prog, red = program(), run.get("reduced")
        if not prog or not red or not red["n_modules"] or not run.get("trace"):
            run["scope_times"] = None
        else:
            st = compute(run["trace"], red, prog["scopes"], run["scan_batches"])
            run["scope_times"] = st
            line = {"program": {k: v for k, v in prog.items() if k != "scopes"},
                    "steps": st["steps"], "ms_per_step": _rounded(st["scopes"]),
                    "groups": _rounded(st["groups"]), "unscoped_ms": round(st["unscoped_ms"], 4)}
            print("bench: device_scopes " + json.dumps(line), flush=True)
    return run["scope_times"]


def group_ms(run: dict, group: str) -> Optional[float]:
    st = of(run)
    return st["groups"][group] if st else None


def hbm_pct(run: dict, scope: str, need_bytes: float) -> Optional[float]:
    """``need_bytes`` a step over the time of ``scope``, as a share of the
    device's HBM peak; None where the scope took no time."""
    st = of(run)
    ms = st["scopes"].get(scope, 0.0) if st else 0.0
    if ms <= 0 or not run.get("peaks"):
        return None
    return 100.0 * need_bytes / (1e-3 * ms) / run["peaks"]["hbm_bytes_per_s"]


def spans(run: dict) -> Optional[dict]:
    """{span name: {"count", "seconds"}} of the program's own host spans over
    the whole process (``PROFILER.totals()``), or None where it keeps none."""
    if "program_spans" not in run:
        from paddlebox_tpu.utils.trace import PROFILER

        totals = getattr(PROFILER, "totals", None)
        run["program_spans"] = totals() if totals else None
        if totals:
            print("bench: program_spans " + json.dumps(
                {n: [t["count"], round(t["seconds"], 4)]
                 for n, t in run["program_spans"].items()}), flush=True)
    return run["program_spans"]


def span_seconds(run: dict, name: str) -> Optional[float]:
    sp = spans(run)
    return sp[name]["seconds"] if sp and name in sp else None


def _rounded(d: Dict[str, float]) -> Dict[str, float]:
    return {k: round(v, 4) for k, v in sorted(d.items(), key=lambda kv: -kv[1])}
