"""From a profiler trace (``.xplane.pb``) to busy and idle time, time per
device operation and the idle gaps named by the harness span that covered
them. Read with ``jax.profiler.ProfileData`` alone.

A device plane is one named ``/device:TPU:<n>``; its ``XLA Ops`` line holds
the operations (nested: a ``while`` covers its body's operations) and its
``XLA Modules`` line one event per executed program. Harness spans are the
host events named ``bench:<name>``.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]


def find_trace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "spans": [...]},
    every event a (name, start_s, end_s)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                raise ValueError(f"{plane.name} has no 'XLA Ops' line: {sorted(lines)}")
            devices[plane.name] = {
                key: [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", "XLA Ops"), ("modules", "XLA Modules"))
            }
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [(e.name[6:], e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                          for e in ln.events if e.name.startswith("bench:")]
    return {"devices": devices, "spans": spans}


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def self_times(ops: List[Tuple[str, float, float]], lo: float, hi: float) -> Dict[str, float]:
    """Seconds per operation name inside [lo, hi], a parent's time less its
    children's (a ``while`` would otherwise count its whole body again)."""
    total: Dict[str, float] = {}
    stack: List[List] = []  # [name, end, self seconds]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0)

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        close(s)
        if stack:
            stack[-1][2] -= e - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return total


def complete_modules(dev: dict, lo: float, hi: float, contains: str):
    """Executions wholly inside [lo, hi]. One that was running when the trace
    began is recorded from the trace's first device event on, and one running
    when it stopped with next to no length: neither is complete."""
    first = min((s for _, s, _ in dev["ops"]), default=lo)
    mods = [(n, s, e) for n, s, e in dev["modules"]
            if contains in n and s >= lo and e <= hi and s > first + 1e-5]
    if not mods:
        return mods
    half = 0.5 * sorted(e - s for _, s, e in mods)[len(mods) // 2]
    return [m for m in mods if m[2] - m[1] >= half]


def reduce(trace: dict, window: Optional[Interval] = None, module: Optional[str] = None) -> dict:
    """Busy seconds (mean over device planes), the window's length, the ten
    operations with most self time and the ten longest idle gaps.

    The window is ``window`` if given, else the ``traced`` harness span; with
    ``module`` it is narrowed to whole periods of the program whose name
    contains that string: from the start of its first complete execution to
    the start of its last, so that every execution counted has its gap."""
    if not trace["devices"]:
        raise ValueError("the trace has no device plane")
    if window is None:
        traced = [(s, e) for n, s, e in trace["spans"] if n == "traced"]
        if not traced:
            raise ValueError("the trace has no bench:traced span")
        window = traced[0]
    lo, hi = window
    busy, ops_total, gaps, n_modules, mods = [], {}, [], 0, []
    for dev in trace["devices"].values():
        d_lo, d_hi = lo, hi
        if module is not None:
            mods = complete_modules(dev, lo, hi, module)
            if len(mods) < 2:
                raise ValueError(f"under two complete executions of a program named *{module}* "
                                 f"in the window; programs: {sorted({m[0] for m in dev['modules']})}")
            d_lo, d_hi = mods[0][1], mods[-1][1]
            n_modules = len(mods) - 1
        merged = union([(s, e) for _, s, e in dev["ops"]], d_lo, d_hi)
        busy.append(sum(e - s for s, e in merged))
        for name, sec in self_times(dev["ops"], d_lo, d_hi).items():
            ops_total[name] = ops_total.get(name, 0.0) + sec / len(trace["devices"])
        edges = [d_lo] + [t for iv in merged for t in iv] + [d_hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        lo_out, hi_out = d_lo, d_hi
    spans = [sp for sp in trace["spans"] if sp[0] != "traced"]

    def covering(s: float, e: float) -> str:
        mid = 0.5 * (s + e)
        inner = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        return min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner else "no_span"

    # XLA's operation names run to hundreds of characters: keep their heads
    top = lambda pairs: [[n[:160], v] for n, v in sorted(pairs, key=lambda p: -p[1])[:10]]  # noqa: E731
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": hi_out - lo_out,
        "window": (lo_out, hi_out),
        "n_modules": n_modules,  # whole periods: the last execution only closes the window
        "modules": [(s, e) for _, s, e in mods],
        "device_ops": top(ops_total.items()),
        "idle_gaps": top((covering(s, e), e - s) for s, e in gaps),
        "gap_list": sorted(gaps),
    }
