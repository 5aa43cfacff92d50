"""Device time of what the program's named scopes leave out, by what it is
and the scope it works for: what the ``unscoped_*_device_ms`` readers share.

Beside its instruction -> scope map a recorded program keeps ``unscoped``:
``{instruction: [kind, serves]}`` for every instruction outside every
``jax.named_scope`` (``paddlebox_tpu/obs/program_scopes.py``: the kind is one
of ``stack``, ``cast``, ``copy``, ``other``; ``serves`` is the scope of the
nearest scoped instruction it feeds or is fed by). Joined with the trace the
way ``scope_times`` joins the scopes, over the same whole periods of the
traced superstep, it says what ``scope_times``' ``unscoped_ms`` is made of.
Other programs' operations, the mapped program's own that it lists under no
kind (a loop's own time) and a scope of none of the four groups count as
``other``, so the four kinds add up to ``unscoped_ms``. A program that keeps
no such account (a commit before it) gives ``None`` everywhere and no line.

One line is printed, once a run: ``bench: unscoped_ops {...}``.
"""

from __future__ import annotations

import json
from typing import Dict, Optional

from benchmark import scope_times

KINDS = ("stack", "cast", "copy", "other")  # the program's, kept here for a program without them
GROUPED = frozenset(top for tops in scope_times.GROUPS.values() for top in tops)


def labels(prog: dict) -> Dict[str, str]:
    """The program's scope map with ``kind|serves|instruction`` in place of
    the ``""`` of every instruction its account lists."""
    out = dict(prog["scopes"])
    for name, (kind, serves) in prog["unscoped"].items():
        out[name] = f"{kind}|{serves}|{name}"
    return out


def by_label(trace: dict, lo: float, hi: float, prog: dict, module: str) -> Dict[str, float]:
    """Self seconds inside [lo, hi] by ``labels`` (mean over device planes)
    of the operations inside executions of the programs named ``*module*``;
    the others' under ``scope_times.OTHER``."""
    names, out = labels(prog), {}
    for dev in trace["devices"].values():
        mods = sorted((s, e) for n, s, e in dev["modules"] if module in n)
        for label, sec in scope_times.scope_seconds(dev["ops"], mods, lo, hi, names).items():
            out[label] = out.get(label, 0.0) + sec / len(trace["devices"])
    return out


def account(values: Dict[str, float]) -> dict:
    """From ``by_label``'s values (in any unit) to what lies in none of the
    four groups of scopes: by kind, by ``kind|serves`` and by instruction
    (value, kind, serves, instruction), the largest first."""
    kinds = dict.fromkeys(KINDS, 0.0)
    pairs: Dict[str, float] = {}
    instructions = []
    for label, v in values.items():
        if "|" in label:
            kind, serves, name = label.split("|", 2)
            instructions.append((v, kind, serves, name))
        elif label.split("/", 1)[0] in GROUPED:
            continue
        else:  # other programs, a loop's own time, a scope of no group
            kind, serves = "other", label
        kinds[kind] += v
        pairs[f"{kind}|{serves}"] = pairs.get(f"{kind}|{serves}", 0.0) + v
    return {"kinds": kinds, "labels": dict(sorted(pairs.items(), key=lambda kv: -kv[1])),
            "instructions": sorted(instructions, reverse=True)}


def of(run: dict) -> Optional[dict]:
    """``account`` in milliseconds a traced step; computed and printed once,
    then kept on the run. None where the program recorded no account."""
    if "unscoped_times" not in run:
        prog, red = scope_times.program(), run.get("reduced")
        if (not prog or prog.get("unscoped") is None or not red or not red["n_modules"]
                or not run.get("trace")):
            run["unscoped_times"] = None
        else:
            steps = red["n_modules"] * run["scan_batches"]
            sec = by_label(run["trace"], *red["window"], prog, "superstep")
            acc = account({k: 1e3 * v / steps for k, v in sec.items()})
            run["unscoped_times"] = acc
            heads = {scope_times.instruction(n): n for dev in run["trace"]["devices"].values()
                     for n, _, _ in dev["ops"]}
            line = {"steps": steps, "unscoped_ms": round(sum(acc["kinds"].values()), 4),
                    "by_kind": {k: round(v, 4) for k, v in acc["kinds"].items()},
                    "labels": {k: round(v, 4) for k, v in list(acc["labels"].items())[:12]},
                    "instructions": [[round(v, 4), kind, serves, heads.get(name, name)[:120]]
                                     for v, kind, serves, name in acc["instructions"][:8]]}
            print("bench: unscoped_ops " + json.dumps(line), flush=True)
    return run["unscoped_times"]


def kind_ms(run: dict, kind: str) -> Optional[float]:
    acc = of(run)
    return acc["kinds"][kind] if acc else None

