"""Milliseconds a traced step by a part of the scope path, for the readers of
a model's own layers: the scopes of ``benchmark/scope_times.py`` summed over
every path that a predicate picks."""

from __future__ import annotations

from typing import Callable, Optional

from benchmark import scope_times


def ms(run: dict, pick: Callable[[str], bool]) -> Optional[float]:
    """None where the run has no scope map or no picked scope took time."""
    st = scope_times.of(run)
    if not st:
        return None
    total = sum(v for k, v in st["scopes"].items() if pick(k))
    return total if total > 0 else None


def mfu_pct(run: dict, flops_per_step: float, pick: Callable[[str], bool]) -> Optional[float]:
    """``flops_per_step`` over the picked scopes' time, as a share of the
    chip's bf16 peak."""
    t = ms(run, pick)
    if t is None or not run.get("peaks") or flops_per_step <= 0:
        return None
    return 100.0 * flops_per_step / (1e-3 * t) / run["peaks"]["bf16_flops_per_s"]
