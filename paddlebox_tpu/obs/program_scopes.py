"""Which of the program's named scopes each compiled instruction belongs to.

A device trace names an operation by its HLO instruction
(``%fusion.255 = s32[442368]{...} fusion(...)``) and carries no metadata, so
``jax.named_scope`` never reaches a trace reader on its own. The compiled
program's HLO text does carry it
(``metadata={op_name="jit(superstep)/while/body/closed_call/build_batch/
inverse_scatter/scatter"}``), and the process that compiled the program can
read that text. This module turns it into ``{instruction name: scope path}``
and keeps one such map per compiled program in a process-global registry
that outlives the trainer, so that whoever reads a trace of this process —
the benchmark's per-layer readers, ``tools/obs_report.py --device-trace`` —
can join the two. ``REGISTRY.dump(path)`` writes the maps beside a trace for
a reader in another process.

An executable loaded from jax's persistent compilation cache carries the
metadata of the build that filled the cache (the cache key leaves debug
information out, ``jax_compilation_cache_include_metadata_in_key``): its map
shows the scopes as they were then. Such an entry has no ``compile_s``.

Scope paths are the program's own names only: ``jit(...)`` tokens, jax's
structural names (``while/body``, ``closed_call``, ``checkpoint``,
``rematted_computation``, ...), the ``jvp(...)``/``transpose(...)`` wrappers
of autodiff, an einsum's own spec, a Pallas kernel's own name and the
trailing primitive name are stripped, and a path repeated under itself (the
recomputed body of a ``jax.checkpoint`` or of a scan over layers) is folded
to its last occurrence, so a forward op, its backward twin and its
recomputed twin land in the same scope. Code under ``checkpoint`` or
``scan`` names its leaf scopes by their whole path
(``jax.named_scope("model/mla/scores")``): the name stack of a re-traced
body starts anew there.
"""

from __future__ import annotations

import json
import re
import threading
from typing import Dict, List, Optional

import jax

from paddlebox_tpu.utils.fs import atomic_write

# one HLO instruction: optional ROOT, optional %, the name, " = "
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_PROGRAM = re.compile(r"\b(?:jit|pjit)\([^()]*\)")
_WRAPPER = re.compile(r"\b\w+\(|\)")  # jvp( transpose( vmap( ... and their )
# names jax itself pushes on the name stack around a traced body ("jit" is
# what a jit(...) token is folded to)
_STRUCTURAL = frozenset((
    "jit", "while", "body", "cond", "closed_call", "core_call", "checkpoint",
    "remat", "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "shard_map", "branch_0_fun", "branch_1_fun", "rematted_computation",
))


def scope_of(op_name: str) -> str:
    """``jit(superstep)/while/body/closed_call/transpose(jvp(seqpool_cvm))/
    mul`` -> ``seqpool_cvm``; an op outside every named scope -> ``""``."""
    first = op_name.split(";", 1)[0]  # merged instructions list every source
    tokens = [t for t in _WRAPPER.sub("", _PROGRAM.sub("jit", first)).split("/") if t]
    # the last token is the primitive; a constant has none, and is seen to
    # have none only where a jit(...) token ends its name
    if tokens and tokens[-1] != "jit":
        # a kernel's ``name=`` is the scope ``pallas_call`` itself opens around
        # the primitive (every kernel of ops/pallas_kernels.py on a model's
        # path is named): the kernel's, not the program's
        if tokens.pop() == "pallas_call" and tokens:
            tokens.pop()
    # an einsum pushes its own spec ("bqhd,bkhd->bhqk"): no scope of the program's
    path = [t for t in tokens if t not in _STRUCTURAL and "->" not in t]
    # the backward pass of a ``jax.checkpoint`` body (and of a scan's) re-traces
    # instructions that carry their whole path under the path of the call:
    # "model/mla/scores/model/mla/scores" is the second "model/..." alone
    if path and path[0] in path[1:]:
        path = path[len(path) - 1 - path[::-1].index(path[0]):]
    return "/".join(path)


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope path} over every instruction of every
    computation of a compiled module's text. A fusion takes the scope of its
    own ``op_name``, which is its root's."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        op = _OP_NAME.search(line)
        out[m.group(1)] = scope_of(op.group(1)) if op else ""
    return out


class ProgramRegistry:
    """Scope maps and build times of the programs this process compiled,
    by the program's name. Plain data: survives ``jax.clear_caches()`` and
    the trainer that recorded it."""

    _EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[str, dict] = {}  # guarded-by: _lock
        self._build: Dict[str, Dict[str, float]] = {}  # guarded-by: _lock
        self._listening = False  # guarded-by: _lock

    def watch(self, fun_name: str) -> None:
        """Collect jax's own trace / lowering / compile seconds of the jitted
        function ``fun_name`` from now on (jax reports them by that name);
        ``record`` hands them to the program and starts anew."""
        with self._lock:
            self._build.setdefault(fun_name, {})
            if not self._listening:
                jax.monitoring.register_event_duration_secs_listener(self._on_event)
                self._listening = True

    def _on_event(self, event: str, duration: float, **kw) -> None:
        key = self._EVENTS.get(event)
        if key is None:
            return
        fun = str(kw.get("fun_name", ""))
        if fun.startswith("jit(") and fun.endswith(")"):
            fun = fun[4:-1]  # tracing reports "superstep", the later stages "jit(superstep)"
        with self._lock:
            build = self._build.get(fun)
            if build is not None:
                build[key] = build.get(key, 0.0) + float(duration)

    def record(self, name: str, fun_name: str, hlo_text: str) -> dict:
        scopes = scope_map(hlo_text)
        with self._lock:
            build = self._build.get(fun_name)
            entry = {
                "fun_name": fun_name, "instructions": len(scopes),
                "scopes": scopes, **(build or {}),
            }
            if build is not None:
                self._build[fun_name] = {}
            self._programs[name] = entry
        return entry

    def get(self, name: str) -> Optional[dict]:
        with self._lock:
            return self._programs.get(name)

    def names(self) -> List[str]:
        with self._lock:
            return list(self._programs)

    def dump(self, path: str) -> None:
        """Every recorded program as JSON, to lie beside a device trace."""
        with self._lock:
            doc = {n: dict(p) for n, p in self._programs.items()}
        with atomic_write(path) as f:
            json.dump(doc, f)


# process-global, like utils.trace.PROFILER
REGISTRY = ProgramRegistry()
