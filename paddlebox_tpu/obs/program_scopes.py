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

What the scopes leave out is accounted for from the same text
(``unscoped_map``): the layer scan's slicing and stacking, the copies layout
assignment inserts and the TPU's expansion of a prefix sum carry jax's own
names only, or none, and no ``jax.named_scope`` reaches them. Each such
instruction gets a *kind* (``stack``, ``cast``, ``copy``, ``other``) from its
opcode, or from the opcodes its fused computation holds, and the scope it
*serves*: that of the nearest scoped instruction its value is handed to
(followed by tuple index across a loop's or a conditional's boundary) or,
failing that, was made from. A recorded program keeps both as ``unscoped``
beside ``scopes``, and what ``memory_analysis()`` says of the executable as
``memory``.
"""

from __future__ import annotations

import json
import re
import threading
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

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


KINDS = ("stack", "cast", "copy", "other")
_ROOT_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s")  # _INSTRUCTION, ROOT kept
# a computation's header: not indented, ends in "{" ("ENTRY %main.3 (...) -> ... {")
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?(?!HloModule\b)([\w.\-]+)\s.*\{\s*$")
_OPCODE = re.compile(r"[\s)]([a-z][\w\-]*)\(")
_CALLED = re.compile(r"\b(calls|to_apply|body|condition|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_NAME = re.compile(r"[\w.\-]+")
_OPERAND = re.compile(r"%([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_REACH = 48  # steps a search for the scope an instruction serves may take
# never a device event of their own: a while / conditional / call is its body's instructions
_CONTROL = frozenset(("while", "conditional", "call"))
_CALLING = _CONTROL | {"fusion", "async-start"}  # the opcodes whose computations matter here
_NO_EVENT = _CONTROL | {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
# what says nothing of a fused computation's kind
_SHAPING = frozenset(("parameter", "constant", "bitcast", "reshape", "broadcast", "tuple",
                      "get-tuple-element"))
_STACK = frozenset(("dynamic-slice", "dynamic-update-slice"))
_CAST = frozenset(("convert", "reduce-precision"))  # the second: what a checkpoint name leaves
_COPY = frozenset(("copy", "transpose", "copy-start", "copy-done"))
_INTEGER = re.compile(r"^(?:pred|[su]\d+)\b")


class _Ins(NamedTuple):
    name: str
    comp: str
    opcode: str
    shape: str
    operands: Tuple[str, ...]  # by position
    called: Dict[str, List[str]]  # attribute -> computations
    index: int  # a get-tuple-element's index, a parameter's number; else -1
    root: bool
    order: int  # its line among the module's instructions


def _parse(hlo_text: str, scopes: Optional[Dict[str, str]] = None):
    """({instruction: _Ins}, {computation: [instruction names in the text's
    order]}, the entry computation's name or None). Instructions ahead of any
    header belong to the computation ``""``. ``scopes``, if given, is filled
    as ``scope_map`` would fill it, in the same reading. A line runs to
    kilobytes of ``backend_config``: it is searched in place, and for the
    computations it names only where its opcode can name one and ahead of its
    metadata."""
    ins: Dict[str, _Ins] = {}
    comps: Dict[str, List[str]] = {}
    entry, comp = None, ""
    scope_of_path: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _ROOT_INSTRUCTION.match(line)
        if m is None:
            head = _COMPUTATION.match(line)
            if head is not None:
                comp = head.group(2)
                if head.group(1):
                    entry = comp
            continue
        name, start = m.group(2), m.end()
        if scopes is not None:
            op_name = _OP_NAME.search(line, start)
            path = op_name.group(1) if op_name else ""
            if path not in scope_of_path:  # a fused kernel's pieces share one op_name
                scope_of_path[path] = scope_of(path) if path else ""
            scopes[name] = scope_of_path[path]
        # the opcode is the first lower-case word that opens a parenthesis: a
        # shape's own are its tiling's and memory space's, T(8,128)(2,1)S(1)
        op = _OPCODE.search(line, start)
        opcode, shape, operands, called, index = "", "", (), {}, -1
        if op is not None:
            opcode, shape = op.group(1), line[start:op.start()]
            close = line.find(")", op.end())  # operands are %names: none holds a parenthesis
            if opcode == "parameter":
                number = line[op.end():close]
                index = int(number) if number.isdigit() else -1
            elif opcode != "constant":  # one %name an operand, by position
                operands = tuple(_OPERAND.findall(line, op.end(), close))
            if opcode == "get-tuple-element":
                at = _INDEX.search(line, close)
                index = int(at.group(1)) if at else -1
            elif opcode in _CALLING:
                stop = line.find(" metadata={", close)
                stop = len(line) if stop < 0 else stop
                for key, target in _CALLED.findall(line, close, stop):
                    called.setdefault(key, []).append(target)
                branches = _BRANCHES.search(line, close, stop)
                if branches:
                    called["branches"] = _NAME.findall(branches.group(1))
                elif "true_computation" in called:
                    called["branches"] = called["true_computation"] + called["false_computation"]
        ins[name] = _Ins(name, comp, opcode, shape, operands, called, index, bool(m.group(1)), len(ins))
        comps.setdefault(comp, []).append(name)
    return ins, comps, entry


def _bodies(i: _Ins) -> List[str]:
    """The computations a loop, a conditional or a call runs."""
    return [c for key in ("body", "condition", "branches", "to_apply") for c in i.called.get(key, ())]


def _executed(ins: Dict[str, _Ins], comps: Dict[str, List[str]], entry: Optional[str]) -> List[str]:
    """The computations whose instructions run as device operations of their
    own: the entry and, from it, the bodies and conditions of loops, the
    branches of conditionals and what a ``call`` applies. A fused computation
    and a reduction's are inside one instruction. Without an entry, every
    computation no instruction names."""
    if entry is None:
        named = {c for i in ins.values() for cs in i.called.values() for c in cs}
        todo = [c for c in comps if c not in named]
    else:
        todo = [entry]
    seen: Dict[str, None] = {}  # in the order met
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen[comp] = None
        for name in comps[comp]:
            if ins[name].opcode in _CONTROL:
                todo += _bodies(ins[name])
    return list(seen)


def kind_of(opcodes: Iterable[Tuple[str, str]]) -> str:
    """One of ``KINDS`` for an instruction given as the (opcode, result
    shape) pairs it holds: itself alone, or its fused computation's
    instructions. ``stack``: a dynamic slice read out of, or written into, a
    stacked array and no arithmetic on floats (a cast or a layout change
    riding along does not change the kind); ``cast``: all it does is
    ``convert`` (or ``reduce-precision``, the rounding in place that jax
    leaves where a value is named for a checkpoint policy); ``copy``: all it
    does is ``copy`` / ``transpose``; whatever
    is left, ``other``. Shaping (``_SHAPING``) and integer arithmetic (a
    slice's index) say nothing."""
    held = set()
    for opcode, shape in opcodes:
        if opcode in _SHAPING:
            continue
        if _INTEGER.match(shape) and opcode not in _STACK | _CAST | _COPY:
            continue
        held.add(opcode)
    if held & _STACK and held <= _STACK | _CAST | _COPY:
        return "stack"
    if held & _CAST and held <= _CAST | _COPY:
        return "cast"
    if held and held <= _COPY:
        return "copy"
    return "other"


class _Flow:
    """Where a value goes and where it came from, over the executed
    computations of one module. A state is (instruction, path): the part of
    the instruction's value that the tuple indices ``path`` pick, so that a
    value is followed through ``tuple`` / ``get-tuple-element`` and across a
    loop's, a conditional's or a call's boundary as itself, not as everything
    else the same tuple carries."""

    def __init__(self, ins: Dict[str, _Ins], comps: Dict[str, List[str]], executed: List[str]):
        self.ins = ins
        self.users: Dict[str, List[Tuple[str, int]]] = {}  # in the text's order
        self.params: Dict[str, Dict[int, str]] = {}
        self.roots: Dict[str, str] = {}
        self.callers: Dict[str, List[Tuple[str, str, int]]] = {}  # comp -> (instruction, role, n)
        for comp in executed:
            for name in comps[comp]:
                i = ins[name]
                for pos, o in enumerate(i.operands):
                    if o in ins and ins[o].comp == comp:
                        self.users.setdefault(o, []).append((name, pos))
                if i.opcode == "parameter":
                    self.params.setdefault(comp, {})[i.index] = name
                if i.root:
                    self.roots[comp] = name
                if i.opcode in _CONTROL:
                    for role in ("body", "condition", "to_apply"):
                        for c in i.called.get(role, ()):
                            self.callers.setdefault(c, []).append((name, role, 0))
                    for n, c in enumerate(i.called.get("branches", ())):
                        self.callers.setdefault(c, []).append((name, "branch", n))

    def _param(self, comp: str, number: int):
        name = self.params.get(comp, {}).get(number)
        return [name] if name else []

    def _root(self, comp: str):
        name = self.roots.get(comp)
        return [name] if name else []

    def _operand(self, i: _Ins, pos: int):
        ok = pos < len(i.operands) and i.operands[pos] in self.ins
        return [i.operands[pos]] if ok else []

    def onward(self, name: str, path: Tuple[int, ...]):
        """The states the value (name, path) is handed to."""
        i = self.ins[name]
        if i.opcode == "while":  # the loop's value is its body's parameter, turn after turn
            for c in i.called.get("body", []) + i.called.get("condition", []):
                yield from ((p, path) for p in self._param(c, 0))
        if i.root:  # a body's result is the value of what ran it
            for caller, role, _ in self.callers.get(i.comp, ()):
                if role != "condition":
                    yield caller, path
        for user, pos in self.users.get(name, ()):
            u = self.ins[user]
            if u.opcode == "tuple":
                yield user, (pos,) + path
            elif u.opcode == "get-tuple-element":
                if not path or path[0] == u.index:
                    yield user, path[1:]
            elif u.opcode == "while":
                yield user, path
            elif u.opcode == "conditional":
                for c in u.called.get("branches", ())[pos - 1:pos] if pos else ():
                    yield from ((p, path) for p in self._param(c, 0))
            elif u.opcode == "call":
                for c in u.called.get("to_apply", ()):
                    yield from ((p, path) for p in self._param(c, pos))
            else:
                yield user, ()

    def backward(self, name: str, path: Tuple[int, ...]):
        """The states the value (name, path) was made from."""
        i = self.ins[name]
        if i.opcode == "tuple":
            picked = self._operand(i, path[0]) if path else [o for o in i.operands if o in self.ins]
            yield from ((o, path[1:]) for o in picked)
        elif i.opcode == "get-tuple-element":
            yield from ((o, (i.index,) + path) for o in self._operand(i, 0))
        elif i.opcode == "parameter":
            for caller, role, n in self.callers.get(i.comp, ()):
                c = self.ins[caller]
                if role in ("body", "condition"):
                    yield from ((o, path) for o in self._operand(c, 0))
                    yield from ((r, path) for b in c.called.get("body", ()) for r in self._root(b))
                elif role == "branch":
                    yield from ((o, path) for o in self._operand(c, n + 1))
                else:
                    yield from ((o, path) for o in self._operand(c, i.index))
        elif i.opcode in _CONTROL:
            if i.opcode == "while":
                yield from ((o, path) for o in self._operand(i, 0))
            for c in i.called.get("body", []) + i.called.get("branches", []) + i.called.get("to_apply", []):
                yield from ((r, path) for r in self._root(c))
        else:
            yield from ((o, ()) for o in i.operands if o in self.ins)

    def nearest(self, start: str, step, scopes: Dict[str, str]) -> str:
        """Breadth-first from ``start`` by ``step`` (``onward`` or
        ``backward``) through instructions without a scope: the scope of the
        nearest scoped one, the first in the text among equals; ``""`` where
        ``_REACH`` steps find none."""
        seen, frontier = {(start, ())}, [(start, ())]
        for _ in range(_REACH):
            reached = []
            for state in frontier:
                for nxt in step(*state):
                    if nxt not in seen:
                        seen.add(nxt)
                        reached.append(nxt)
            scoped = [n for n, _ in reached if scopes.get(n)]
            if scoped:
                return scopes[min(scoped, key=lambda n: self.ins[n].order)]
            if not reached:
                break
            frontier = reached
        return ""


def unscoped_map(hlo_text: str, scopes: Optional[Dict[str, str]] = None) -> Dict[str, List[str]]:
    """{instruction: [kind, serves]} for every instruction of a compiled
    module's text that has no scope and can run as a device operation of its
    own: not a ``parameter``, ``constant``, ``tuple``, ``get-tuple-element``
    or ``bitcast``, not inside a fused computation or a reduction's, and not a
    ``while`` / ``conditional`` / ``call``, which is its body's instructions.
    ``kind`` is ``kind_of`` the instruction, or of the computation a fusion
    calls. ``serves`` is the scope of the nearest scoped instruction the value
    is handed to, through unscoped instructions, through tuples by index and
    across a loop's or a conditional's boundary (a forward scan's
    ``dynamic-update-slice`` feeds only the loop's root tuple: it serves the
    scope that reads the stack in the backward loop); where nothing that
    uses it is scoped, the nearest that it was made from; else ``""``."""
    return _account(_parse(hlo_text), scope_map(hlo_text) if scopes is None else scopes)


def program_maps(hlo_text: str) -> Tuple[Dict[str, str], Dict[str, List[str]]]:
    """(``scope_map``, ``unscoped_map``) of a compiled module's text, from
    one reading of it."""
    scopes: Dict[str, str] = {}
    parsed = _parse(hlo_text, scopes)
    return scopes, _account(parsed, scopes)


def _account(parsed, scopes: Dict[str, str]) -> Dict[str, List[str]]:
    ins, comps, entry = parsed
    executed = _executed(ins, comps, entry)
    flow = _Flow(ins, comps, executed)
    out: Dict[str, List[str]] = {}
    for comp in executed:
        for name in comps[comp]:
            i = ins[name]
            if scopes.get(name) or i.opcode in _NO_EVENT:
                continue
            held = [(i.opcode, i.shape)]
            if "calls" in i.called:
                held = [(ins[n].opcode, ins[n].shape) for n in comps.get(i.called["calls"][0], ())]
            serves = (flow.nearest(name, flow.onward, scopes)
                      or flow.nearest(name, flow.backward, scopes))
            out[name] = [kind_of(held), serves]
    return out


_MEMORY = {
    "peak_bytes": "peak_memory_in_bytes", "temp_bytes": "temp_size_in_bytes",
    "argument_bytes": "argument_size_in_bytes", "output_bytes": "output_size_in_bytes",
    "alias_bytes": "alias_size_in_bytes",
}


def memory_of(compiled) -> Dict[str, int]:
    """What the backend's ``memory_analysis()`` says of a compiled program,
    each figure only where the backend gives it (the CPU gives no peak)."""
    try:
        stats = compiled.memory_analysis()
    except (NotImplementedError, RuntimeError):  # a backend without the analysis: no figures
        return {}
    return {key: int(getattr(stats, attr)) for key, attr in _MEMORY.items()
            if getattr(stats, attr, None) is not None}


_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}


def table_layout_of(hlo_text: str, table) -> Dict[str, str]:
    """The entry and the result layout of the pass table (``table``: its
    shape and dtype) as a compiled superstep's text states them, e.g.
    ``{"in": "{1,0:T(8,128)}", "out": "{1,0:T(8,128)}"}``: the first argument
    and the first result of that shape in ``entry_computation_layout``. Equal
    means the table crosses a dispatch as the loop carries it; {} where the
    text names no such layout."""
    head = hlo_text[: hlo_text.find("\n")]
    at = head.find("entry_computation_layout={(")
    cut = head.find(")->", at)
    dtype = _HLO_DTYPE.get(str(table.dtype))
    if at < 0 or cut < 0 or dtype is None:
        return {}
    shape = re.compile(
        re.escape(f"{dtype}[{','.join(map(str, table.shape))}]") + r"(\{[^}]*\})"
    )
    found = shape.search(head, at, cut), shape.search(head, cut)
    if None in found:
        return {}
    return {"in": found[0].group(1), "out": found[1].group(1)}


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

    def record(self, name: str, fun_name: str, hlo_text: str,
               memory: Optional[Dict[str, int]] = None,
               table_layout: Optional[Dict[str, str]] = None) -> dict:
        scopes, unscoped = program_maps(hlo_text)
        with self._lock:
            build = self._build.get(fun_name)
            entry = {
                "fun_name": fun_name, "instructions": len(scopes),
                "scopes": scopes, "unscoped": unscoped, "memory": dict(memory or {}),
                "table_layout": dict(table_layout or {}),
                **(build or {}),
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
