"""Whole-program call graph + thread-entrypoint graph for pbox-lint.

PR 2's THR002 grades findings by an *intra-module* thread-reachability
approximation; the threaded planes added since (transport sender/reader/
heartbeat, serving follower/batcher, async dense, boundary prefetch) call
across module boundaries, so the flow-sensitive rules (THR006, and any
future one that needs "who runs this?") build on this pass instead.

The pass resolves, over the FULL scanned module set:

- every function/method/nested def as a :class:`FuncNode` with its owning
  class and module;
- an interprocedural call graph.  Resolution is deliberately conservative
  and name-based (no type inference):

    * ``f()``        -> def ``f`` in the same module, else any module-level
                        def ``f`` in the scanned set;
    * ``self.m()``   -> method ``m`` of the caller's class (class name
                        matched across modules, so mixins resolve);
    * ``obj.m()``    -> method ``m`` ONLY when exactly one class in the
                        scanned set defines it (unique-name resolution;
                        ambiguous names like ``get``/``close`` would
                        overlink the graph into uselessness), and never
                        when ``obj`` is a name the caller's module bound
                        with ``import`` (``json.load`` is no method);

- *thread entry points*: each ``threading.Thread(target=X)`` and
  ``executor.submit(X, ...)`` creation site mints a distinct thread label
  ``"path:lineno(target)"``.  A target spun in a loop (pollers, heartbeat)
  is still one label — the label means "an instance of this thread kind",
  and two *kinds* touching the same state is already a race;
- a ``runs_on`` set per function: the thread labels whose entry reaches it
  through the call graph, plus the synthetic label ``MAIN`` when the
  function is also reachable from non-thread code (module top level, a
  def nobody in the scanned set calls — i.e. API surface driven by the
  user's thread — or any function only reachable from those);
- ``locks_held_in``: the set of lock names guaranteed held on EVERY path
  from an entry to the function (meet-over-paths with set intersection),
  seeded from ``with <lock>:`` blocks around call sites.  Only context
  managers whose expression looks lock-like (``lock``/``mutex``/``cond``/
  ``sem``, case-insensitive) count — ``with inject(...)`` or file handles
  never satisfy a lock requirement.

Everything here is a static approximation; the docstrings of the rules
that consume it state which side (over- or under-) each choice errs on.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

from .core import ModuleCtx

MAIN = "<main>"

_LOCKISH_RE = re.compile(r"lock|mutex|cond|sem", re.IGNORECASE)


def _is_lockish(expr_text: str) -> bool:
    return bool(_LOCKISH_RE.search(expr_text))


@dataclass
class FuncNode:
    """One def (function, method, or nested def) in the scanned set."""

    module: str  # ModuleCtx.path
    cls: Optional[str]  # owning class name (nested defs inherit it)
    name: str
    qualname: str  # "module.py::Class.method" / "module.py::fn.inner"
    node: ast.AST = field(repr=False)
    host: Optional[int] = None  # id() of the enclosing def, for nested defs
    # resolved out-edges: (callee id, locks held at the call site)
    out: List[Tuple[int, FrozenSet[str]]] = field(default_factory=list)
    runs_on: Set[str] = field(default_factory=set)
    locks_held_in: FrozenSet[str] = frozenset()

    @property
    def key(self) -> int:
        return id(self.node)


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    # unparse failure only degrades lock-name resolution, never a training
    # path  # pbox-lint: disable=EXC007
    except Exception:  # pragma: no cover - malformed synthetic nodes only
        return ""


class _FuncCollector:
    """Collects every def with ownership, mirroring rules_locks' walk but
    keeping nested-def host links (a nested def runs on its host's
    thread when called locally)."""

    def __init__(self, ctx: ModuleCtx):
        self.ctx = ctx
        self.funcs: List[FuncNode] = []

    def collect(self) -> List[FuncNode]:
        self._walk(self.ctx.tree, None, "", None)
        return self.funcs

    def _walk(self, node, cls, prefix, host) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                self._walk(child, child.name, f"{prefix}{child.name}.", host)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn = FuncNode(
                    module=self.ctx.path,
                    cls=cls,
                    name=child.name,
                    qualname=f"{self.ctx.path}::{prefix}{child.name}",
                    node=child,
                    host=host,
                )
                self.funcs.append(fn)
                self._walk(child, cls, f"{prefix}{child.name}.", id(child))
            else:
                self._walk(child, cls, prefix, host)


@dataclass
class ThreadEntry:
    label: str  # "path:lineno(target_name)"
    target_ids: List[int]  # resolved FuncNode keys


class CallGraph:
    """The resolved whole-program graph; built once per lint run and shared
    by every rule that needs thread or lock flow."""

    def __init__(self, modules: Sequence[ModuleCtx]):
        self.modules = list(modules)
        self.funcs: List[FuncNode] = []
        for ctx in self.modules:
            self.funcs.extend(_FuncCollector(ctx).collect())
        self.by_key: Dict[int, FuncNode] = {f.key: f for f in self.funcs}
        # resolution indexes
        self._module_defs: Dict[Tuple[str, str], List[FuncNode]] = {}
        self._methods: Dict[Tuple[str, str], List[FuncNode]] = {}  # (cls, name)
        self._by_name: Dict[str, List[FuncNode]] = {}
        # module path -> names bound by ``import x`` / ``import x.y as z``
        self._imported: Dict[str, Set[str]] = {
            ctx.path: {
                (a.asname or a.name).split(".")[0]
                for node in ast.walk(ctx.tree)
                if isinstance(node, ast.Import)
                for a in node.names
            }
            for ctx in self.modules
        }
        for f in self.funcs:
            if f.cls is None and f.host is None:
                self._module_defs.setdefault((f.module, f.name), []).append(f)
            if f.cls is not None:
                self._methods.setdefault((f.cls, f.name), []).append(f)
            self._by_name.setdefault(f.name, []).append(f)
        self.entries: List[ThreadEntry] = []
        self._callers: Dict[int, List[int]] = {}
        self._build_edges()
        self._find_entries()
        self._propagate_threads()
        self._propagate_locks()

    # ---- resolution --------------------------------------------------------

    def resolve_call(self, caller: FuncNode, call: ast.Call) -> List[FuncNode]:
        fn = call.func
        if isinstance(fn, ast.Name):
            return self._resolve_name(caller, fn.id)
        if isinstance(fn, ast.Attribute):
            if isinstance(fn.value, ast.Name):
                if fn.value.id in ("self", "cls"):
                    return self._resolve_method(caller.cls, fn.attr)
                if fn.value.id in self._imported.get(caller.module, ()):
                    return []
            return self._resolve_unique_method(fn.attr)
        return []

    def _resolve_name(self, caller: FuncNode, name: str) -> List[FuncNode]:
        local = self._module_defs.get((caller.module, name))
        if local:
            return local
        # nested defs of the caller's own scope (closure calls)
        nested = [
            f
            for f in self.funcs
            if f.module == caller.module and f.name == name and f.host is not None
        ]
        if nested:
            return nested
        return [
            f
            for f in self._by_name.get(name, [])
            if f.cls is None and f.host is None
        ]

    def _resolve_method(self, cls: Optional[str], name: str) -> List[FuncNode]:
        if cls is not None:
            hits = self._methods.get((cls, name))
            if hits:
                return hits
        return self._resolve_unique_method(name)

    def _resolve_unique_method(self, name: str) -> List[FuncNode]:
        if name.startswith("__"):
            return []
        hits = [
            f for (_, n), fs in self._methods.items() if n == name for f in fs
        ]
        owning = {f.cls for f in hits}
        if len(owning) == 1:
            return hits
        return []

    # ---- graph construction ------------------------------------------------

    def _build_edges(self) -> None:
        for f in self.funcs:
            held: List[str] = []

            def visit(node: ast.AST) -> None:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if node is not f.node:
                        return  # nested defs get their own edges
                if isinstance(node, ast.With):
                    names = [
                        _unparse(item.context_expr.func)
                        if isinstance(item.context_expr, ast.Call)
                        else _unparse(item.context_expr)
                        for item in node.items
                    ]
                    lockish = [n for n in names if n and _is_lockish(n)]
                    held.extend(lockish)
                    for child in ast.iter_child_nodes(node):
                        visit(child)
                    del held[len(held) - len(lockish):]
                    return
                if isinstance(node, ast.Call):
                    for callee in self.resolve_call(f, node):
                        f.out.append((callee.key, frozenset(held)))
                        self._callers.setdefault(callee.key, []).append(f.key)
                for child in ast.iter_child_nodes(node):
                    visit(child)

            for stmt in getattr(f.node, "body", []):
                visit(stmt)
            # a nested def is conservatively assumed to run where its host
            # runs (local call or callback on the same thread)
            if f.host is not None and f.host in self.by_key:
                host = self.by_key[f.host]
                host.out.append((f.key, frozenset()))
                self._callers.setdefault(f.key, []).append(host.key)

    def _resolve_target(self, caller: FuncNode, t: ast.AST) -> List[FuncNode]:
        if isinstance(t, ast.Name):
            return self._resolve_name(caller, t.id)
        if isinstance(t, ast.Attribute):
            if isinstance(t.value, ast.Name) and t.value.id in ("self", "cls"):
                return self._resolve_method(caller.cls, t.attr)
            return self._resolve_unique_method(t.attr)
        if isinstance(t, ast.Lambda):
            return []  # lambda bodies are scanned via the host function
        return []

    def _find_entries(self) -> None:
        for f in self.funcs:
            for node in ast.walk(f.node):
                if not isinstance(node, ast.Call):
                    continue
                fname = (
                    node.func.attr
                    if isinstance(node.func, ast.Attribute)
                    else (node.func.id if isinstance(node.func, ast.Name) else None)
                )
                targets: List[ast.AST] = []
                if fname == "Thread":
                    for kw in node.keywords:
                        if kw.arg == "target":
                            targets.append(kw.value)
                elif fname == "submit" and node.args:
                    targets.append(node.args[0])
                for t in targets:
                    resolved = self._resolve_target(f, t)
                    if not resolved:
                        continue
                    label = (
                        f"{f.module}:{node.lineno}"
                        f"({_unparse(t) or 'target'})"
                    )
                    self.entries.append(
                        ThreadEntry(label=label, target_ids=[r.key for r in resolved])
                    )

    def _propagate_threads(self) -> None:
        # 1. each thread label floods its reachable set
        thread_reached: Set[int] = set()
        for entry in self.entries:
            frontier = list(entry.target_ids)
            seen: Set[int] = set()
            while frontier:
                k = frontier.pop()
                if k in seen:
                    continue
                seen.add(k)
                fn = self.by_key.get(k)
                if fn is None:
                    continue
                fn.runs_on.add(entry.label)
                for callee, _ in fn.out:
                    if callee not in seen:
                        frontier.append(callee)
            thread_reached |= seen

        # 2. MAIN floods from non-thread roots: every def that (a) nobody
        # in the scanned set calls and is not a thread target (API surface
        # the user drives), or (b) is called from module top level.  A def
        # reached ONLY as a thread target does not seed MAIN.
        thread_targets = {k for e in self.entries for k in e.target_ids}
        roots: List[int] = []
        for f in self.funcs:
            if f.key in thread_targets:
                continue
            if f.host is not None:
                continue  # nested defs run where their host runs
            if not self._callers.get(f.key):
                roots.append(f.key)
        self._main_roots: Set[int] = set(roots)
        frontier = roots
        seen_main: Set[int] = set()
        while frontier:
            k = frontier.pop()
            if k in seen_main:
                continue
            seen_main.add(k)
            fn = self.by_key.get(k)
            if fn is None:
                continue
            fn.runs_on.add(MAIN)
            for callee, _ in fn.out:
                if callee not in seen_main:
                    frontier.append(callee)

    def _propagate_locks(self) -> None:
        """Meet-over-paths: a lock counts as held *in* a function only when
        every resolved call edge into it (from an already-constrained
        caller) holds that lock.  Entries and MAIN roots start with
        nothing held."""
        UNIVERSE = None  # sentinel: unconstrained (no path seen yet)
        held: Dict[int, Optional[FrozenSet[str]]] = {
            f.key: UNIVERSE for f in self.funcs
        }
        # seed ONLY true roots (thread targets + the MAIN flood roots) with
        # nothing held — seeding every MAIN-running function would zero the
        # meet for callees whose every call site holds a lock
        entry_keys = {k for e in self.entries for k in e.target_ids}
        for f in self.funcs:
            if f.key in entry_keys or f.key in self._main_roots:
                held[f.key] = frozenset()
        changed = True
        iters = 0
        while changed and iters < 50:
            changed = False
            iters += 1
            for f in self.funcs:
                base = held[f.key]
                if base is UNIVERSE:
                    continue
                for callee, at_site in f.out:
                    incoming = frozenset(base | at_site)
                    cur = held.get(callee, UNIVERSE)
                    new = incoming if cur is UNIVERSE else (cur & incoming)
                    if new != cur:
                        held[callee] = new
                        changed = True
        for f in self.funcs:
            h = held[f.key]
            f.locks_held_in = frozenset() if h is None else h

    # ---- queries -----------------------------------------------------------

    def func_at(self, module: str, node: ast.AST) -> Optional[FuncNode]:
        return self.by_key.get(id(node))

    def functions_in(self, module: str) -> List[FuncNode]:
        return [f for f in self.funcs if f.module == module]


_CACHE: Dict[int, CallGraph] = {}


def get_callgraph(modules: Sequence[ModuleCtx]) -> CallGraph:
    """Build (or reuse) the graph for this exact module list — several
    rules share one lint run's graph, and the build is the expensive part
    of whole-program linting."""
    key = hash(tuple(id(m) for m in modules))
    cg = _CACHE.get(key)
    if cg is None:
        _CACHE.clear()  # one live graph: runs never interleave
        cg = CallGraph(modules)
        _CACHE[key] = cg
    return cg
