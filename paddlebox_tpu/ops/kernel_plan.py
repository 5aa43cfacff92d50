"""Per-shape kernel implementation selection for the sparse hot path.

The pull/push hot path has two implementations per op — XLA's native
gather/scatter lowering and the hand-tuned Pallas row-DMA kernels
(ops/pallas_kernels.py) — and the winner is SHAPE-DEPENDENT: the measured
v5p numbers (pallas_kernels.py docstring) have XLA winning at the CTR
flagship shape while per-row DMA amortizes better at wide rows, and the
scatter-sweep non-monotonicity (tools/op_probe.py, SCATTER_NOTES) says the
crossover moves with table width. A single hand-picked heuristic (the old
``_use_pallas``: one bool flag + alignment check) can't express that, so
selection is a REGISTRY lookup instead:

    (op, backend, shape bucket: table rows x width x batch-unique-keys)
        -> implementation {"native", "pallas"}

Plans load from a JSON artifact (``kernel_plan_path`` flag; the committed
default is ``tools/kernel_plan.json``, regenerated from op_probe sweep
artifacts by ``tools/tune_kernels.py``) with deterministic built-in
defaults when no artifact exists. Row and unique-key counts bucket to
ceil-log2 so a plan entry covers a 2x shape band — the same pad-bucket
granularity the batch packer already quantizes to (``batch_bucket_rounding``
keeps repeated shapes compile-cache-stable, so per-bucket choice is also
per-compilation choice).

Correctness constraints are enforced HERE, not trusted to the artifact: a
plan may *prefer* pallas, but selection clamps to native unless the backend
is TPU, the width is lane-aligned, the index count is block-aligned, and
(push only) rows are unique — a hand-edited artifact can never route an
ineligible shape into a kernel that would miscompile.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from paddlebox_tpu.utils.monitor import STAT_ADD

# Mosaic alignment facts the Pallas kernels require (pallas_kernels.py
# imports these back, so the eligibility clamp and the kernels themselves
# can never disagree): rows must be DMA-sliceable out of a lane-tiled HBM
# memref (width % LANE == 0) and the grid unrolls BLK rows per step.
PALLAS_LANE = 128
PALLAS_BLK = 8

OPS = ("pull", "push")
IMPLS = ("native", "pallas")

PLAN_VERSION = 1


def log2_bucket(n: int) -> int:
    """Ceil-log2 shape bucket: all n in (2^(k-1), 2^k] share bucket k."""
    n = int(n)
    if n <= 1:
        return 0
    return (n - 1).bit_length()


def current_backend() -> str:
    """The default jax backend name."""
    import jax

    return jax.default_backend()


# lookup probe order per (op, backend): exact bucket first, then wildcard
# uniq, wildcard rows, width-only, and finally the (op, backend) catch-all
_PROBE_ORDER = (
    (True, True, True),
    (True, True, False),
    (True, False, True),
    (True, False, False),
    (False, False, False),
)


@dataclass(frozen=True)
class PlanEntry:
    """One routing decision. ``None`` fields are wildcards."""

    op: str
    backend: str
    impl: str
    width: Optional[int] = None
    rows_log2: Optional[int] = None
    uniq_log2: Optional[int] = None
    why: str = ""

    def key(self) -> Tuple:
        return (self.op, self.backend, self.width, self.rows_log2, self.uniq_log2)

    def as_dict(self) -> Dict:
        d = {"op": self.op, "backend": self.backend, "impl": self.impl}
        for f in ("width", "rows_log2", "uniq_log2"):
            v = getattr(self, f)
            if v is not None:
                d[f] = v
        if self.why:
            d["why"] = self.why
        return d


@dataclass
class KernelPlan:
    """Immutable-after-construction (op, backend, shape-bucket) -> impl map.

    ``fallback`` is the impl preferred when no entry matches — "native" by
    default; ``default_plan`` maps the legacy ``use_pallas_sparse`` flag to
    a pallas fallback so the old opt-in keeps working bit-for-bit.
    """

    entries: List[PlanEntry] = field(default_factory=list)
    fallback: str = "native"
    source: str = "builtin-default"

    def __post_init__(self):
        if self.fallback not in IMPLS:
            raise ValueError(f"fallback {self.fallback!r} not in {IMPLS}")
        self._index: Dict[Tuple, str] = {}
        for e in self.entries:
            if e.op not in OPS:
                raise ValueError(f"plan entry op {e.op!r} not in {OPS}")
            if e.impl not in IMPLS:
                raise ValueError(f"plan entry impl {e.impl!r} not in {IMPLS}")
            k = e.key()
            if k in self._index:
                raise ValueError(f"duplicate plan entry for {k}")
            self._index[k] = e.impl

    # ---- selection -------------------------------------------------------

    def preferred(
        self, op: str, backend: str, n_rows: int, width: int, n_idx: int
    ) -> str:
        """Registry answer BEFORE the eligibility clamp (artifact intent)."""
        r, u = log2_bucket(n_rows), log2_bucket(n_idx)
        for use_w, use_r, use_u in _PROBE_ORDER:
            k = (
                op,
                backend,
                width if use_w else None,
                r if use_r else None,
                u if use_u else None,
            )
            impl = self._index.get(k)
            if impl is not None:
                return impl
        return self.fallback

    def select(
        self,
        op: str,
        backend: str,
        n_rows: int,
        width: int,
        n_idx: int,
        unique_rows: bool = True,
    ) -> str:
        """Implementation for one op instance; deterministic in its inputs.

        Runs at trace time (shapes are static), so the returned choice is
        baked into the compiled program — one selection per compilation,
        not per step.
        """
        if op not in OPS:
            raise ValueError(f"unknown op {op!r}; known: {OPS}")
        impl = self.preferred(op, backend, n_rows, width, n_idx)
        if impl == "pallas" and not pallas_eligible(
            op, backend, width, n_idx, unique_rows
        ):
            STAT_ADD("kernel_plan.pallas_clamped")
            impl = "native"
        STAT_ADD("kernel_plan.selects")
        if impl == "pallas":
            STAT_ADD("kernel_plan.selects_pallas")
        return impl

    # ---- (de)serialization ----------------------------------------------

    def to_json(self) -> Dict:
        return {
            "version": PLAN_VERSION,
            "fallback": self.fallback,
            "source": self.source,
            "entries": [e.as_dict() for e in self.entries],
        }

    @classmethod
    def from_json(cls, doc: Dict, source: str = "json") -> "KernelPlan":
        if int(doc.get("version", PLAN_VERSION)) != PLAN_VERSION:
            raise ValueError(
                f"kernel plan version {doc.get('version')} != {PLAN_VERSION}"
            )
        entries = [
            PlanEntry(
                op=e["op"],
                backend=e["backend"],
                impl=e["impl"],
                width=e.get("width"),
                rows_log2=e.get("rows_log2"),
                uniq_log2=e.get("uniq_log2"),
                why=e.get("why", ""),
            )
            for e in doc.get("entries", [])
        ]
        return cls(
            entries=entries,
            fallback=doc.get("fallback", "native"),
            source=doc.get("source", source),
        )

    def save(self, path: str) -> None:
        from paddlebox_tpu.utils.fs import atomic_write

        with atomic_write(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "KernelPlan":
        with open(path) as f:
            doc = json.load(f)
        plan = cls.from_json(doc)
        # operational provenance: artifacts that embed plan.source must say
        # which FILE routed the run; the file's own "source" field keeps the
        # generation story (tune_kernels invocation) inside the artifact
        plan.source = path
        return plan


def pallas_eligible(
    op: str, backend: str, width: int, n_idx: int, unique_rows: bool = True
) -> bool:
    """Hard constraints for routing into the Pallas kernels (see module
    docstring; these are correctness bounds, not preferences)."""
    if backend != "tpu":
        return False
    if width % PALLAS_LANE != 0 or n_idx % PALLAS_BLK != 0:
        return False
    if op == "push" and not unique_rows:
        # the pallas writeback is per-row SET: duplicates with differing
        # contents would be last-write-wins instead of merged
        return False
    return True


def default_plan() -> KernelPlan:
    """Deterministic built-in plan.

    Maps the legacy ``use_pallas_sparse`` opt-in onto the registry: flag on
    -> prefer pallas everywhere it is eligible (the old gate's exact
    semantics, alignment clamp included); flag off -> native everywhere.
    """
    from paddlebox_tpu import config

    prefer_pallas = bool(config.get_flag("use_pallas_sparse"))
    return KernelPlan(
        entries=[],
        fallback="pallas" if prefer_pallas else "native",
        source="builtin-default"
        + (":use_pallas_sparse" if prefer_pallas else ""),
    )


# ---- process-wide cached plan ------------------------------------------
#
# Selection runs on the jit trace path, so the plan must be a cheap dict
# lookup: resolve (flag -> file -> plan) once and cache until the flag or
# the opt-in changes. invalidate_plan() drops the cache (tests, re-tune).

_lock = threading.Lock()
_cached: Optional[Tuple[Tuple, KernelPlan]] = None  # guarded-by: _lock


def _default_artifact_path() -> str:
    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    return os.path.join(repo, "tools", "kernel_plan.json")


def resolve_plan_path(flag_value: str) -> Optional[str]:
    """kernel_plan_path flag -> artifact path or None (builtin defaults).

    "auto" uses the committed tools/kernel_plan.json when present; "" / "off"
    forces the builtin defaults; anything else is an explicit path and must
    exist — a typo'd path silently falling back would un-tune the hot path.
    """
    v = (flag_value or "").strip()
    if v in ("", "off", "none"):
        return None
    if v == "auto":
        p = _default_artifact_path()
        return p if os.path.exists(p) else None
    if not os.path.exists(v):
        raise FileNotFoundError(
            f"kernel_plan_path={v!r} does not exist (use 'auto' or 'off' "
            "for defaults)"
        )
    return v


def get_plan() -> KernelPlan:
    """The active plan (cached; keyed on the path flag + pallas opt-in)."""
    from paddlebox_tpu import config

    global _cached
    key = (
        str(config.get_flag("kernel_plan_path")),
        bool(config.get_flag("use_pallas_sparse")),
    )
    with _lock:
        if _cached is not None and _cached[0] == key:
            return _cached[1]
    path = resolve_plan_path(key[0])
    plan = KernelPlan.load(path) if path is not None else default_plan()
    with _lock:
        _cached = (key, plan)
    return plan


def invalidate_plan() -> None:
    """Drop the cached plan (next get_plan() re-resolves flag + file)."""
    global _cached
    with _lock:
        _cached = None
