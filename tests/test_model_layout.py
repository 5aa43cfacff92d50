"""The layering of ``paddlebox_tpu/models/``, read off the source with ``ast``:
the models stand on shared modules (``attention``, ``linear_attention``,
``moe``, ``lm_layers``, ``layers``, ``base``) and not on each other, no
module reaches into another's private names, and each kernel (the fused
attention's, the delta rule's recurrence) has one way in."""

from __future__ import annotations

import ast
import pathlib

import pytest

MODELS = pathlib.Path(__file__).resolve().parents[1] / "paddlebox_tpu" / "models"
SHARED = {"__init__", "attention", "base", "layers", "linear_attention", "lm_layers", "moe"}
# the one import of a model module by another: Xing4 subclasses GLM's model and config
ALLOWED = {("xing4", "glm_moe_lite")}
# the shared modules' own bfloat16 products, which every model's equations are built of
SHARED_PRIVATE = {("lm_layers", "_mm"), ("lm_layers", "_product")}


def _trees():
    return {p.stem: ast.parse(p.read_text(), str(p)) for p in sorted(MODELS.glob("*.py"))}


def _imports(tree):
    """(module under ``paddlebox_tpu``, name) for every name a module imports
    from the package; a module imported whole gives the name None."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and (node.module or "").startswith("paddlebox_tpu"):
            for alias in node.names:
                yield node.module, alias.name
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith("paddlebox_tpu"):
                    yield alias.name, None


def _model_modules_imported(tree, models):
    for module, name in _imports(tree):
        if module == "paddlebox_tpu.models" and name in models:
            yield name  # from paddlebox_tpu.models import afmoe
        elif module.startswith("paddlebox_tpu.models.") and module.split(".")[2] in models:
            yield module.split(".")[2]


def test_no_model_module_imports_another_but_xing4_glms():
    trees = _trees()
    models = set(trees) - SHARED
    assert {"glm_moe_lite", "afmoe", "smallthinker", "sdar", "xing4", "olmo_hybrid"} <= models
    edges = {(name, other) for name, tree in trees.items() if name != "__init__"  # the exports
             for other in _model_modules_imported(tree, models) if other != name}
    assert edges == ALLOWED
    for name in SHARED - {"__init__"}:  # and the shared modules import no model
        assert not set(_model_modules_imported(trees[name], models)), name


def test_no_module_imports_a_private_name_from_another():
    found = set()
    for name, tree in _trees().items():
        for module, imported in _imports(tree):
            if imported and imported.startswith("_") and imported != "__future__":
                found.add((module.split(".")[-1], imported))
    assert found <= SHARED_PRIVATE, found - SHARED_PRIVATE


@pytest.mark.parametrize("callee,where", [
    ("causal_attention", ("attention", "scores")),  # the one way into the fused kernel
    ("_attend_block", ("attention", "scores")),     # and its one oracle beside it
    ("fused", ("attention", "scores")),             # chosen by one rule
    ("delta_rule_recurrence", ("linear_attention", "delta_rule")),  # the recurrence's kernel,
    ("chunk_scan", ("linear_attention", "delta_rule")),             # its oracle
    ("fused_recurrence", ("linear_attention", "delta_rule")),       # and its rule
])
def test_the_kernel_its_oracle_and_its_rule_are_called_from_one_function(callee, where):
    calls = []
    for name, tree in _trees().items():
        for fn in ast.walk(tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                calls += [(name, fn.name) for node in ast.walk(fn) if isinstance(node, ast.Call)
                          and getattr(node.func, "id", getattr(node.func, "attr", None)) == callee]
    assert calls == [where]


def test_one_blocked_oracle_and_one_rule_are_defined():
    defs = [(name, fn.name) for name, tree in _trees().items() for fn in ast.walk(tree)
            if isinstance(fn, ast.FunctionDef) and (fn.name == "_attend_block" or "fused" in fn.name)]
    assert defs == [("attention", "_attend_block"), ("attention", "fused"),
                    ("linear_attention", "fused_recurrence")]
