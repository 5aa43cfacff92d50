"""The gated delta rule's chunk-to-chunk recurrence as a Pallas kernel
(``ops/pallas_kernels.py::delta_rule_recurrence``) against the chunk scan it
replaces on a TPU (``models/linear_attention.py::chunk_scan``, every other
backend's path and the oracle here): interpret mode on the CPU, forward and
the cotangents of all six operands, at one chunk and at eight, one head and
three, the Olmo-Hybrid cell's widths (96 / 192) and lane-whole ones, beta
near 2 over alike keys and decays near 1; ``delta_rule``'s gradient through
the kernel; the one rule that chooses between the two (``fused_recurrence``),
its counters, and the model's CPU program held to what it was before the
kernel. The kernel compiled for a described v5e is in
``tests/test_fused_attention.py``, beside the other kernels' compiles."""

from __future__ import annotations

import hashlib
import re
from functools import partial

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.models import olmo_hybrid as build  # noqa: E402
from benchmark.tests import toy_olmo_hybrid  # noqa: E402
from paddlebox_tpu.models import linear_attention as la  # noqa: E402
from paddlebox_tpu.ops.pallas_kernels import delta_rule_recurrence  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402

C = 64  # the cell's chunk
REGIMES = {  # (beta's range, the log-decay's range, how alike the keys are: 0 random, 1 one key)
    "beta_near_2_alike_keys": ((1.9, 2.0), (-1.0, 0.0), 0.9),
    "decays_near_1": ((0.0, 2.0), (-0.01, 0.0), 0.0),
}


def _inputs(T: int, H: int, dk: int, dv: int, regime: str, seed: int = 0):
    """delta_rule's q, k, v, beta, g [1, T, H, ...] and an output cotangent."""
    (blo, bhi), (glo, ghi), alike = REGIMES[regime]
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = la.l2_normalize(jax.random.normal(ks[0], (1, T, H, dk))) * dk ** -0.5
    base = jax.random.normal(ks[1], (1, 1, H, dk))
    k = la.l2_normalize(alike * base + (1 - alike) * jax.random.normal(ks[2], (1, T, H, dk)))
    v = jax.random.normal(ks[3], (1, T, H, dv))
    beta = jax.random.uniform(ks[4], (1, T, H), minval=blo, maxval=bhi)
    g = jax.random.uniform(ks[5], (1, T, H), minval=glo, maxval=ghi)
    return (q, k, v, beta, g), jax.random.normal(ks[6], (1, T, H, dv))


def _rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


@pytest.mark.parametrize("regime", sorted(REGIMES))
@pytest.mark.parametrize("dk,dv", [(96, 192), (128, 128)])
@pytest.mark.parametrize("H", [1, 3])
@pytest.mark.parametrize("T", [64, 512])
def test_the_kernel_is_the_chunk_scan_forward_and_for_all_six_cotangents(T, H, dk, dv, regime):
    args, cot = _inputs(T, H, dk, dv, regime)
    operands = la.chunk_operands(*args, C)
    do = jnp.moveaxis(jnp.swapaxes(cot.reshape(1, T // C, C, H, dv), 2, 3), 1, 0)
    kernel = partial(delta_rule_recurrence, interpret=True)
    scan = partial(la.chunk_scan, scope="s")
    (o, vjp), (want, want_vjp) = jax.vjp(kernel, *operands), jax.vjp(scan, *operands)
    assert o.dtype == jnp.float32 and o.shape == want.shape == (T // C, 1, H, C, dv)
    assert _rel(o, want) < 1e-5
    for name, a, b in zip(("W", "U0", "P", "Qd", "Kd", "last"), vjp(do), want_vjp(do)):
        assert a.shape == b.shape and a.dtype == jnp.float32, name
        if T == C and name == "last":  # one chunk: its state enters at zero, nothing flows back
            assert not jnp.any(a) and not jnp.any(b)
            continue
        assert _rel(a, b) < 1e-5, name


def test_every_product_of_both_kernels_is_float32_at_highest():
    """The configuration's precision: the forward's four products and the
    backward's nine (U recomputed, two for dU, dP, dQd, dKd, dW, two for dM)
    take float32 operands at ``highest`` with float32 results, as the scan's do."""
    f32 = lambda *s: jax.ShapeDtypeStruct((2, 1, 3) + s, jnp.float32)  # noqa: E731
    text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(delta_rule_recurrence(*a)), argnums=range(6)))(
        f32(64, 96), f32(64, 192), f32(64, 64), f32(64, 96), f32(64, 96), f32()))
    assert text.count("dot_general[") == 4 + 9
    assert text.count("precision=") == text.count("precision=(Precision.HIGHEST, Precision.HIGHEST)")
    assert text.count("precision=") == text.count("preferred_element_type=float32") == 4 + 9
    assert "bf16" not in text


def _through(monkeypatch, backend: str):
    """``delta_rule`` as a model calls it, on ``backend``: the kernel in interpret mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(la, "delta_rule_recurrence", partial(delta_rule_recurrence, interpret=True))
    return lambda *a: la.delta_rule(*a, C, "model/linear_attn/delta_rule")


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_delta_rules_gradient_through_the_kernel_is_the_scans(monkeypatch, regime):
    args, cot = _inputs(256, 2, 96, 192, regime, seed=1)
    grads = {}
    for backend in ("tpu", "cpu"):
        rule = _through(monkeypatch, backend)
        before = STAT_GET("model.linear_attn.fused_sites")
        grads[backend] = jax.value_and_grad(lambda *a: jnp.sum(rule(*a) * cot), argnums=range(5))(*args)
        assert STAT_GET("model.linear_attn.fused_sites") - before == (backend == "tpu")
    (loss, got), (want_loss, want) = grads["tpu"], grads["cpu"]
    assert abs(float(loss - want_loss)) <= 1e-5 * abs(float(want_loss))
    for name, a, b in zip(("q", "k", "v", "beta", "g"), got, want):
        assert _rel(a, b) < 1e-5, name


@pytest.mark.parametrize("backend,chunk,dk,dv,heads,fused", [
    ("tpu", 64, 96, 192, 10, True),     # the Olmo-Hybrid cell: 10 of 30 heads
    ("tpu", 64, 128, 128, 10, True),    # lane-whole widths
    ("tpu", 8, 8, 16, 2, True),         # the toy cell's widths
    ("cpu", 64, 96, 192, 10, False),    # tier-1, whatever the shape
    ("gpu", 64, 96, 192, 10, False),
    ("tpu", 12, 96, 192, 10, False),    # a chunk of no whole sublane rows
    ("tpu", 64, 100, 192, 10, False),   # keys of no whole sublane rows
    ("tpu", 64, 96, 192, 30, False),    # all 30 heads: a step's blocks over half the VMEM
    ("tpu", 256, 128, 256, 10, False),  # chunks of 256: the same
])
def test_the_recurrence_path_is_chosen_from_backend_and_shapes(backend, chunk, dk, dv, heads, fused):
    assert la.fused_recurrence(backend, chunk, dk, dv, heads) is fused


TINY = toy_olmo_hybrid.cell()["cfg"]


def _model_jaxpr() -> str:
    """The toy cell's loss and gradient of every leaf and of the rows, as a jaxpr's text."""
    model = build.build(TINY, 3 + TINY["hidden_size"])
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, T, H = TINY["batch_size"], TINY["seq_len"], TINY["hidden_size"]
    return str(jax.make_jaxpr(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True))(
        params, f32(B, T, H), f32(B, T)))


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_olmo_hybrids_linear_layers_count_the_form_they_were_lowered_to(monkeypatch, backend):
    """One linear layer traced in the period's body: the CPU's adds to
    ``chunked_sites`` alone, a TPU's (stubbed) to ``fused_sites`` alone, and
    only the latter holds the kernels: the forward twice (the layer's
    checkpoint keeps nothing, so the backward recomputes it), the backward
    once."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    stats = ("model.linear_attn.chunked_sites", "model.linear_attn.fused_sites")
    before = [STAT_GET(s) for s in stats]
    text = _model_jaxpr()
    assert [STAT_GET(s) - b for s, b in zip(stats, before)] == ([0, 1] if backend == "tpu" else [1, 0])
    calls = [len(re.findall(rf"name=delta_rule_recurrence_{d}\b", text)) for d in ("fwd", "bwd")]
    assert calls == ([2, 1] if backend == "tpu" else [0, 0])


def test_olmo_hybrids_cpu_program_is_the_one_it_was_before_the_kernel():
    """The toy cell's jaxpr on the CPU, source locations and function
    addresses aside, is the one the tree had before the kernel was there:
    every other backend runs the chunk scan as it did."""
    text = re.sub(r" at 0x[0-9a-f]+", "", re.sub(r"\S+\.py:\d+", "", _model_jaxpr()))
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == "d66130aeb8a952c221491bf876ad4858f42f473fd32a6fba11352c26eb53bc48"
