"""The grouped expert product's output kept across the layer checkpoint
(``models/moe.py::KEEP_EXPERTS``). An expert layer's checkpoint keeps the
output by name; where the layer's backward reads it (Trinity's norm after the
expert sum, Xing4's ``H_post`` map of post_res) the recomputation's pass over
the blocks is then dead code, and where only an add reads it (SmallThinker,
SDAR, GLM) the backward never needed it and the program is the one it was.
Compiled on the CPU at ``tests/test_fused_attention.py::KEEPERS``' shapes,
with the policy as shipped and taken back to ``KEEP_SCORES``."""

from __future__ import annotations

import re
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddlebox_tpu.ops.pallas_kernels import KEEP_SCORES  # noqa: E402
from tests.test_fused_attention import KEEPERS  # noqa: E402

# name -> whether the layer's backward reads the expert output; each model has one scan body of
# expert layers (GLM's MTP module is a second checkpoint, its output read by an add alone)
READS_OUTPUT = {"trinity": True, "xing4": True, "smallthinker": False, "sdar": False, "glm": False}
_COMPILED = {}


def _compiled(name: str, kept: bool):
    """(the model, ``value_and_grad(apply)`` compiled on the CPU) with the
    expert layers' policy as shipped or ``KEEP_SCORES``; compiled once a
    module."""
    if (name, kept) not in _COMPILED:
        model = KEEPERS[name][0]()
        with pytest.MonkeyPatch.context() as mp:
            if not kept:
                mp.setattr(sys.modules[type(model).__module__], "KEEP_EXPERTS", KEEP_SCORES)
            f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
            params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            _COMPILED[name, kept] = model, jax.jit(jax.value_and_grad(
                model.apply, argnums=(0, 1), has_aux=True)).lower(
                params, f32(1, 256, 64), f32(1, 256)).compile()
    return _COMPILED[name, kept]


def _loops(compiled) -> int:
    return len(re.findall(r"\) while\(%", compiled.as_text()))


@pytest.mark.parametrize("name", sorted(READS_OUTPUT))
def test_the_expert_forward_runs_once_where_the_layers_backward_reads_its_output(name):
    """Trinity's and Xing4's compiled gradients hold one loop fewer (the
    recomputed pass over the blocks of their one expert scan body); the other
    three hold as many loops as without the name."""
    kept, recomputed = (_loops(_compiled(name, k)[1]) for k in (True, False))
    assert kept == recomputed - READS_OUTPUT[name], (kept, recomputed)


@pytest.mark.parametrize("name", ["trinity", "xing4"])
def test_the_kept_output_gives_the_recomputed_ones_loss_and_gradient_bit_for_bit(name):
    """The kept output is the value the recomputation gives, from the same
    operations on the same inputs: loss, counters and every gradient leaf
    (parameters and rows) equal bit for bit."""
    model, kept = _compiled(name, True)
    recomputed = _compiled(name, False)[1]
    params = model.init(jax.random.PRNGKey(0))
    emb = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 64), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, 256), 0, 64).astype(jnp.float32)
    got, want = kept(params, emb, ids), recomputed(params, emb, ids)
    assert np.isfinite(float(got[0][0]))
    leaves = jax.tree.leaves(got)
    assert len(leaves) == len(jax.tree.leaves(want)) and any(np.any(a != 0) for a in leaves[1:])
    assert all(np.array_equal(a, b) for a, b in zip(leaves, jax.tree.leaves(want)))
