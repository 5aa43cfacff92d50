"""The fused causal attention kernel (``ops/pallas_kernels.py::causal_attention``)
against the blocked form it replaces on a TPU (``models/glm_moe_lite.py::
_attend_block``, which stays as every other backend's path and is the oracle
here): interpret mode on the CPU at a small tiled shape, the rule that chooses
between the two, its counters, and the kernels compiled at the token cell's
shapes for a described v5e."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddlebox_tpu.models import glm_moe_lite as glm  # noqa: E402
from paddlebox_tpu.models import GlmMoeLite, GlmMoeLiteConfig  # noqa: E402
from paddlebox_tpu.ops.pallas_kernels import causal_attention  # noqa: E402
from paddlebox_tpu.utils.monitor import STAT_GET  # noqa: E402

B, T, H, D = 1, 256, 2, 128
SCALE = D ** -0.5
BLOCKS = [128, 256]  # queries and keys a tile: two tiles a side with a diagonal, and one


@pytest.fixture(scope="module")
def qkvg():
    ks = jax.random.split(jax.random.PRNGKey(29), 4)
    q, k, v = (jax.random.normal(a, (B, T, H, D)).astype(jnp.bfloat16) for a in ks[:3])
    return q, k, v, jax.random.normal(ks[3], (B, T, H, D))


def blocked(q, k, v):
    return jnp.concatenate(
        [glm._attend_block(q, k, v, i, 128, SCALE) for i in range(0, T, 128)], axis=1)


def _rel(a, b) -> float:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("block", BLOCKS)
def test_output_matches_the_blocked_form_within_bfloat16_round_off(qkvg, block):
    q, k, v, _ = qkvg
    o, want = causal_attention(q, k, v, SCALE, block, True), blocked(q, k, v)
    assert o.dtype == jnp.float32 and o.shape == want.shape
    # the two round p to bfloat16 at different scales (normalised there, under the running
    # maximum here): 2**-9 an element, far less over a row's sum
    assert _rel(o, want) < 2e-3
    assert float(jnp.max(jnp.abs(o - want))) < 2e-2


@pytest.mark.parametrize("block", BLOCKS)
def test_gradients_match_the_blocked_form_within_bfloat16_round_off(qkvg, block):
    q, k, v, g = qkvg
    grad = lambda f: jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * g), argnums=(0, 1, 2))(q, k, v)  # noqa: E731
    got = grad(lambda q, k, v: causal_attention(q, k, v, SCALE, block, True))
    for a, b in zip(got, grad(blocked)):
        assert a.dtype == b.dtype == jnp.bfloat16  # as `_product` hands them back
        assert _rel(a, b) < 6e-3  # each side rounds its result to bfloat16 (2**-9 an element)


@pytest.mark.parametrize("block", BLOCKS)
def test_a_later_key_leaves_an_earlier_querys_output_bit_equal(qkvg, block):
    q, k, v, _ = qkvg
    at = 130  # inside the second key tile: a diagonal tile and a wholly visible one see it
    k2, v2 = k.at[:, at:].set(k[:, at:] * -3 + 1), v.at[:, at:].set(v[:, at:] + 7)
    o, o2 = (np.asarray(causal_attention(q, a, b, SCALE, block, True)) for a, b in ((k, v), (k2, v2)))
    assert np.array_equal(o[:, :at], o2[:, :at])
    assert not np.array_equal(o[:, at], o2[:, at])


@pytest.mark.parametrize("backend,t,qk,vd,block,fused", [
    ("tpu", 4096, 256, 256, 512, True),    # the token cell
    ("tpu", 256, 128, 128, 128, True),
    ("cpu", 4096, 256, 256, 512, False),   # tier-1, whatever the shape
    ("gpu", 4096, 256, 256, 512, False),
    ("tpu", 64, 16, 16, 8, False),         # the toy token cell's widths
    ("tpu", 4096, 192, 128, 512, False),   # the published inference widths: q/k and v differ
    ("tpu", 4096, 256, 256, 64, False),    # a query block the kernel does not tile
    ("tpu", 4096, 320, 320, 512, False),   # a head that is no multiple of a lane row
])
def test_the_path_is_chosen_from_backend_and_shapes(backend, t, qk, vd, block, fused):
    assert glm.fused_scores(backend, t, qk, vd, block) is fused


def _mla_call(cfg: GlmMoeLiteConfig):
    p = GlmMoeLite(cfg)._attn_init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, cfg.seq_len, cfg.hidden_size))
    rope = glm.rope_tables(cfg.seq_len, cfg.qk_rope_head_dim, cfg.rope_theta)
    return lambda: jax.make_jaxpr(
        lambda p, x: glm.mla(p, x, jnp.ones((cfg.hidden_size,)), cfg, rope, "model"))(p, x)


TILED = GlmMoeLiteConfig(hidden_size=64, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
                         qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128, seq_len=256,
                         attn_block=128)


def test_each_call_site_is_counted_under_the_path_it_was_lowered_to(monkeypatch):
    stats = lambda: (STAT_GET("model.mla.fused_scores"), STAT_GET("model.mla.blocked_scores"))  # noqa: E731
    trace = _mla_call(TILED)
    f0, b0 = stats()
    assert "pallas_call" not in str(trace())  # the CPU: the blocked form, at any shape
    assert stats() == (f0, b0 + 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = str(trace())
    assert stats() == (f0 + 1, b0 + 1)
    assert text.count("pallas_call") == 1 and "causal_attention_fwd" in text
    # shapes the kernel does not tile stay blocked on a TPU as well
    assert "pallas_call" not in str(_mla_call(GlmMoeLiteConfig(
        hidden_size=64, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12,
        qk_rope_head_dim=4, v_head_dim=16, seq_len=64, attn_block=8))())
    assert stats() == (f0 + 1, b0 + 2)


def test_mla_through_the_kernel_agrees_with_mla_through_the_blocks(monkeypatch):
    """The whole attention block both ways (the kernel interpreted): the
    layout in and out of the kernel, the scale and the head split."""
    cfg = TILED
    p = GlmMoeLite(cfg)._attn_init(jax.random.PRNGKey(1))
    p = jax.tree.map(lambda a: a * 20 if a.ndim == 2 else a, p)  # scores of order 1, not 1e-3
    x = jax.random.normal(jax.random.PRNGKey(2), (2, cfg.seq_len, cfg.hidden_size))
    rope = glm.rope_tables(cfg.seq_len, cfg.qk_rope_head_dim, cfg.rope_theta)
    run = lambda: jax.value_and_grad(lambda x: jnp.sum(  # noqa: E731
        glm.mla(p, x, jnp.ones((cfg.hidden_size,)), cfg, rope, "model") ** 2))(x)
    want, dwant = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(glm, "causal_attention",
                        lambda q, k, v, s, block: causal_attention(q, k, v, s, block, True))
    got, dgot = run()
    assert float(got) == pytest.approx(float(want), rel=1e-3)
    assert _rel(dgot, dwant) < 1e-2


# ---- compiled for the chip, without the chip ---------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_both_kernels_compile_for_a_v5e_at_the_token_cells_shapes(one_chip):
    from paddlebox_tpu.obs.program_scopes import scope_map

    shape = (2, 4096, 20, 256)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def step(q, k, v, g):
        with jax.named_scope("model/mla/scores"):
            return jax.grad(lambda q, k, v: jnp.sum(causal_attention(q, k, v, 256 ** -0.5, 512) * g),
                            argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(x, x, x, g).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    kernels = {n: s for n, s in scope_map(text).items() if "causal_attention" in n}
    assert len(kernels) == 2 and set(kernels.values()) == {"model/mla/scores"}, kernels
    # no score block in HBM: the program's temporaries are the statistics and the row term
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 * 4096 * 20 * 256 * 4
