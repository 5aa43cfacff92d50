"""The fused causal attention kernel (``ops/pallas_kernels.py::causal_attention``)
against the blocked form it replaces on a TPU (``models/attention.py::
_attend_block``, which stays as every other backend's path and is the oracle
here): interpret mode on the CPU at a small tiled shape, the one rule that
chooses between the two for every model (``attention.fused``), its counters,
and the kernels compiled at the token cells' shapes for a described v5e: with
grouped-query heads and a window (``models/afmoe.py``), at a group that is no
power of two (7, ``models/smallthinker.py``) and that model's 16k shapes, under
the block-diffusion mask (``diffusion_block``, ``models/sdar.py``), and at
query/key heads of another width than the value heads (192 and 128,
``models/xing4.py``), and the full layers of a stack that is three parts
linear attention (``models/olmo_hybrid.py``); every older call held to the
kernel it had."""

from __future__ import annotations

import hashlib
import json
import os
import re
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.ad_checkpoint import checkpoint_name  # noqa: E402

from paddlebox_tpu.models import afmoe, attention, lm_layers, moe, sdar  # noqa: E402
from paddlebox_tpu.models import glm_moe_lite as glm  # noqa: E402
from paddlebox_tpu.models import (  # noqa: E402
    Afmoe, AfmoeConfig, GlmMoeLite, GlmMoeLiteConfig, OlmoHybrid, OlmoHybridConfig, SmallThinker,
    SmallThinkerConfig, Xing4, Xing4Config)
from paddlebox_tpu.ops.pallas_kernels import (  # noqa: E402
    KEEP_SCORES, SCORES_LSE, SCORES_OUT, causal_attention)
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
        [attention._attend_block(q, k, v, i, 128, SCALE, 1, None, None) for i in range(0, T, 128)],
        axis=1)


def _interpreted(q, k, v, scale, block, interpret, *rest):
    """``causal_attention`` as ``attention.scores`` calls it, in interpret mode."""
    return causal_attention(q, k, v, scale, block, True, *rest)


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


@pytest.mark.parametrize("backend,t,qk,vd,block,window,dblock,fused", [
    # GLM's and Xing4's latent attention
    ("tpu", 4096, 256, 256, 512, None, None, True),    # the token cell
    ("tpu", 256, 128, 128, 128, None, None, True),
    ("cpu", 4096, 256, 256, 512, None, None, False),   # tier-1, whatever the shape
    ("gpu", 4096, 256, 256, 512, None, None, False),
    ("tpu", 64, 16, 16, 8, None, None, False),         # the toy token cell's widths
    ("tpu", 4096, 192, 128, 512, None, None, True),    # Xing4's cell: q/k of 192 behind zero columns
    ("tpu", 4096, 192, 64, 512, None, None, False),    # value heads that are no whole lane rows
    ("tpu", 4096, 96, 128, 512, None, None, False),    # query/key heads that are no half lane rows
    ("tpu", 4096, 256, 256, 64, None, None, False),    # a query block the kernel does not tile
    ("tpu", 4096, 320, 320, 512, None, None, False),   # a head that is no multiple of a lane row
    # Trinity's and SmallThinker's window and full layers
    ("tpu", 8192, 128, 128, 512, 2048, None, True),    # the Trinity cell's window layers
    ("tpu", 8192, 128, 128, 512, None, None, True),    # and its full layer
    ("cpu", 8192, 128, 128, 512, 2048, None, False),   # tier-1, whatever the shape
    ("tpu", 8192, 128, 128, 512, 2000, None, False),   # a window the tiles do not divide
    ("tpu", 1024, 128, 128, 512, 2048, None, True),    # a window longer than the record: full causal
    ("tpu", 32, 16, 16, 8, 16, None, False),           # the toy cell's widths
    ("tpu", 8192, 128, 128, 64, 2048, None, False),    # a query block the kernel does not tile
    # SDAR's block-diffusion mask
    ("tpu", 16384, 128, 128, 512, None, 4, True),      # the SDAR cell: 8,192 tokens twice
    ("cpu", 16384, 128, 128, 512, None, 4, False),     # tier-1, whatever the shape
    ("tpu", 12288, 128, 128, 512, None, 4, True),      # 6,144 tokens twice
    ("tpu", 8192 + 512, 128, 128, 512, None, 4, False),  # a half that is no whole number of tiles
    ("tpu", 16384, 128, 128, 512, None, 96, False),    # blocks the tile does not hold whole
    ("tpu", 64, 16, 16, 8, None, 4, False),            # the toy cell's widths
])
def test_the_path_is_chosen_from_backend_and_shapes(backend, t, qk, vd, block, window, dblock, fused):
    """One rule for every call site of the kernel, whatever the model."""
    assert attention.fused(backend, t, qk, vd, block, window, dblock) is fused


def _mla_call(cfg: GlmMoeLiteConfig):
    p = GlmMoeLite(cfg)._attn_init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, cfg.seq_len, cfg.hidden_size))
    rope = lm_layers.rope_tables(cfg.seq_len, cfg.qk_rope_head_dim, cfg.rope_theta)
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
    rope = lm_layers.rope_tables(cfg.seq_len, cfg.qk_rope_head_dim, cfg.rope_theta)
    run = lambda: jax.value_and_grad(lambda x: jnp.sum(  # noqa: E731
        glm.mla(p, x, jnp.ones((cfg.hidden_size,)), cfg, rope, "model") ** 2))(x)
    want, dwant = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "causal_attention", _interpreted)
    got, dgot = run()
    assert float(got) == pytest.approx(float(want), rel=1e-3)
    assert _rel(dgot, dwant) < 1e-2


# ---- query/key heads of one width, value heads of another (PR 42) -----------------------

# (Dqk, Dv, group): Xing4's own pair, a toy pair below a lane row, the pair grouped, and one
# whose query/key heads are wider than two lane rows
WIDTHS = [(192, 128, 1), (64, 128, 1), (192, 128, 2), (320, 256, 1)]


def _two_widths(dqk: int, dv: int, group: int):
    ks = jax.random.split(jax.random.PRNGKey(42 + dqk), 4)
    q = jax.random.normal(ks[0], (1, T, 2 * group, dqk)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, T, 2, dqk)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, T, 2, dv)).astype(jnp.bfloat16)
    return q, k, v, jax.random.normal(ks[3], (1, T, 2 * group, dv))


def _blocked_two_widths(q, k, v, group: int):
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    scale = q.shape[-1] ** -0.5
    return jnp.concatenate([attention._attend_block(q, k, v, i, 128, scale, 1, None, None)
                            for i in range(0, T, 128)], axis=1)


@pytest.mark.parametrize("dqk,dv,group", WIDTHS)
def test_two_widths_output_and_all_three_cotangents_match_the_blocked_form(dqk, dv, group):
    q, k, v, g = _two_widths(dqk, dv, group)
    fused = lambda q, k, v: causal_attention(q, k, v, dqk ** -0.5, 128, True, group)  # noqa: E731
    blocked_ = partial(_blocked_two_widths, group=group)
    o, want = fused(q, k, v), blocked_(q, k, v)
    assert o.dtype == jnp.float32 and o.shape == want.shape == q.shape[:3] + (dv,)
    assert _rel(o, want) < 2e-3
    grad = lambda f: jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * g), argnums=(0, 1, 2))(q, k, v)  # noqa: E731
    for a, b, like in zip(grad(fused), grad(blocked_), (q, k, v)):
        assert a.dtype == b.dtype == jnp.bfloat16 and a.shape == b.shape == like.shape
        assert _rel(a, b) < 6e-3


def test_the_zero_columns_are_traced_only_where_the_width_needs_them():
    """192 goes in behind 64 zero columns (one ``pad`` each of q and k a kernel
    call, the cotangents cut back); 128 and 256 trace no pad at all, so the
    digests below hold."""
    def text(dqk):
        q = jax.ShapeDtypeStruct((1, 256, 2, dqk), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
        f = lambda q, k, v: jnp.sum(causal_attention(q, k, v, 0.1, 128, True))  # noqa: E731
        return str(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, q, v))

    assert "pad" in text(192) and "bf16[1,256,512]" in text(192)  # the kernel sees 2 heads of 256
    for whole in (128, 256):
        assert "pad" not in text(whole)
    with pytest.raises(ValueError, match="whole lane rows"):
        causal_attention(*(jnp.zeros((1, 256, 2, d), jnp.bfloat16) for d in (128, 128, 64)), 0.1, 128, True)


XING4_TILED = Xing4Config(hidden_size=64, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16,
                          qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, seq_len=256,
                          attn_block=128)


def test_xing4s_attention_through_the_kernel_agrees_with_the_blocks(monkeypatch):
    """The attention branch at 192 / 128 both ways (the kernel interpreted),
    under YaRN's tables and scale: the layout in and out, the zero columns,
    the head split."""
    cfg = XING4_TILED
    p = GlmMoeLite(cfg)._attn_init(jax.random.PRNGKey(1))
    p = jax.tree.map(lambda a: a * 20 if a.ndim == 2 else a, p)  # scores of order 1, not 1e-3
    x = jax.random.normal(jax.random.PRNGKey(2), (2, cfg.seq_len, cfg.hidden_size))
    rope = lm_layers.yarn_rope_tables(cfg.seq_len, cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor,
                                64, cfg.beta_fast, cfg.beta_slow)
    stats = lambda: (STAT_GET("model.mla.fused_scores"), STAT_GET("model.mla.blocked_scores"))  # noqa: E731
    run = lambda: jax.value_and_grad(lambda x: jnp.sum(glm.mla_branch(  # noqa: E731
        p, x, jnp.ones((cfg.hidden_size,)), cfg, rope, "model", cfg.softmax_scale) ** 2))(x)
    f0, b0 = stats()
    want, dwant = run()
    assert stats() == (f0, b0 + 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "causal_attention", _interpreted)
    got, dgot = run()
    assert stats() == (f0 + 1, b0 + 1)
    assert float(got) == pytest.approx(float(want), rel=1e-3)
    assert _rel(dgot, dwant) < 1e-2


# ---- grouped-query heads and a window (PR 33) ---------------------------------------

WINDOW, GROUP = 256, 8


def _grouped(T: int, group: int = GROUP, kv_heads: int = 1):
    ks = jax.random.split(jax.random.PRNGKey(33 + T), 4)
    q = jax.random.normal(ks[0], (1, T, kv_heads * group, D)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(a, (1, T, kv_heads, D)).astype(jnp.bfloat16) for a in ks[1:3])
    return q, k, v, jax.random.normal(ks[3], (1, T, kv_heads * group, D))


def _blocked_grouped(q, k, v, window, group: int = GROUP):
    return jnp.concatenate([attention._attend_block(q, k, v, i, 128, SCALE, group, window, None)
                            for i in range(0, q.shape[1], 128)], axis=1)


@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("T", [WINDOW, 2 * WINDOW, 4 * WINDOW])
def test_group_8_and_a_window_match_the_blocked_form_output_and_gradients(block, T):
    """Eight query heads over one key-value head, a band of ``WINDOW``: T = W
    (the band hides nothing), 2W and 4W (tiles on the edge, tiles skipped)."""
    q, k, v, g = _grouped(T)
    fused = lambda q, k, v: causal_attention(q, k, v, SCALE, block, True, GROUP, WINDOW)  # noqa: E731
    want_f = lambda q, k, v: _blocked_grouped(q, k, v, WINDOW)  # noqa: E731
    o, want = fused(q, k, v), want_f(q, k, v)
    assert o.dtype == jnp.float32 and o.shape == want.shape == q.shape
    # p's rounding to bfloat16 (2**-9 an element) averages over a row's visible keys: at most 256
    assert _rel(o, want) < 3e-3 and float(jnp.max(jnp.abs(o - want))) < 2e-2
    grad = lambda f: jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * g), argnums=(0, 1, 2))(q, k, v)  # noqa: E731
    for a, b in zip(grad(fused), grad(want_f)):
        assert a.dtype == b.dtype == jnp.bfloat16 and a.shape == b.shape
        assert _rel(a, b) < 8e-3  # dk, dv are sums over the group's 8 heads, each side rounds once
    if T > WINDOW:  # and the window is there: full causal is another function
        assert _rel(o, _blocked_grouped(q, k, v, None)) > 0.05


@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("window", [WINDOW, None])
def test_group_7_over_two_key_value_heads_matches_the_blocked_form_output_and_gradients(block, window):
    """SmallThinker's group, the first that is no power of two: 14 query heads
    over 2 key-value heads, a band of ``WINDOW`` in 4 W (tiles on the edge,
    tiles skipped) and full causal."""
    q, k, v, g = _grouped(4 * WINDOW, 7, 2)
    fused = lambda q, k, v: causal_attention(q, k, v, SCALE, block, True, 7, window)  # noqa: E731
    want_f = lambda q, k, v: _blocked_grouped(q, k, v, window, 7)  # noqa: E731
    o, want = fused(q, k, v), want_f(q, k, v)
    assert o.dtype == jnp.float32 and o.shape == want.shape == q.shape
    assert _rel(o, want) < 3e-3 and float(jnp.max(jnp.abs(o - want))) < 2e-2
    grad = lambda f: jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * g), argnums=(0, 1, 2))(q, k, v)  # noqa: E731
    for a, b in zip(grad(fused), grad(want_f)):
        assert a.dtype == b.dtype == jnp.bfloat16 and a.shape == b.shape
        assert _rel(a, b) < 8e-3  # dk, dv are sums over the group's 7 heads, each side rounds once
    # query head h reads key-value head h // 7: the two key-value heads swapped is another function
    assert _rel(o, fused(q, k[:, :, ::-1], v[:, :, ::-1])) > 0.05


@pytest.mark.parametrize("block", BLOCKS)
def test_a_key_a_window_behind_leaves_the_output_bit_equal_and_one_nearer_does_not(block):
    q, k, v, _ = _grouped(4 * WINDOW)
    at = 130
    k2, v2 = k.at[:, at].set(k[:, at] * -3 + 1), v.at[:, at].set(v[:, at] + 7)
    o, o2 = (np.asarray(causal_attention(q, a, b, SCALE, block, True, GROUP, WINDOW))
             for a, b in ((k, v), (k2, v2)))
    same = np.all(o == o2, axis=(0, 2, 3))  # by query position
    assert same[:at].all() and not same[at:at + WINDOW].any()  # i - j = 0 .. W - 1: seen
    assert same[at + WINDOW:].all()  # i - j >= W: not seen


def test_glms_call_lowers_to_the_kernel_it_had_before_group_and_window():
    """``group`` 1 and no window trace to the jaxpr of PR 32's kernel pair
    (``ops/pallas_kernels.py``: kernel bodies, grids, block index maps), source
    locations aside, and since PR 36 two ``name`` equations behind the forward
    call (the flat output and the logsumexp, the identity unless a checkpoint
    keeps them). The digest was taken anew from PR 36's tree (it was
    ``08284859...`` from commit 631c704 to 866c886): a diff of the two texts
    shows those two equations and the variables renamed after them, nothing of
    a kernel. A change to the kernel GLM's cell runs has to change it."""
    x = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
    f = lambda q, k, v: jnp.sum(causal_attention(q, k, v, 128 ** -0.5, 128, True))  # noqa: E731
    text = str(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(x, x, x))
    digest = hashlib.sha256(re.sub(r"\S+\.py:\d+", "", text).encode()).hexdigest()
    assert digest == "68b3e6182885371b39f558d6453dc3759b471777a6c52d6a50dc89695a33ecc5"


# (group, window) -> digest, taken from PR 38's tree (commit c0630af) with these lines: Trinity's
# calls (8; its window and full layers) and SmallThinker's (7), at a window of two tiles
OLDER_CALLS = {
    (8, 256): "1cdb572ad6df912a7b259d7f06fa32fe00563a8f3835df42e46f9bc10c5994ff",
    (8, None): "13eaabbaf559956f4b73cea458ca9022dabc6eabc098446a4787def57ba65e10",
    (7, 256): "bb9c3a0137f37b64805f475a0adee9e5dc80402cfd4c93e3ba6c497bba9c872e",
    (7, None): "318257848b581a83927ad376b322ec5d5dfc291955297f17357d5e2b595eec53",
}


@pytest.mark.parametrize("group,window", sorted(OLDER_CALLS, key=str))
def test_trinitys_and_smallthinkers_calls_lower_to_the_kernels_they_had_before_the_diffusion_mask(
        group, window):
    """A call that names no ``diffusion_block`` traces to the jaxpr it had
    before the argument was there (kernel bodies, grids, block index maps),
    source locations aside; GLM's call is held by the test above."""
    q = jax.ShapeDtypeStruct((1, 512, 2 * group, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
    f = lambda q, k, v: jnp.sum(causal_attention(q, k, v, 128 ** -0.5, 128, True, group, window))  # noqa: E731
    text = str(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(q, kv, kv))
    digest = hashlib.sha256(re.sub(r"\S+\.py:\d+", "", text).encode()).hexdigest()
    assert digest == OLDER_CALLS[group, window]


def test_no_group_and_no_window_are_the_call_that_names_neither(qkvg):
    q, k, v, g = qkvg
    run = lambda *a: jax.value_and_grad(  # noqa: E731
        lambda q, k, v: jnp.sum(causal_attention(q, k, v, SCALE, 128, True, *a) * g),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.tree.leaves(run())
    for args in ((1, None), (1, T), (1, 4 * T)):  # a window no shorter than the record hides nothing
        assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(run(*args)), want))


@pytest.mark.parametrize("backend,window,fused", [
    ("tpu", 4096, True),    # the SmallThinker cell's window layers: 32 tiles a side, a band of 8 + 1
    ("tpu", None, True),    # and its full layer
    ("cpu", 4096, False),   # tier-1, whatever the shape
])
def test_smallthinkers_path_is_chosen_from_backend_and_shapes(backend, window, fused, monkeypatch):
    """The model's scores and Trinity's reach ``attention.scores`` alike, with
    its rule and its counters: at the cell's shapes a TPU takes one kernel a kind."""
    from paddlebox_tpu.models import SmallThinkerConfig, smallthinker

    c = SmallThinkerConfig()  # the published widths, the cell's record and blocks
    assert (c.group, c.seq_len, c.head_dim, c.attn_block, c.sliding_window) == (7, 16384, 128, 512, 4096)
    assert attention.fused(backend, c.seq_len, c.head_dim, c.head_dim, c.attn_block, window) is fused
    assert not attention.fused("tpu", 32, 16, 16, 8, 16)  # the toy cell's widths stay blocked
    reached, scores = [], attention.scores
    monkeypatch.setattr(attention, "scores",
                        lambda *a, **kw: reached.append(kw["kind"]) or scores(*a, **kw))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    small = SmallThinkerConfig(hidden_size=64, num_attention_heads=7, num_key_value_heads=1,
                               seq_len=1024, sliding_window=512, attn_block=128)
    x = jax.ShapeDtypeStruct((1, small.seq_len, small.hidden_size), jnp.float32)
    p = {"q": jnp.zeros((64, 7 * 128)), "k": jnp.zeros((64, 128)), "v": jnp.zeros((64, 128)),
         "o": jnp.zeros((7 * 128, 64))}
    rope = lm_layers.rope_tables(small.seq_len, small.head_dim, small.rope_theta)
    text = str(jax.make_jaxpr(lambda x: smallthinker.attention(
        p, x, jnp.ones((64,)), small, rope, window is not None))(x))
    assert text.count("pallas_call") == (1 if fused else 0)
    trinity = AfmoeConfig(hidden_size=64, num_attention_heads=7, num_key_value_heads=1,
                          seq_len=1024, sliding_window=512, attn_block=128)
    ln = jnp.ones((64,))
    p = Afmoe(trinity)._attn_init(jax.random.PRNGKey(0))
    text = str(jax.make_jaxpr(lambda x: afmoe.attention(
        p, x, ln, ln, trinity, rope, window is not None))(x))
    assert text.count("pallas_call") == (1 if fused else 0)
    assert reached == ["window" if window else "full"] * 2


AF_TILED = AfmoeConfig(hidden_size=64, num_attention_heads=2, num_key_value_heads=1, head_dim=128,
                       sliding_window=128, seq_len=256, attn_block=128)


def test_afmoes_call_sites_are_counted_by_kind_under_the_path_they_were_lowered_to(monkeypatch):
    names = ("model.attn.fused_window_scores", "model.attn.fused_full_scores",
             "model.attn.blocked_scores")
    stats = lambda: tuple(STAT_GET(n) for n in names)  # noqa: E731
    c = AF_TILED
    p = Afmoe(c)._attn_init(jax.random.PRNGKey(0))
    x, ln = jnp.zeros((1, c.seq_len, c.hidden_size)), jnp.ones((c.hidden_size,))
    rope = lm_layers.rope_tables(c.seq_len, c.head_dim, c.rope_theta)
    trace = lambda s: str(jax.make_jaxpr(  # noqa: E731
        lambda p, x, s=s: afmoe.attention(p, x, ln, ln, c, rope, s))(p, x))
    w0, f0, b0 = stats()
    assert "pallas_call" not in trace(True)  # the CPU: the blocked form, at any shape
    assert stats() == (w0, f0, b0 + 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert trace(True).count("pallas_call") == 1 and stats() == (w0 + 1, f0, b0 + 1)
    assert trace(False).count("pallas_call") == 1 and stats() == (w0 + 1, f0 + 1, b0 + 1)
    # a step told its kind holds both call sites, one kernel each
    text = str(jax.make_jaxpr(lambda p, x, s: afmoe.attention(p, x, ln, ln, c, rope, s))(
        p, x, jnp.asarray(True)))
    assert text.count("pallas_call") == 2 and stats() == (w0 + 2, f0 + 2, b0 + 1)


@pytest.mark.parametrize("sliding", [True, False])
def test_afmoe_attention_through_the_kernel_agrees_with_it_through_the_blocks(monkeypatch, sliding):
    """The whole block both ways (the kernel interpreted): the layout in and
    out of the kernel, the group, the window, the scale."""
    c = AfmoeConfig(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=128,
                    sliding_window=128, seq_len=384, attn_block=128)
    p = Afmoe(c)._attn_init(jax.random.PRNGKey(1))
    p = jax.tree.map(lambda a: a * 20 if a.ndim == 2 else a, p)  # scores of order 1, not 1e-3
    x = jax.random.normal(jax.random.PRNGKey(2), (1, c.seq_len, c.hidden_size))
    ln = jnp.ones((c.hidden_size,))
    rope = lm_layers.rope_tables(c.seq_len, c.head_dim, c.rope_theta)
    run = lambda: jax.value_and_grad(lambda x: jnp.sum(  # noqa: E731
        afmoe.attention(p, x, ln, ln, c, rope, sliding) ** 2))(x)
    want, dwant = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "causal_attention", _interpreted)
    got, dgot = run()
    assert float(got) == pytest.approx(float(want), rel=1e-3)
    assert _rel(dgot, dwant) < 1e-2


# ---- the block-diffusion mask (PR 39) ------------------------------------------------

def _blocked_diffusion(q, k, v, block: int, group: int = GROUP):
    return jnp.concatenate([attention._attend_block(q, k, v, i, 128, SCALE, group, None, block)
                            for i in range(0, q.shape[1], 128)], axis=1)


@pytest.mark.parametrize("T,block,dblock,group,kv_heads", [
    (768, 128, 4, 8, 1),   # three tiles a half: diagonal tiles of all three masks, whole tiles, skipped steps
    (512, 256, 32, 8, 1),  # one tile a half: every tile is masked
    (512, 128, 4, 1, 2),   # no group: dk, dv are whole-T float32 blocks all the same
])
def test_the_diffusion_mask_matches_the_blocked_form_output_and_gradients(T, block, dblock, group, kv_heads):
    """The record twice, clean then noised, in blocks of ``dblock``: a clean
    query sees its own and the earlier clean blocks, a noisy one the earlier
    clean blocks and its own noisy block. Against ``attention._attend_block`` (held
    to a brute-force table in ``tests/test_sdar.py``)."""
    q, k, v, g = _grouped(T, group, kv_heads)
    fused = lambda q, k, v: causal_attention(q, k, v, SCALE, block, True, group, None, dblock)  # noqa: E731
    want_f = lambda q, k, v: _blocked_diffusion(q, k, v, dblock, group)  # noqa: E731
    o, want = fused(q, k, v), want_f(q, k, v)
    assert o.dtype == jnp.float32 and o.shape == want.shape == q.shape
    assert _rel(o, want) < 3e-3 and float(jnp.max(jnp.abs(o - want))) < 2e-2
    grad = lambda f: jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) * g), argnums=(0, 1, 2))(q, k, v)  # noqa: E731
    for a, b in zip(grad(fused), grad(want_f)):
        assert a.dtype == b.dtype == jnp.bfloat16 and a.shape == b.shape
        assert _rel(a, b) < 8e-3  # dk, dv are sums over the group's heads, each side rounds once
    # and the mask is there: causal over the doubled record is another function
    assert _rel(o, causal_attention(q, k, v, SCALE, block, True, group)) > 0.05


def test_under_the_diffusion_mask_a_change_moves_the_rows_that_see_it_and_no_other():
    L, n = 384, 4
    q, k, v, _ = _grouped(2 * L)
    run = lambda k, v: np.asarray(causal_attention(q, k, v, SCALE, 128, True, GROUP, None, n))  # noqa: E731
    base = run(k, v)
    moved = lambda at: np.flatnonzero(np.any(  # noqa: E731
        run(k.at[:, at].set(k[:, at] * -3 + 1), v.at[:, at].add(7.0)) != base, axis=(0, 2, 3)))
    at = 130  # a clean key of block 32: its own block's clean queries on, the noisy from block 33 on
    first = at // n * n
    assert np.array_equal(moved(at), np.concatenate([np.arange(first, L), np.arange(L + first + n, 2 * L)]))
    assert np.array_equal(moved(L + at), np.arange(L + first, L + first + n))  # a noisy key: its block
    with pytest.raises(ValueError, match="diffusion blocks"):
        causal_attention(q, k, v, SCALE, 128, True, GROUP, 256, n)  # no window with it
    with pytest.raises(ValueError, match="diffusion blocks"):
        causal_attention(q, k, v, SCALE, 256, True, GROUP, None, n)  # 768 is not two halves of tiles of 256


@pytest.mark.parametrize("backend,fused", [("tpu", True), ("cpu", False)])
def test_sdars_call_site_is_counted_under_the_path_it_was_lowered_to(backend, fused, monkeypatch):
    """At the SDAR cell's record (8,192 tokens twice, tiles of 512, blocks of 4) a
    TPU takes the kernel (``test_the_path_is_chosen_from_backend_and_shapes``)."""
    assert attention.fused(backend, 16384, 128, 128, 512, None, 4) is fused
    names = ("model.attn.fused_diffusion_scores", "model.attn.blocked_scores")
    before = [STAT_GET(n) for n in names]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    c = sdar.SdarConfig(hidden_size=64, num_attention_heads=8, num_key_value_heads=1,
                        seq_len=512, attn_block=128)
    p = {"q": jnp.zeros((64, 8 * 128)), "k": jnp.zeros((64, 128)), "v": jnp.zeros((64, 128)),
         "o": jnp.zeros((8 * 128, 64)), "q_norm": jnp.ones((128,)), "k_norm": jnp.ones((128,))}
    rope = tuple(jnp.tile(a, (2, 1))
                 for a in lm_layers.rope_tables(c.data_len, c.head_dim, c.rope_theta))
    text = str(jax.make_jaxpr(lambda x: sdar.attention(p, x, jnp.ones((64,)), c, rope))(
        jax.ShapeDtypeStruct((1, c.seq_len, 64), jnp.float32)))
    assert text.count("pallas_call") == (1 if fused else 0)
    assert [STAT_GET(n) - b for n, b in zip(names, before)] == ([1, 0] if fused else [0, 1])


# ---- o and the logsumexp kept across a layer's checkpoint, by name (PR 36) -------------

def _kernel_calls(text: str):
    """(forward, backward) kernel calls in a jaxpr's text."""
    return tuple(len(re.findall(rf"name=causal_attention_{d}\b", text)) for d in ("fwd", "bwd"))


def _checkpointed_stack(group: int, window, policy):
    """Three scanned layers under ``jax.checkpoint``, each told its kind by a
    traced flag: with a ``window`` a ``lax.cond`` over a windowed and a full
    call site (``afmoe``'s and ``smallthinker``'s stack), without one a single
    full call site (GLM's). -> (the gradient's jaxpr as text, loss and gradients)."""
    T, hid, layers = 256, 32, 3
    ks = jax.random.split(jax.random.PRNGKey(36), 5)
    w = {"q": jax.random.normal(ks[0], (layers, hid, group * D)) * 0.3,
         "k": jax.random.normal(ks[1], (layers, hid, D)) * 0.3,
         "v": jax.random.normal(ks[2], (layers, hid, D)) * 0.3,
         "o": jax.random.normal(ks[3], (layers, group * D, hid)) * 0.1}
    x = jax.random.normal(ks[4], (1, T, hid))
    call = lambda win: (  # noqa: E731
        lambda q, k, v: causal_attention(q, k, v, SCALE, 128, True, group, win))

    @partial(jax.checkpoint, policy=policy)
    def layer(x, step):
        p, kind = step
        q, k, v = ((x @ p[n]).reshape(1, T, -1, D).astype(jnp.bfloat16) for n in "qkv")
        o = call(None)(q, k, v) if window is None else lax.cond(kind, call(window), call(None), q, k, v)
        return x + o.reshape(1, T, group * D) @ p["o"], None

    loss = lambda w, x: jnp.sum(  # noqa: E731
        lax.scan(layer, x, (w, jnp.asarray([True, False, True])))[0] ** 2)
    grad = jax.value_and_grad(loss, argnums=(0, 1))
    return str(jax.make_jaxpr(grad)(w, x)), grad(w, x)


@pytest.mark.parametrize("window", [128, None])
@pytest.mark.parametrize("group", [1, 7])
def test_a_checkpoint_that_keeps_the_scores_runs_each_forward_kernel_once_and_changes_no_bit(group, window):
    """Under a layer checkpoint the backward needs (q, k, v, o, lse): q, k, v
    are the layer's recomputation, o and lse only the kernel's second run
    gives, unless the checkpoint keeps them (``KEEP_SCORES``): then every call
    site's forward kernel stands once in the gradient, not twice, the backward
    once either way, and the backward kernel receives the very arrays."""
    sites = 1 if window is None else 2
    plain, want = _checkpointed_stack(group, window, None)
    kept, got = _checkpointed_stack(group, window, KEEP_SCORES)
    assert _kernel_calls(plain) == (2 * sites, sites)
    assert _kernel_calls(kept) == (sites, sites)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def test_the_policy_keeps_the_two_names_the_forward_rule_gives_and_no_other():
    x = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(causal_attention(q, k, v, SCALE, 128, True)), argnums=(0, 1, 2)))(x, x, x))
    named = dict(re.findall(r"(f32\[[\d,]+\]) = name\[name=(\w+)\]", text))
    # the kernel's flat [B, T, H * D] float32 output, before its reshape, and the rows' logsumexp
    assert named == {"f32[1,256,256]": SCORES_OUT, "f32[1,2,1,256]": SCORES_LSE}

    def f(x):  # no step's derivative reads its own output: a step runs again only to feed a later one
        a = checkpoint_name(x ** 3, SCORES_OUT)
        b = checkpoint_name(jnp.cos(a), SCORES_LSE)
        c = checkpoint_name(jnp.log1p(b), "causal_attention_other")
        return jnp.sum(c ** 2)

    runs = lambda policy: [  # noqa: E731
        len(re.findall(op, str(jax.make_jaxpr(jax.grad(jax.checkpoint(f, policy=policy)))(
            jnp.ones((4,)))))) for op in (r"integer_pow\[y=3\]", "= cos ", "= log1p ")]
    assert runs(None) == [2, 2, 2]  # without a policy a name is the identity
    assert runs(KEEP_SCORES) == [1, 1, 2]


_SMALL = dict(hidden_size=64, seq_len=256, attn_block=128, loss_block=128, expert_block=128,
              moe_intermediate_size=16, num_experts_per_tok=2, experts_held=4, vocab_size=64)
# name -> (the model at shapes the kernel tiles, its call sites of the kernel, its checkpoint
# sites, the counter of the latter): GLM's dense layer, scanned expert layer and MTP module;
# Trinity's dense layer (its kind static) and a scan body of two branches; SmallThinker's body;
# SDAR's, whose layers are all alike: one call site
KEEPERS = {
    "glm": (lambda: GlmMoeLite(GlmMoeLiteConfig(
        **_SMALL, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=64,
        qk_rope_head_dim=64, v_head_dim=128, num_hidden_layers=3, first_k_dense_replace=1,
        intermediate_size=32, n_routed_experts=8)), 3, 3, "model.mla.keep_scores_sites"),
    "trinity": (lambda: Afmoe(AfmoeConfig(
        **_SMALL, num_attention_heads=2, num_key_value_heads=1, head_dim=128, sliding_window=128,
        layer_types=("sliding_attention", "full_attention", "sliding_attention"),
        num_dense_layers=1, intermediate_size=32, num_experts=8)), 3, 2,
        "model.attn.keep_scores_sites"),
    "smallthinker": (lambda: SmallThinker(SmallThinkerConfig(
        **_SMALL, num_attention_heads=7, num_key_value_heads=1, sliding_window=128,
        layer_kinds=(0, 1, 1), num_experts=8)), 2, 1, "model.attn.keep_scores_sites"),
    "sdar": (lambda: sdar.Sdar(sdar.SdarConfig(
        **_SMALL, num_attention_heads=8, num_key_value_heads=1, num_hidden_layers=3, num_experts=8,
        mask_id=63)), 1, 1, "model.attn.keep_scores_sites"),
    "xing4": (lambda: Xing4(Xing4Config(
        **_SMALL, num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, num_hidden_layers=3, first_k_dense_replace=1,
        intermediate_size=32, n_routed_experts=8)), 2, 2, "model.mla.keep_scores_sites"),
    "olmo_hybrid": (lambda: OlmoHybrid(OlmoHybridConfig.from_dict(dict(
        _SMALL, num_attention_heads=1, num_key_value_heads=1, head_dim=128, linear_num_key_heads=2,
        linear_num_value_heads=2, linear_key_head_dim=16, linear_value_head_dim=32,
        intermediate_size=32))), 1, 1, "model.attn.keep_scores_sites"),
}


@pytest.mark.parametrize("name", sorted(KEEPERS))
def test_every_token_models_checkpoints_keep_the_scores_and_count_themselves(monkeypatch, name):
    """All six models take the policy (Olmo-Hybrid's in the full layer of a
    period's scan body, Xing4's in a dense layer and a scan body at 192 / 128
    wide; with it each cell's superstep still
    fits the chip and its step is shorter, ``PERF.md`` section 6): on a TPU the gradient of
    ``apply`` holds each call site's forward kernel once, every checkpoint of
    a layer carries ``KEEP_SCORES``, and the trace-time counter says how many."""
    make, calls, sites, stat = KEEPERS[name]
    model = make()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    before = STAT_GET(stat)
    text = str(jax.make_jaxpr(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True))(
        params, f32(1, 256, 64), f32(1, 256)))
    assert _kernel_calls(text) == (calls, calls)
    assert len(re.findall(r"policy=<function save_only_these_names", text)) == sites
    assert STAT_GET(stat) - before == sites  # apply was traced once


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


def test_grouped_window_and_full_kernels_compile_for_a_v5e_at_the_trinity_cells_shapes(one_chip):
    """1 x 8,192 x 32 query heads over 4 key-value heads of 128, tiles of 512:
    the window layers' pair (a band of 4 tiles) and the full layer's."""
    from paddlebox_tpu.obs.program_scopes import scope_map

    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 4, 128), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.float32, sharding=one_chip)
    for scope, window in (("model/attn/scores_window", 2048), ("model/attn/scores_full", None)):
        def step(q, k, v, g, scope=scope, window=window):
            with jax.named_scope(scope):
                return jax.grad(lambda q, k, v: jnp.sum(
                    causal_attention(q, k, v, 128 ** -0.5, 512, False, 8, window) * g),
                    argnums=(0, 1, 2))(q, k, v)

        compiled = jax.jit(step).lower(q, kv, kv, g).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        kernels = {n: s for n, s in scope_map(text).items() if "causal_attention" in n}
        assert len(kernels) == 2 and set(kernels.values()) == {scope}, kernels
        # no score block and no 8-fold k, v, dk or dv in HBM: the temporaries are the
        # float32 output and dq, the statistics, the row term and dk, dv at 4 heads
        assert compiled.memory_analysis().temp_size_in_bytes < 3 * 8192 * 32 * 128 * 4


def test_group_7_window_and_full_kernels_compile_for_a_v5e_at_the_smallthinker_cells_shapes(one_chip):
    """1 x 16,384 x 28 query heads over 4 key-value heads of 128, tiles of 512:
    32 tiles a side (16 was the most), the window layers' pair (a band of
    8 + 1 tiles) and the full layer's; the backward holds three whole-T
    float32 blocks of [16384, 128] (dq; dk, dv over a group's 7 heads) in VMEM."""
    from paddlebox_tpu.obs.program_scopes import scope_map

    q = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.float32, sharding=one_chip)
    for scope, window in (("model/attn/scores_window", 4096), ("model/attn/scores_full", None)):
        def step(q, k, v, g, scope=scope, window=window):
            with jax.named_scope(scope):
                return jax.grad(lambda q, k, v: jnp.sum(
                    causal_attention(q, k, v, 128 ** -0.5, 512, False, 7, window) * g),
                    argnums=(0, 1, 2))(q, k, v)

        compiled = jax.jit(step).lower(q, kv, kv, g).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        kernels = {n: s for n, s in scope_map(text).items() if "causal_attention" in n}
        assert len(kernels) == 2 and set(kernels.values()) == {scope}, kernels
        # no score block and no 7-fold k, v, dk or dv in HBM: the temporaries are the
        # float32 output and dq, the statistics, the row term and dk, dv at 4 heads
        assert compiled.memory_analysis().temp_size_in_bytes < 3 * 16384 * 28 * 128 * 4


@pytest.mark.parametrize("kept", [True, False])
def test_smallthinkers_loss_and_gradient_compile_for_a_v5e_with_each_forward_kernel_once(
        one_chip, monkeypatch, kept):
    """The cell's model (``benchmark/configs/smallthinker_21b_ep8.json``: one
    record of 16,384 tokens, 4 layers, blocks of 4,096), loss and gradient of
    every leaf and of the rows, the fused path forced: 4 kernel calls (a
    window and a full forward, a window and a full backward: the scan body's
    two branches) where the layer's checkpoint keeps o and the logsumexp, 6
    (each forward twice) where it does not. What keeping costs: the stacked
    ``[4, 1, 16384, 3584]`` float32 o, 0.94 GB, held once (5.99 GB of
    temporaries against 3.84; 6.11 where the 4-D output is the one named)."""
    from benchmark.models import smallthinker as build
    from paddlebox_tpu.models import smallthinker

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "smallthinker_21b_ep8.json")) as f:
        cfg = json.load(f)
    model = build.build(cfg, 3 + cfg["embedx_dim"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if not kept:
        monkeypatch.setattr(smallthinker, "KEEP_SCORES", None)
    B, T, H = cfg["batch_size"], cfg["seq_len"], cfg["hidden_size"]
    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(on, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    emb, ids = (jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in ((B, T, H), (B, T)))
    compiled = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True)).lower(
        params, emb, ids).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == (4 if kept else 6)
    # the token sum is cut in two parts of 1,280 columns (``moe.combine_parts``): the
    # backward keeps dhg and dhu, bfloat16 [M, 768] each with M = 32 blocks of 4,096 rows (0.40 GB),
    # where the checkpoint recomputes the forward it also keeps h (0.20 GB), and a part's 84 MB
    # stands beside the joined sum once (6.19 / 4.52 GB; 5.99 / 3.84 before)
    stash = 2 * 32 * 4096 * 768 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < (
        6.1e9 + stash if kept else 3.9e9 + 1.5 * stash + 16384 * 1280 * 4)


def test_diffusion_kernels_compile_for_a_v5e_at_the_sdar_cells_shapes(one_chip):
    """1 x 16,384 (8,192 tokens twice) x 32 query heads over 4 key-value heads
    of 128, tiles of 512, blocks of 4: 16 tiles a half, a forward inner axis of
    17 and a backward one of 33; the backward holds three whole-T float32
    blocks of [16384, 128] (dq; dk, dv over a group's 8 heads) in VMEM, as
    SmallThinker's call does."""
    from paddlebox_tpu.obs.program_scopes import scope_map

    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.float32, sharding=one_chip)

    def step(q, k, v, g):
        with jax.named_scope("model/attn/scores_diffusion"):
            return jax.grad(lambda q, k, v: jnp.sum(
                causal_attention(q, k, v, 128 ** -0.5, 512, False, 8, None, 4) * g),
                argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(q, kv, kv, g).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    kernels = {n: s for n, s in scope_map(text).items() if "causal_attention" in n}
    assert len(kernels) == 2 and set(kernels.values()) == {"model/attn/scores_diffusion"}, kernels
    # no score block and no 8-fold k, v, dk or dv in HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 16384 * 32 * 128 * 4


def test_sdars_loss_and_gradient_compile_for_a_v5e_with_one_kernel_pair(one_chip, monkeypatch):
    """The cell's model (``benchmark/configs/sdar_30b_a3b_ep8.json``: one
    record of 8,192 tokens twice, 4 layers alike), loss and gradient of every
    leaf and of the rows, the fused path forced: the scan body's one forward
    and one backward kernel (the layer's checkpoint keeps o and the
    logsumexp), 7.96 GB at the peak today."""
    from benchmark.models import sdar as build

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "sdar_30b_a3b_ep8.json")) as f:
        cfg = json.load(f)
    model = build.build(cfg, 3 + cfg["embedx_dim"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, T, H = cfg["batch_size"], cfg["seq_len"], cfg["hidden_size"]
    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(on, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    emb, ids = (jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in ((B, T, H), (B, T)))
    compiled = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True)).lower(
        params, emb, ids).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2
    # 6.31 before PR 44 and 6.30 with the token sum cut in two: the backward's kept dhg and dhu
    # (bfloat16 [250,880, 768] each, 0.77 GB) do not stand at the temporaries' peak
    assert compiled.memory_analysis().temp_size_in_bytes < 6.6e9


def test_two_width_kernels_compile_for_a_v5e_at_the_xing4_cells_shapes(one_chip):
    """1 x 4,096 x 32 heads, q and k 192 wide (256 behind the zero columns), v and
    the output 128, tiles of 512: the backward holds dq as a whole-T float32 block
    of [4096, 256] in VMEM."""
    from paddlebox_tpu.obs.program_scopes import scope_map

    q = jax.ShapeDtypeStruct((1, 4096, 32, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.float32, sharding=one_chip)

    def step(q, k, v, g):
        with jax.named_scope("model/mla/scores"):
            return jax.grad(lambda q, k, v: jnp.sum(causal_attention(q, k, v, 0.14468, 512) * g),
                            argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(q, q, v, g).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    kernels = {n: s for n, s in scope_map(text).items() if "causal_attention" in n}
    assert len(kernels) == 2 and set(kernels.values()) == {"model/mla/scores"}, kernels
    # no score block in HBM: the padded q and k, the padded dq and dk, the statistics
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 4096 * 32 * 256 * 4


def test_xing4s_loss_and_gradient_compile_for_a_v5e_inside_the_memory_4096_tokens_leave(
        one_chip, monkeypatch):
    """The cell's model (``benchmark/configs/xing4_29b_a4b_ep8.json``: one
    record of 4,096 tokens, a dense layer and a scan body of 4 expert layers,
    four streams), loss and gradient of every leaf and of the rows, the fused
    path forced: two forward and two backward kernels, and 9.54 GB at the peak
    today (2.86 of parameters, 2.86 of gradients, 3.8 of temporaries). The
    superstep adds Adam's two moments (5.6 GB) and the table to that; with
    more than 10.2 GB here it would not fit the chip's 17.18 at this seq_len."""
    from benchmark.models import xing4 as build

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "xing4_29b_a4b_ep8.json")) as f:
        cfg = json.load(f)
    model = build.build(cfg, 3 + cfg["embedx_dim"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, T, H = cfg["batch_size"], cfg["seq_len"], cfg["hidden_size"]
    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(on, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    emb, ids = (jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in ((B, T, H), (B, T)))
    compiled = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True)).lower(
        params, emb, ids).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert compiled.memory_analysis().peak_memory_in_bytes < 9.8e9  # 9.54 today
    # no block's rows join the token sum in the sorted form (PR 43): y, dx and the recomputation's y
    assert len(re.findall(r" scatter\(.*moe/combine", text)) == 3 * 2  # a block of 896 in two pieces
    assert not re.findall(r" sort\(.*moe/combine", text)


def test_xing4s_piece_joins_the_token_sum_as_written_and_its_whole_block_sorted(one_chip):
    """What ``glm_moe_lite.combine_piece_rows`` stays under, read from the
    optimised HLO: a block of 896 rows x 3,584 float32 columns into 4,096
    tokens, in a ``fori_loop`` as ``_grouped_fwd`` calls it. Cut by
    ``_add_rows`` (512 + 384 rows, an eighth of the tokens at most) both
    scatters run as written; whole, the compiler sorts the block's indices
    and gathers its updates through the permutation (``indices_are_sorted``
    on the scatter, a ``sort`` and a ``gather`` beside it): 1.00 ms a call on
    the chip against 0.066 for 256 rows (PERF.md section 6, PRs 42-43)."""
    N, R, C = 4096, 896, 3584

    def joined(add):
        def f(tok, upd):
            def body(j, acc):
                return add(acc, lax.dynamic_slice_in_dim(tok, j * R, R),
                           lax.dynamic_slice_in_dim(upd, j * R, R))
            return lax.fori_loop(0, 4, body, jnp.zeros((N, C), jnp.float32))
        tok = jax.ShapeDtypeStruct((4 * R,), jnp.int32, sharding=one_chip)
        upd = jax.ShapeDtypeStruct((4 * R, C), jnp.float32, sharding=one_chip)
        return jax.jit(f).lower(tok, upd).compile().as_text()

    assert moe.combine_piece_rows(N) == 512
    cut = joined(moe._add_rows)
    assert cut.count(" scatter(") == 2 and " sort(" not in cut and "indices_are_sorted=true" not in cut
    whole = joined(lambda acc, tb, rows: acc.at[tb].add(rows, mode="drop"))
    assert whole.count(" scatter(") == 1 and " sort(" in whole and " gather(" in whole
    assert "indices_are_sorted=true" in whole


@pytest.mark.parametrize("cell", ["sdar", "xing4"])
def test_the_combines_token_sum_stays_in_fast_memory_cut_where_it_is_over_96_mib(one_chip, cell):
    """``grouped_experts``, forward and gradient through ``routed_experts``, at
    a cell's token sum, compiled for a described v5e (fewer held experts than
    the cell's: the sum's shape is what decides). SDAR's float32 sum of
    16,384 x 2,048 (128 MiB) took every scatter into HBM before PR 44; cut in
    two parts of 1,024 columns, each joined by a loop of its own, every
    ``moe/combine`` scatter's output is in ``S(1)``: the cell's block of
    7,168 rows as 7 pieces of 1,024 into each part, forward and backward (at
    blocks of 2,048 the compiler leaves one of the backward's two loops in
    HBM: PERF.md section 7). Xing4's
    4,096 x 3,584 (56 MiB) stays one part, with the four scatters it had
    (PR 43: a block of 896 as 512 + 384, forward and backward)."""
    N, H, I, R, k, parts, scatters = {"sdar": (16384, 2048, 768, 7168, 2, (1024, 1024), 7 * 2 * 2),
                                      "xing4": (4096, 3584, 1024, 896, 2, (3584,), 2 * 2)}[cell]
    G = 4
    c = GlmMoeLiteConfig(hidden_size=H, moe_intermediate_size=I, n_routed_experts=4 * G,
                         num_experts_per_tok=k, experts_held=G, expert_block=R)
    on = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    p = {"gate": on(G, H, I), "up": on(G, H, I), "down": on(G, I, H)}

    def loss(p, x, g, idx, dy):
        y = moe.routed_experts(p, x, idx, g, c.experts_held, c.experts_offset, c.expert_block, "model")[0]
        return jnp.sum(y * dy)

    assert moe.combine_parts(N, H) == parts
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        p, on(N, H), on(N, k), on(N, k, dt=jnp.int32), on(N, H)).compile().as_text()
    assert STAT_GET("model.moe.combine_parts") == len(parts)
    # each moe/combine scatter fusion: is its output (the sum, or a part of it) in fast memory
    in_fast = [("S(1)" in line.split(" fusion(")[0]) for line in text.splitlines()
               if " fusion(" in line and "moe/combine/scatter-add" in line]
    assert len(in_fast) == scatters and all(in_fast), in_fast
    assert not re.findall(r" sort\(.*moe/combine", text)


def test_olmo_hybrids_loss_and_gradient_compile_for_a_v5e_inside_the_memory_4096_tokens_leave(
        one_chip, monkeypatch):
    """The cell's model (``benchmark/configs/olmo_hybrid_7b_hp3.json``: one
    record of 4,096 tokens, one period of three linear layers and a full one,
    10 of 30 heads), loss and gradient of every leaf and of the rows, the
    fused path forced: the full layer's forward and backward kernel, and 9.28
    GB at the peak today (3.04 of parameters, 3.04 of gradients, 3.47 of
    temporaries). The superstep adds Adam's two moments (5.95 GB) and the
    table (0.51) to that; with more than 10.2 GB here it would not fit the
    chip's 17.18 at this seq_len."""
    from benchmark.models import olmo_hybrid as build
    from paddlebox_tpu.obs.program_scopes import scope_map

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "olmo_hybrid_7b_hp3.json")) as f:
        cfg = json.load(f)
    model = build.build(cfg, 3 + cfg["embedx_dim"])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    B, T, H = cfg["batch_size"], cfg["seq_len"], cfg["hidden_size"]
    on = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)  # noqa: E731
    params = jax.tree.map(on, jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    emb, ids = (jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in ((B, T, H), (B, T)))
    compiled = jax.jit(jax.value_and_grad(model.apply, argnums=(0, 1), has_aux=True)).lower(
        params, emb, ids).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert compiled.memory_analysis().peak_memory_in_bytes < 9.6e9  # 9.28 today
    kernels = {n: s for n, s in scope_map(text).items() if "causal_attention" in n}
    assert set(kernels.values()) == {"model/attn/scores_full"}, kernels
    assert "model/linear_attn/delta_rule" in set(scope_map(text).values())
