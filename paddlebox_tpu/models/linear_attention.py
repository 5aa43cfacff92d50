"""Linear attention's pieces, for a token model whose layers recur (Gated
DeltaNet: Yang, Kautz, Hatamizadeh, arXiv:2412.06464): the causal depthwise
short convolution, the gated delta rule in its chunked form (Yang et al.,
arXiv:2406.06484), and the gated RMSNorm on its output.

The rule, per head, with unit-norm keys and queries, S in R^{dv x dk} and S_0 = 0:

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,    o_t = S_t q_t.

Chunked form (``delta_rule``): inside a chunk of C tokens, with gamma the
cumulative log-decay from the chunk's start, the new values
u_t = beta_t (v_t - alpha_t S_{t-1} k_t) solve a unit lower-triangular system
(I + A) U = diag(beta) V - diag(beta e^gamma) K S_0^T, A[t, s] =
beta_t e^{gamma_t - gamma_s} (k_t . k_s) for s < t; its inverse is the
doubling product (I - A)(I + A^2)(I + A^4)... on the MXU over blocks of 16
rows, joined by forward substitution a block row at a time. Everything that
does not read S_0 is batched over every chunk and head; only S passes from
chunk to chunk, in a ``lax.scan`` of T / C steps. Decays enter as
differences of cumulative log-decays masked to t >= s before ``exp``, never
as e^gamma and e^-gamma apart. The rule is float32 throughout, its products
at ``highest``; the backward is jax's through the chunk scan (a state a
chunk kept).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from paddlebox_tpu.models.lm_layers import F32
from paddlebox_tpu.utils.monitor import STAT_ADD, STAT_SET

HI = lax.Precision.HIGHEST
INVERSE_BLOCK = 16  # rows of the diagonal blocks the triangular inverse's doubling product takes


def short_conv(x, w):
    """The causal depthwise convolution and its SiLU: x [B, T, C], w [K, C]
    -> y_t = silu(sum_j w[j] x_{t - K + 1 + j}), zero before the record's start."""
    K, T = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + T] * w[j] for j in range(K)))


def l2_normalize(x, eps: float = 1e-6):
    """x over the last axis divided by its norm: sqrt(sum x^2 + eps)."""
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_rms_norm(o, gate, w, eps: float):
    """RMSNorm over each head's value width (o [..., dv], one weight ``w`` [dv]
    for all heads) times silu of the gate."""
    return o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w * jax.nn.silu(gate)


def _product32(spec, a, b):
    """A product of the rule: float32 operands at ``highest``, float32 result."""
    return jnp.einsum(spec, a, b, precision=HI, preferred_element_type=F32)


def _doubling_inverse(A):
    """(I + A)^-1 for A [..., b, b] strictly lower triangular: the doubling
    product (I - A)(I + A^2)(I + A^4)... up to the power b (A^b = 0)."""
    b = A.shape[-1]
    inv, power, reach = jnp.eye(b, dtype=A.dtype) - A, A, 2
    while reach < b:
        power = _product32("...ij,...jk->...ik", power, power)
        inv = inv + _product32("...ij,...jk->...ik", inv, power)
        reach *= 2
    return inv


def unit_lower_inverse(A):
    """(I + A)^-1 for A [..., C, C] strictly lower triangular, float32 at
    ``highest``: the diagonal blocks of ``INVERSE_BLOCK`` rows by the doubling
    product, then forward substitution a block row at a time, each two
    products (the doubling product over all C rows loses digits where beta
    is near 2 and keys are alike: its powers grow before they cancel)."""
    C = A.shape[-1]
    b = min(INVERSE_BLOCK, C)
    if C % b:
        raise ValueError(f"chunk {C} is not a multiple of {b}")
    diag = [_doubling_inverse(A[..., i:i + b, i:i + b]) for i in range(0, C, b)]
    inv = diag[0]
    for r, d in zip(range(b, C, b), diag[1:]):  # inv holds the first r rows' inverse
        below = _product32("...ij,...jk->...ik", A[..., r:r + b, :r], inv)
        row = -_product32("...ij,...jk->...ik", d, below)
        pad = jnp.zeros(inv.shape[:-1] + (b,), inv.dtype)
        inv = jnp.concatenate([jnp.concatenate([inv, pad], axis=-1),
                               jnp.concatenate([row, d], axis=-1)], axis=-2)
    return inv


def delta_rule(q, k, v, beta, g, chunk: int, scope: str):
    """o_t = S_t q_t. q, k [B, T, H, dk] (unit rows), v [B, T, H, dv], beta and
    the log-decay g = log alpha [B, T, H], float32 -> o [B, T, H, dv] float32,
    in chunks of ``chunk`` tokens (a divisor of T). ``scope`` names the chunk
    scan's body too: a scan's body is traced under a name stack of its own."""
    B, T, H, dk = q.shape
    dv, C = v.shape[-1], min(chunk, T)
    if T % C:
        raise ValueError(f"seq_len {T} is not a multiple of chunk {C}")
    n = T // C
    STAT_ADD("model.linear_attn.chunked_sites")  # at trace time
    STAT_SET("model.linear_attn.chunk", C)

    def chunks(a):  # [B, T, H, ...] -> [n, B, H, C, ...]
        a = a.reshape(B, n, C, H, *a.shape[3:])
        return jnp.moveaxis(jnp.swapaxes(a, 2, 3), 1, 0)

    q, k, v, beta, g = map(chunks, (q, k, v, beta, g))
    t, s = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    gam = _product32("...s,ts->...t", g, (s <= t).astype(F32))  # [n, B, H, C]: prefix sums
    diff = gam[..., :, None] - gam[..., None, :]
    decay = jnp.exp(jnp.where(s <= t, diff, -jnp.inf))  # e^{gamma_t - gamma_s}, t >= s; else 0
    kk = _product32("...td,...sd->...ts", k, k)
    A = jnp.where(s < t, beta[..., :, None] * kk * decay, 0.0)
    inv = unit_lower_inverse(A)
    first = jnp.exp(gam)[..., None]  # e^{gamma_t}: the incoming state's decay to t
    W = _product32("...ts,...sd->...td", inv, (beta[..., None] * first) * k)  # [.., C, dk]
    U0 = _product32("...ts,...se->...te", inv, beta[..., None] * v)  # [.., C, dv]
    P = _product32("...td,...sd->...ts", q, k) * decay  # t >= s, the diagonal too
    Qd = first * q
    Kd = jnp.exp(gam[..., -1:] - gam)[..., None] * k  # e^{gamma_C - gamma_s} k_s
    last = jnp.exp(gam[..., -1])[..., None, None]  # [n, B, H, 1, 1]

    def step(M, xs):  # M = S^T [B, H, dk, dv]
        with jax.named_scope(scope):
            W, U0, P, Qd, Kd, last = xs
            U = U0 - _product32("...td,...de->...te", W, M)
            o = (_product32("...td,...de->...te", Qd, M)
                 + _product32("...ts,...se->...te", P, U))
            return last * M + _product32("...sd,...se->...de", Kd, U), o

    _, o = lax.scan(step, jnp.zeros((B, H, dk, dv), F32), (W, U0, P, Qd, Kd, last))
    return jnp.swapaxes(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, T, H, dv)
