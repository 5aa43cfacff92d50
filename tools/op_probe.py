"""Device-op timing immune to dispatch latency: each op is iterated
K times inside ONE jitted fori_loop with a data dependency between
iterations, so per-op device time = (blocked wall - overhead) / K.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

NUM_SLOTS = 39
BATCH = 4096
ROWS = 2_514_944
L = NUM_SLOTS * BATCH
U = 131_072
W = 21
PW = 19
K = 30  # iterations inside the loop


def timed_loop(name, body, init):
    """body(carry, salt) -> carry. Chained K times inside one jit."""

    @jax.jit
    def run(init):
        def f(i, c):
            return body(c, i)

        return jax.lax.fori_loop(0, K, f, init)

    out = run(init)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = run(init)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / K * 1e3
    print(f"{name:44s} {dt:9.3f} ms")
    return dt


SWEEP_WIDTHS = (8, 16, 21, 24, 32, 64, 128)


def sweep_point_names():
    """Addressable scatter-sweep probe points, in run order (``only=``
    runs one of them)."""
    return [f"w{w}" for w in SWEEP_WIDTHS] + [
        "hints", "gather_set", "bf16",
    ]


def main():
    if "--list-sweep-points" in sys.argv:
        print("\n".join(sweep_point_names()))
        return
    only = None
    for a in sys.argv[1:]:
        if a.startswith("--scatter-sweep="):
            only = a.split("=", 1)[1]
    if only is not None:
        # single-point mode: skip the baseline probes so the per-point
        # subprocess pays backend init + ONE probe, nothing else
        if only not in sweep_point_names():
            print(f"unknown sweep point {only!r}; known: "
                  + " ".join(sweep_point_names()), file=sys.stderr)
            sys.exit(2)
        scatter_sweep(np.random.default_rng(0), only=only)
        return
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((ROWS, W)).astype(np.float32) * 0.01)
    rows_u = jnp.asarray(rng.integers(0, ROWS, U).astype(np.int32))
    rows_l = jnp.asarray(rng.integers(0, ROWS, L).astype(np.int32))
    inverse = jnp.asarray(rng.integers(0, U, L).astype(np.int32))
    gflat = jnp.asarray(rng.standard_normal((L, PW)).astype(np.float32))
    gu = jnp.asarray(rng.standard_normal((U, W)).astype(np.float32))
    preds = jnp.asarray(rng.random(BATCH).astype(np.float32))
    labels = jnp.asarray((rng.random(BATCH) < 0.2).astype(np.float32))

    # gather [U] rows from table
    timed_loop(
        "gather U=131k rows [2.5M,21]",
        lambda c, i: (c[0], c[1], jnp.take(c[0], c[1], axis=0).sum() + c[2] * 0),
        (table, rows_u, jnp.float32(0)),
    )

    # gather [L] rows
    timed_loop(
        "gather L=160k rows",
        lambda c, i: (c[0], c[1], jnp.take(c[0], c[1], axis=0).sum() + c[2] * 0),
        (table, rows_l, jnp.float32(0)),
    )

    # scatter-add U unique rows into table
    timed_loop(
        "scatter-add U=131k uniq [U,21] -> table",
        lambda c, i: (c[0].at[rows_u].add(c[1] * 1e-6), c[1]),
        (table, gu),
    )

    # scatter-add L dup rows into table-shaped accumulator
    timed_loop(
        "scatter-add L=160k dup [L,19] -> table acc",
        lambda c, i: (c[0].at[rows_l].add(c[1] * 1e-6), c[1]),
        (jnp.zeros((ROWS, PW)), gflat),
    )

    # segment_sum L->U
    timed_loop(
        "segment_sum L->U width 19",
        lambda c, i: (
            jax.ops.segment_sum(c[1], inverse, num_segments=U) * 1e-6 + c[0] * 0,
            c[1],
        ),
        (jnp.zeros((U, PW)), gflat),
    )

    # full-table elementwise update (adagrad-ish math on every row)
    def full_update(c, i):
        t, acc = c
        g = acc[:, :PW]
        g2 = t[:, 3:4] + jnp.sum(g * g, axis=1, keepdims=True)
        nt = t.at[:, 2 : 2 + PW].add(-0.05 * g / jnp.sqrt(g2 + 1e-8) * 0 + 1e-9)
        return (nt, acc)

    timed_loop(
        "full-table rowwise update [2.5M,21]",
        full_update,
        (table, jnp.zeros((ROWS, PW + 2))),
    )

    # AUC scatter 4096 -> 100k + saturation min
    def auc_body(c, i):
        pos, neg = c
        bucket = jnp.clip((preds * 100_000).astype(jnp.int32), 0, 99_999)
        il = (labels > 0.5).astype(jnp.int32)
        return (
            jnp.minimum(pos.at[bucket].add(il), 1 << 30),
            jnp.minimum(neg.at[bucket].add(1 - il), 1 << 30),
        )

    timed_loop(
        "auc update (2 scatters 4k->100k + min)",
        auc_body,
        (jnp.zeros(100_000, jnp.int32), jnp.zeros(100_000, jnp.int32)),
    )

    # device sort of L i32 (for on-device dedup option)
    timed_loop(
        "sort 160k i32 + argsort payload",
        lambda c, i: (jax.lax.sort_key_val(c[0] + i, c[1])[0], c[1]),
        (rows_l, jnp.arange(L, dtype=jnp.int32)),
    )

    # ragged expansion as _ragged_rows does it: a scatter of the segment
    # starts and a prefix sum over the L flat positions
    lens = jnp.asarray(rng.integers(0, 3, NUM_SLOTS * BATCH).astype(np.int32))

    def ragged(c, i):
        ln = c[0]
        begin = jnp.cumsum(ln) - ln
        marks = jnp.zeros((L,), jnp.int32).at[begin].add(
            1, mode="drop", indices_are_sorted=True
        )
        seg = jnp.cumsum(marks) - 1
        return (ln, seg.astype(jnp.float32).sum() * 0)

    timed_loop("ragged expand (scatter of starts + cumsum L)", ragged, (lens, jnp.float32(0)))

    if "--scatter-sweep" in sys.argv:
        scatter_sweep(rng)


def scatter_sweep(rng, only=None):
    """Candidate strategies against the ~16 ms scatter-add floor at
    U=131k/W=21 (VERDICT r4 item 5; box_wrapper.cu:31-456 PushCopy is the
    reference's hand-written answer to the same problem). Run on a HEALTHY
    chip; each row prints device ms/op. Interpretation notes inline.

    ``only`` restricts the run to one point of :func:`sweep_point_names`."""

    def want(name):
        return only is None or only == name

    if only is None:
        print("\n--- scatter strategy sweep (U=131k unique rows) ---")
    rows_np = np.sort(rng.choice(ROWS, U, replace=False).astype(np.int32))
    rows_s = jnp.asarray(rows_np)

    # width variants: the known non-monotonicity (W=8 fast, W=21 slow,
    # W=128 medium). A padded-width TABLE trades HBM for scatter speed.
    for w in SWEEP_WIDTHS:
        if not want(f"w{w}"):
            continue
        t = jnp.zeros((ROWS, w), jnp.float32)
        g = jnp.asarray(rng.standard_normal((U, w)).astype(np.float32))
        timed_loop(
            f"scatter-add uniq sorted W={w:<3d}",
            lambda c, i: (c[0].at[rows_s].add(c[1] * 1e-6), c[1]),
            (t, g),
        )

    if want("hints") or want("gather_set") or want("bf16"):
        t21 = jnp.zeros((ROWS, W), jnp.float32)
        g21 = jnp.asarray(rng.standard_normal((U, W)).astype(np.float32))

    # sorted + hint combos at W=21 (hints measured no-op before; re-check)
    if want("hints"):
        timed_loop(
            "scatter-add W=21 hints(sorted+unique)",
            lambda c, i: (
                c[0].at[rows_s].add(
                    c[1] * 1e-6, indices_are_sorted=True, unique_indices=True
                ),
                c[1],
            ),
            (t21, g21),
        )

    # gather-modify-SET (unique rows): scatter with set semantics instead
    # of add — different lowering, sometimes different cost
    if want("gather_set"):
        timed_loop(
            "gather+set W=21 (set semantics)",
            lambda c, i: (
                c[0].at[rows_s].set(jnp.take(c[0], rows_s, axis=0) + c[1] * 1e-6),
                c[1],
            ),
            (t21, g21),
        )

    # bf16 update payload into an f32 table (half the update bytes; the
    # read-modify-write of the table itself is unchanged)
    if want("bf16"):
        timed_loop(
            "scatter-add W=21 bf16 updates",
            lambda c, i: (
                c[0].at[rows_s].add((c[1] * 1e-6).astype(jnp.bfloat16).astype(jnp.float32)),
                c[1],
            ),
            (t21, g21),
        )


if __name__ == "__main__":
    main()
