"""A new key's row, as the deployment defines it: zeros, with the embed_w
column and the embedx block uniform in (-r, r) from a splitmix64 stream of
(table seed, key, column order). Written from the rule, in numpy; nothing of
the program is imported."""

from __future__ import annotations

import numpy as np

_G = np.uint64(0x9E3779B97F4A7C15)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = x + _G
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def init_rows(keys: np.ndarray, seed: int, width: int, init_cols, init_range: float):
    """float32 [n, width] pass-open rows of never-seen ``keys`` (uint64)."""
    keys = np.asarray(keys, np.uint64)
    rows = np.zeros((len(keys), width), np.float32)
    with np.errstate(over="ignore"):
        st = _splitmix64(np.uint64(seed) ^ _splitmix64(keys))
        for c in init_cols:
            st = st + _G
            u = (_splitmix64(st) >> np.uint64(40)).astype(np.float32) * np.float32(
                1.0 / 16777216.0)
            rows[:, c] = (np.float32(2.0) * u - np.float32(1.0)) * np.float32(init_range)
    return rows
