"""Traffic generator: slot-format text files of one training pass, from a seed.

One general generator for every mix under ``benchmark/traffic/``. A pass is

- ``train_records`` records whose key in slot f is drawn from a power law
  over that field's own ``field_cardinalities[f]`` values (the list is
  repeated over the configuration's slots): rank k in [1, N_f] with
  probability proportional to the integral of x**-zipf_s over [k, k+1). These
  come first in the pass and are what a window trains;
- then ``fill_records`` records of keys that occur nowhere else: they stand
  for the rest of a pass far longer than a host can load inside a run, and
  exist so that the pass's table has a deployment's size (the mix file gives
  the cut and its factor).

Every slot has a key space of its own, ranks are scattered over it (a key is
a hash upstream: hot keys are not neighbours), and every key lies in
[10**12, 10**13), so every line has the same width and a file is one uint8
array written with one ``tofile``.

Line: ``1 <label>.0 1 <key> 1 <key> ...`` — the label slot (one float) and
one 13-digit key per sparse slot.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

KEY_BASE = 10**12  # first key; 13 digits, no leading zero
FIELD_SPAN = 1 << 33  # key space of one slot; a field has fewer values
_SCATTER = 0x9E3779B1  # odd: rank -> rank * _SCATTER mod FIELD_SPAN is one to one
FILL_BASE = 3 * 10**12  # fill keys lie in [FILL_BASE, FILL_BASE + FILL_SPAN)
FILL_SPAN = 7 * 10**12
_FIELD = 16  # " 1 " + 13 digits


def field_sizes(mix: dict, num_slots: int) -> np.ndarray:
    sizes = np.resize(np.asarray(mix["field_cardinalities"], np.int64), num_slots)
    if sizes.min() < 1 or sizes.max() >= FIELD_SPAN or num_slots * FIELD_SPAN >= FILL_BASE - KEY_BASE:
        raise ValueError("field cardinalities or slots outside the key layout")
    return sizes


def draw_ranks(rng: np.random.Generator, n: int, sizes: np.ndarray, s: float) -> np.ndarray:
    """int64 [n, S] ranks in [1, N_f], the discretised power law x**-s."""
    u = rng.random((n, len(sizes)))
    top = sizes.astype(np.float64) + 1.0
    if s == 1.0:
        x = np.exp(u * np.log(top))
    else:
        x = (u * (top ** (1.0 - s) - 1.0) + 1.0) ** (1.0 / (1.0 - s))
    return np.clip(x.astype(np.int64), 1, sizes)


def draw(rng: np.random.Generator, n_train: int, n_fill: int, mix: dict, num_slots: int):
    """(keys uint64 [n_train + n_fill, S], labels uint8) of consecutive records."""
    sizes = field_sizes(mix, num_slots)
    ranks = draw_ranks(rng, n_train, sizes, float(mix["zipf_s"])).astype(np.uint64)
    base = KEY_BASE + np.arange(num_slots, dtype=np.uint64) * np.uint64(FIELD_SPAN)
    train = base + (ranks - np.uint64(1)) * np.uint64(_SCATTER) % np.uint64(FIELD_SPAN)
    fill = rng.integers(FILL_BASE, FILL_BASE + FILL_SPAN, (n_fill, num_slots), dtype=np.uint64)
    labels = (rng.random(n_train + n_fill) < float(mix["click_rate"])).astype(np.uint8)
    return np.concatenate([train, fill]), labels


def _digit_lut() -> np.ndarray:
    """[10000, 4] ASCII digits of 0000..9999."""
    v = np.arange(10_000)
    return np.stack([v // 1000, v // 100 % 10, v // 10 % 10, v % 10], 1).astype(
        np.uint8) + ord("0")


def encode_lines(keys: np.ndarray, labels: np.ndarray) -> np.ndarray:
    """uint8 [n, 6 + 16 * S] — the file's bytes, one row a line."""
    n, S = keys.shape
    lut = _digit_lut()
    out = np.empty((n, 5 + _FIELD * S + 1), np.uint8)
    out[:, :5] = np.frombuffer(b"1 0.0", np.uint8)
    out[:, 2] += labels
    body = out[:, 5:-1].reshape(n, S, _FIELD)
    body[:, :, :3] = np.frombuffer(b" 1 ", np.uint8)
    lead, rest = np.divmod(keys, np.uint64(10**12))
    body[:, :, 3] = lead.astype(np.uint8) + ord("0")
    rest = rest.astype(np.int64)
    body[:, :, 4:8] = lut[rest // 10**8]
    body[:, :, 8:12] = lut[rest // 10**4 % 10**4]
    body[:, :, 12:16] = lut[rest % 10**4]
    out[:, -1] = ord("\n")
    return out


def make_pass(dirpath: Optional[str], mix: dict, num_slots: int, seed: int):
    """The pass: (files, keys [n, S], labels [n]), records in file order.
    Each file is drawn from its own stream of (seed, file number) and written
    by its own thread; with ``dirpath`` None nothing is written."""
    n_train, n_files = int(mix["train_records"]), int(mix["n_files"])
    bounds = np.linspace(0, n_train + int(mix["fill_records"]), n_files + 1).astype(np.int64)

    def one(i: int):
        lo, hi = int(bounds[i]), int(bounds[i + 1])
        k = min(max(n_train - lo, 0), hi - lo)  # records of this file that are trained
        keys, labels = draw(np.random.default_rng([seed, i]), k, hi - lo - k, mix, num_slots)
        path = None
        if dirpath is not None:
            path = os.path.join(dirpath, f"pass-{i:03d}.txt")
            encode_lines(keys, labels).tofile(path)
        return path, keys, labels

    with ThreadPoolExecutor(max_workers=min(8, n_files)) as pool:
        files, keys, labels = zip(*pool.map(one, range(n_files)))
    return list(files), np.concatenate(keys), np.concatenate(labels)
