"""Traffic generator of token passes: slot-format text files of records that
hold one document window each, from a seed.

A record is ``seq_len`` token ids drawn independently by the power law of
``benchmark/gen.py`` (``draw_ranks``: rank k of ``vocab`` with probability
proportional to the integral of x**-zipf_s over [k, k+1)); a rank is scattered
over the vocabulary slice (frequent tokens are not neighbouring rows) and
``key = KEY_BASE + id`` (a key of 0 would be dropped by the parser).

Line: ``1 0.0 T <id>.0 ... T <key> ...``: the label slot (unused), the dense
float slot ``ids`` (the targets) and the sparse slot ``tokens``.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from benchmark import gen

KEY_BASE = gen.KEY_BASE
_SCATTER = 7919  # prime, divides no vocabulary slice in use: rank -> id is one to one


def draw_ids(rng: np.random.Generator, n: int, mix: dict) -> np.ndarray:
    """int64 [n, seq_len] token ids in [0, vocab)."""
    V, T = int(mix["vocab"]), int(mix["seq_len"])
    if np.gcd(_SCATTER, V) != 1:
        raise ValueError(f"vocab {V} shares a factor with {_SCATTER}")
    ranks = gen.draw_ranks(rng, n * T, np.asarray([V], np.int64), float(mix["zipf_s"]))
    return ((ranks[:, 0] - 1) * _SCATTER % V).reshape(n, T)


def encode_lines(ids: np.ndarray) -> str:
    T = ids.shape[1]
    return "".join(
        f"1 0.0 {T} " + " ".join(f"{i}.0" for i in row) + f" {T} "
        + " ".join(str(KEY_BASE + i) for i in row) + "\n"
        for row in ids.tolist())


def make_pass(dirpath: Optional[str], mix: dict, seed: int):
    """(files, ids [n, seq_len]) of the pass's records in file order; with
    ``dirpath`` None nothing is written."""
    n, n_files = int(mix["train_records"]) + int(mix["fill_records"]), int(mix["n_files"])
    bounds = np.linspace(0, n, n_files + 1).astype(np.int64)
    files, parts = [], []
    for i in range(n_files):
        ids = draw_ids(np.random.default_rng([seed, i]), int(bounds[i + 1] - bounds[i]), mix)
        path = None
        if dirpath is not None:
            path = os.path.join(dirpath, f"tokens-{i:03d}.txt")
            with open(path, "w") as f:
                f.write(encode_lines(ids))
        files.append(path)
        parts.append(ids)
    return files, np.concatenate(parts)
