"""Traffic generator of block-diffusion passes: slot-format text files of
records that hold one document window twice, clean and noised, from a seed.

A record is ``seq_len`` = 2 x ``data_len`` ids. The first half is
``data_len`` clean token ids drawn as ``benchmark/gen_tokens.py`` draws them
(``draw_ids``: the power law over the data ids, every id of the held slice but
``mask_id``, the slice's last; a rank scattered over them). The second half
is their noised copy: in every block of ``block_length`` positions a count
uniform on 1 .. ``block_length`` and that many positions, chosen without
replacement, replaced by ``mask_id`` (the fixed-count form of the linear
schedule: the loss weighs a masked position block_length / count). The noise
comes from the same ``np.random.default_rng([seed, i])`` stream, after the
file's clean ids. Lines as ``gen_tokens.encode_lines`` writes them: the dense
slot ``ids`` and the sparse slot ``tokens`` both hold the 2 x ``data_len``
ids, ``key = KEY_BASE + id``.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from benchmark import gen_tokens


def draw_records(rng: np.random.Generator, n: int, mix: dict) -> np.ndarray:
    """int64 [n, seq_len]: clean ids in [0, vocab - 1), then their noised copy."""
    V, T, L = int(mix["vocab"]), int(mix["seq_len"]), int(mix["data_len"])
    blk, mask_id = int(mix["block_length"]), int(mix["mask_id"])
    if T != 2 * L or L % blk or mask_id != V - 1:
        raise ValueError(f"a record of {T} is not {L} tokens twice in blocks of {blk}, or mask_id "
                         f"{mask_id} is not the last of {V} ids")
    clean = gen_tokens.draw_ids(rng, n, {**mix, "vocab": V - 1, "seq_len": L})
    count = rng.integers(1, blk + 1, size=(n, L // blk, 1))
    # a block's positions in a random order: the first ``count`` of them are masked
    order = np.argsort(np.argsort(rng.random((n, L // blk, blk)), axis=-1), axis=-1)
    masked = (order < count).reshape(n, L)
    return np.concatenate([clean, np.where(masked, mask_id, clean)], axis=1)


def make_pass(dirpath: Optional[str], mix: dict, seed: int):
    """(files, ids [n, seq_len]) of the pass's records in file order; with
    ``dirpath`` None nothing is written (``gen_tokens.make_pass``'s contract)."""
    n, n_files = int(mix["train_records"]) + int(mix["fill_records"]), int(mix["n_files"])
    bounds = np.linspace(0, n, n_files + 1).astype(np.int64)
    files, parts = [], []
    for i in range(n_files):
        ids = draw_records(np.random.default_rng([seed, i]), int(bounds[i + 1] - bounds[i]), mix)
        path = None
        if dirpath is not None:
            path = os.path.join(dirpath, f"tokens-{i:03d}.txt")
            with open(path, "w") as f:
                f.write(gen_tokens.encode_lines(ids))
        files.append(path)
        parts.append(ids)
    return files, np.concatenate(parts)
