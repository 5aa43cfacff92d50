"""The least bytes one training step must move through HBM for the sparse
path: every distinct row of the batch read once at pull width, then read and
written once at full row width by the push. Nothing else is counted (not the
per-occurrence gather, the gradients, the resident pass arrays), so the share
is of the unavoidable traffic only."""


def bytes_per_step(cfg: dict, distinct_rows: float) -> float:
    pull = 3 + cfg["embedx_dim"]
    width = 5 + cfg["embedx_dim"]
    return 4.0 * distinct_rows * (pull + 2 * width)
