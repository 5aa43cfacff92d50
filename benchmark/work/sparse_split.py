"""``sparse.bytes_per_step`` split into its two kernels: the pull's gather
reads every distinct row once at pull width; the push's scatter reads and
writes it once at full row width. The two add up to ``bytes_per_step``."""


def pull_bytes(cfg: dict, distinct_rows: float) -> float:
    return 4.0 * distinct_rows * (3 + cfg["embedx_dim"])


def push_bytes(cfg: dict, distinct_rows: float) -> float:
    return 4.0 * distinct_rows * 2 * (5 + cfg["embedx_dim"])
