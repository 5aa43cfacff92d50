"""Operations one sample needs in DeepFM, forward and backward (see dcn.py
for the counting rule)."""


def flops_per_sample(cfg: dict) -> float:
    S, D = cfg["num_slots"], cfg["embedx_dim"]
    dims = [S * (3 + D)] + list(cfg["hidden"])
    tower = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    head = 2 * dims[-1]
    fm = 4 * S * D + 2 * D  # sum v, v*v and its sum, (sum v)^2, the difference
    first = S
    return 3.0 * (tower + head + fm + first)
