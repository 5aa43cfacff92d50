"""Operations one sample needs in Deep & Cross, forward and backward.
A multiply-add counts 2; the backward pass costs twice the forward (gradients
with respect to the inputs and to the weights; the first layer's inputs are
trained embeddings, so both are needed there too)."""


def flops_per_sample(cfg: dict) -> float:
    d = cfg["num_slots"] * (3 + cfg["embedx_dim"])
    dims = [d] + list(cfg["hidden"])
    tower = sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))
    cross = cfg["n_cross"] * (2 * d + 3 * d)  # x.w, then x0 * s + b + x
    head = 2 * (dims[-1] + d)
    return 3.0 * (tower + cross + head)
