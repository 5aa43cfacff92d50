"""Operations and bytes of Xing4.0's share as the configuration file cuts it.
The branches are ``glm_moe_lite``'s at this model's numbers (no MTP module:
``num_nextn_predict_layers`` 0 takes its terms away there; the scores count
the model's own 192 + 128 a head, so the zero columns the kernel adds show as
a lower share, not as more work), and its counts serve as they stand. The
hyper-connections add the maps' product (n C x (2 n + n^2) a sublayer and
token) to the operations, and their own bytes: a mechanism with no
matrix-product work to speak of is measured against the HBM peak."""

from benchmark.work import glm_moe_lite as glm
from benchmark.work.glm_moe_lite import experts_flops, scores_flops_per_step  # noqa: F401 (the readers')


def sublayers(c: dict) -> int:
    """Hyper-connections a step runs: around attention and around the feed-forward of every layer."""
    return 2 * c["num_hidden_layers"]


def maps_forward_per_token(c: dict) -> float:
    n = c["hc_mult"]
    return 2.0 * n * c["hidden_size"] * (2 * n + n * n)


def flops_per_sample(c: dict) -> float:
    """A sample is one record of seq_len tokens; forward and backward."""
    return glm.flops_per_sample(c) + 3.0 * sublayers(c) * maps_forward_per_token(c) * c["seq_len"]


def mhc_bytes_per_step(c: dict) -> float:
    """The least bytes the hyper-connections must move in a step. A sublayer's
    forward reads the n streams once for the maps and ``pre`` (n C), reads
    them and the branch's output and writes the new streams for ``post_res``
    (n + 1 + n): (3 n + 1) C float32 a token; the backward twice that;
    recomputed passes do not count. 22.9 GB at 4,096 tokens, n = 4, C = 3,584,
    10 sublayers."""
    n = c["hc_mult"]
    per_token = (3 * n + 1) * c["hidden_size"] * 4.0
    return 3.0 * sublayers(c) * per_token * c["seq_len"] * c["batch_size"]
