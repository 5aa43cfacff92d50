"""The program's DeepFM model from a configuration file."""

from paddlebox_tpu.models import DeepFM


def build(cfg: dict, feat_width: int):
    return DeepFM(cfg["num_slots"], feat_width=feat_width, embedx_dim=cfg["embedx_dim"],
                  hidden=tuple(cfg["hidden"]))
