"""The program's Deep & Cross model from a configuration file."""

from paddlebox_tpu.models import DCN


def build(cfg: dict, feat_width: int):
    return DCN(cfg["num_slots"], feat_width=feat_width, n_cross=cfg["n_cross"],
               hidden=tuple(cfg["hidden"]))
