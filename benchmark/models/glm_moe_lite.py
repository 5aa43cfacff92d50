"""The program's GLM-4.7-Flash share from a configuration file. The file's
``n_routed_experts`` is what this chip holds (``reduced``); the router keeps
``router_experts`` outputs."""

from paddlebox_tpu.models import GlmMoeLite, GlmMoeLiteConfig


def build(cfg: dict, feat_width: int):
    if feat_width != 3 + cfg["hidden_size"]:
        raise ValueError(f"pull width {feat_width} is not 3 + hidden_size {cfg['hidden_size']}")
    return GlmMoeLite(GlmMoeLiteConfig.from_dict({
        **cfg, "n_routed_experts": cfg["router_experts"], "experts_held": cfg["n_routed_experts"]}))
