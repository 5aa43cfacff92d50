"""The program's Trinity-Mini share from a configuration file. The file's
``num_experts`` is what this chip holds (``reduced``); the router keeps
``router_experts`` outputs. ``layer_types`` and ``num_dense_layers`` stay as
published in the file; the model is given the held ones
(``held_layer_types``, ``held_dense_layers``)."""

from paddlebox_tpu.models import Afmoe, AfmoeConfig


def build(cfg: dict, feat_width: int):
    if feat_width != 3 + cfg["hidden_size"]:
        raise ValueError(f"pull width {feat_width} is not 3 + hidden_size {cfg['hidden_size']}")
    if not (cfg["route_norm"] and cfg["score_func"] == "sigmoid"):
        raise ValueError("the program's router is a normalised sigmoid's")
    return Afmoe(AfmoeConfig.from_dict({
        **cfg, "num_experts": cfg["router_experts"], "experts_held": cfg["num_experts"],
        "layer_types": cfg["held_layer_types"], "num_dense_layers": cfg["held_dense_layers"]}))
