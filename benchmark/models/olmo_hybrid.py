"""The program's Olmo-Hybrid share from a configuration file. The file's head
counts are what this chip holds (``reduced``); ``layer_types`` stays as
published in the file, and the model is given the first
``num_hidden_layers`` of it."""

from paddlebox_tpu.models import OlmoHybrid, OlmoHybridConfig


def build(cfg: dict, feat_width: int):
    if feat_width != 3 + cfg["hidden_size"]:
        raise ValueError(f"pull width {feat_width} is not 3 + hidden_size {cfg['hidden_size']}")
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"] or (cfg.get("rope_parameters") or {}).get(
            "rope_theta") is not None:
        raise ValueError("the program's layers are a silu SwiGLU, projections without biases and "
                         "full layers without positions")
    return OlmoHybrid(OlmoHybridConfig.from_dict({
        **cfg, "layer_types": cfg["layer_types"][:cfg["num_hidden_layers"]]}))
