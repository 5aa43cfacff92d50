"""The program's SmallThinker share from a configuration file. The file's
``moe_num_primary_experts`` is what this chip holds (``reduced``); the router
keeps ``router_experts`` outputs. ``rope_layout`` and ``sliding_window_layout``
stay as published in the file; the model is given the held layers' kinds
(``held_sliding_layout``, which ``held_rope_layout`` has to equal: rope on the
sliding layers alone)."""

from paddlebox_tpu.models import SmallThinker, SmallThinkerConfig


def build(cfg: dict, feat_width: int):
    if feat_width != 3 + cfg["hidden_size"]:
        raise ValueError(f"pull width {feat_width} is not 3 + hidden_size {cfg['hidden_size']}")
    if not (cfg["moe_primary_router_apply_softmax"] and cfg["norm_topk_prob"]):
        raise ValueError("the program's router here is a softmax over the chosen logits")
    if cfg["held_rope_layout"] != cfg["held_sliding_layout"]:
        raise ValueError("rope is on the sliding layers alone")
    return SmallThinker(SmallThinkerConfig.from_dict({
        **cfg, "layer_kinds": cfg["held_sliding_layout"],
        "sliding_window": cfg["sliding_window_size"],
        "moe_intermediate_size": cfg["moe_ffn_hidden_size"],
        "num_experts": cfg["router_experts"], "experts_held": cfg["moe_num_primary_experts"],
        "num_experts_per_tok": cfg["moe_num_active_primary_experts"]}))
