"""The program's Xing4.0 share from a configuration file. The file's
``n_routed_experts`` is what this chip holds (``reduced``); the router keeps
``router_experts`` outputs. ``rope_scaling`` goes in as published
(``Xing4Config.from_dict`` flattens it)."""

from paddlebox_tpu.models import Xing4, Xing4Config


def build(cfg: dict, feat_width: int):
    if feat_width != 3 + cfg["hidden_size"]:
        raise ValueError(f"pull width {feat_width} is not 3 + hidden_size {cfg['hidden_size']}")
    if (cfg["scoring_func"], cfg["topk_method"], cfg["n_group"], cfg["norm_topk_prob"],
            cfg["n_shared_experts"], cfg["hidden_act"]) != ("sigmoid", "noaux_tc", 1, True, 1, "silu"):
        raise ValueError("the program's router here is sigmoid + bias over one group, renormalised, "
                         "beside one shared expert, its gate silu")
    return Xing4(Xing4Config.from_dict({
        **cfg, "n_routed_experts": cfg["router_experts"], "experts_held": cfg["n_routed_experts"]}))
