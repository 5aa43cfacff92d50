"""The program's SDAR share from a configuration file. The file's
``num_experts`` is what this chip holds (``reduced``); the router keeps
``router_experts`` outputs. ``seq_len`` counts a record's keys: the
``data_len`` clean tokens and their noised copy."""

from paddlebox_tpu.models import Sdar, SdarConfig


def build(cfg: dict, feat_width: int):
    if feat_width != 3 + cfg["hidden_size"]:
        raise ValueError(f"pull width {feat_width} is not 3 + hidden_size {cfg['hidden_size']}")
    if not cfg["norm_topk_prob"] or cfg["hidden_act"] != "silu":
        raise ValueError("the program's router here is a softmax over the chosen logits, its gate silu")
    if cfg["seq_len"] != 2 * cfg["data_len"] or not 0 <= cfg["mask_id"] < cfg["vocab_size"]:
        raise ValueError("a record is data_len clean tokens and their noised copy, the MASK id a held row")
    return Sdar(SdarConfig.from_dict({
        **cfg, "num_experts": cfg["router_experts"], "experts_held": cfg["num_experts"]}))
