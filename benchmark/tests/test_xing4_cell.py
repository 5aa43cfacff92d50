"""The Xing4 cell (``xing4_29b_a4b_ep8.pass_train``) in rehearsal on the CPU: its
files, its work counts, its readers and a whole toy run with its controls.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import compare, control_xing4, run as bench_run
from benchmark.tests import toy_xing4
from benchmark.work import xing4 as work

SPEC = bench_run.load_json("BENCHMARK.json")
CELL = toy_xing4.WORKLOAD
NEW = ["mhc_device_ms", "mhc_hbm_pct", "mhc_maps_device_ms"]
SHARED = ["mla_device_ms", "moe_device_ms", "head_loss_device_ms", "mla_scores_mfu_pct",
          "experts_mfu_pct", "expert_load_max_over_mean", "train_mfu_pct", "superstep_peak_memory_gb"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
           "num_nextn_predict_layers", "vocab_size"]


def _cfg():
    return bench_run.load_json("benchmark", "configs", "xing4_29b_a4b_ep8.json")


def test_the_cell_resolves_to_files_that_exist_and_fit_each_other():
    cell = bench_run.resolve(SPEC, CELL)
    cfg, mix = cell["cfg"], cell["mix"]
    assert cell["chips"] == 1 and cfg["kind"] == "xing4" and mix["driver"] == "pass_train_tokens"
    assert (mix["seq_len"], mix["vocab"], mix["zipf_s"]) == (cfg["seq_len"], cfg["vocab_size"], 1.0)
    assert (cfg["seq_len"], cfg["vocab_size"], cfg["batch_size"]) == (4096, 16384, 1)
    assert (mix["train_records"], mix["fill_records"], mix["n_files"]) == (32, 0, 1)
    assert 7919 % 2 == 1  # odd, and the slice a power of two: the generator's scatter is one to one
    here = os.path.dirname(os.path.dirname(__file__))
    for d in ("models", "reference", "work"):
        assert os.path.exists(os.path.join(here, d, "xing4.py"))
    mine = [m["name"] for m in bench_run.metrics_of(SPEC, "per_layer", CELL)]
    for name in mine:
        assert os.path.exists(os.path.join(here, "layer_metrics", name + ".py")), name
    assert os.path.exists(os.path.join(here, "limits", CELL + ".txt"))
    assert set(cell["limits"]) == {
        "early_loss_gap", "loss_gap", "logit_gap", "counter_gap", "sparse_grad_gap",
        "sparse_delta_gap", "dense_grad_gap", "dense_delta_gap", "router_flip_share"}
    assert set(NEW + SHARED) <= set(mine)
    # nothing of another model's own: no MTP, no grouped-query attention, no window, no diffusion
    assert not [n for n in mine if n.startswith(("mtp_", "attn_", "window_", "full_", "diffusion_"))]
    assert [m["name"] for m in bench_run.metrics_of(SPEC, "end_to_end", CELL)] == [
        "train_samples_per_s", "setup_s"]
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    assert all(listed[n]["workloads"] == [CELL] and listed[n]["layer"] == "Model"
               and listed[n]["moves"] == "train_samples_per_s" for n in NEW)
    assert all(CELL in listed[n]["workloads"] for n in SHARED)
    assert [w["name"] for w in SPEC["workloads"]].count(CELL) == 1


def test_configuration_keeps_every_published_key_and_states_its_cut():
    cfg = _cfg()
    if os.path.exists(CATALOG):  # every number of the catalog row, under the same key
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "Xing4.0-29B-A4B")
        assert cfg["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items() if k not in cfg or cfg[k] != v)
        assert differs == REDUCED
    published = dict(
        hidden_size=3584, intermediate_size=9216, moe_intermediate_size=1024, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_attention_heads=32, num_key_value_heads=32, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=2, scoring_func="sigmoid", topk_method="noaux_tc", n_group=1,
        topk_group=1, norm_topk_prob=True, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
        mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30, rope_theta=10000, rms_norm_eps=1e-6,
        model_type="xing4_0", tie_word_embeddings=False, max_position_embeddings=262144)
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    assert sorted(cfg["reduced"]) == REDUCED
    assert cfg["published_counts"] == {
        "num_hidden_layers": 40, "first_k_dense_replace": 2, "n_routed_experts": 64,
        "vocab_size": 131072, "num_nextn_predict_layers": 1}
    assert [cfg[k] for k in REDUCED] == [1, 8, 5, 0, 16384]
    assert cfg["router_experts"] == 64 and cfg["vocab_size"] * 8 == 131072 and cfg["experts_offset"] == 0
    assert cfg["embedx_dim"] == cfg["hidden_size"] and cfg["num_slots"] == 1
    assert "8 that share each layer" in cfg["deployment"] and "MTP" in cfg["held_here"]
    assert len(cfg["assumed"]) >= 10 and any(a.startswith("seq_len 4096") for a in cfg["assumed"])
    glm = bench_run.load_json("benchmark", "configs", "glm47_flash_ep8.json")
    assert cfg["dense_opt"] == glm["dense_opt"] and cfg["sparse_opt"] == glm["sparse_opt"]
    entry = next(c for c in SPEC["configs"] if c["name"] == "xing4_29b_a4b_ep8")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/xing4_29b_a4b_ep8.json"


def test_the_dense_state_is_the_cuts_700_million_parameters():
    import jax

    from benchmark import program

    cfg = _cfg()
    build, ref, _ = program.kind_modules(cfg)
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg, 3 + 3584), jax.random.PRNGKey(0))
    attn = (3584 * 768 + 768 + 768 * 6144 + 3584 * 576 + 512 + 512 * 8192 + 4096 * 3584)
    hc = 14336 * 24 + 24 + 3
    dense = attn + 2 * hc + 2 * 3584 + 3 * 3584 * 9216
    expert = 3 * 3584 * 1024
    moe = attn + 2 * hc + 2 * 3584 + 3584 * 64 + 64 + expert + 8 * expert  # the 64 biases are a buffer
    assert (attn, hc, dense, moe) == (28_411_136, 344_091, 128_196_918, 128_426_294 + 64)
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == dense + 4 * moe + 16384 * 3584 + 3584 == 700_625_934 + 4 * 64  # x 16 B = 11.21 GB
    mine = jax.eval_shape(build.build(cfg, 3 + 3584).init, jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(shapes)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(shapes)]


def test_operation_and_byte_counts_against_hand_counts():
    c = _cfg()
    T = 4096
    proj = 2 * (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256 + 32 * 128 * 3584)
    scores = 2 * 32 * (192 + 128) * (T + 1) / 2  # a query and layer: the model's own widths
    expert = 2 * 3 * 3584 * 1024
    maps = 2 * 14336 * 24
    expert_layer = 2 * 3584 * 64 + (1 + 4 * 8 / 64) * expert
    fwd = (5 * (proj + scores + 2 * maps) + 2 * 3 * 3584 * 9216 + 4 * expert_layer
           + 2 * 3584 * 16384)
    assert work.flops_per_sample(c) == pytest.approx(3 * fwd * T, rel=1e-12)
    assert 11.5e12 < work.flops_per_sample(c) < 12.5e12  # a step of one record
    assert work.scores_flops_per_step(c) == pytest.approx(3 * 5 * scores * T, rel=1e-12)
    assert work.experts_flops(c, 1000.0) == 3 * expert * 1000.0
    assert work.sublayers(c) == 10
    # a sublayer's forward: 4 streams read for the maps and pre, 4 + 1 read and 4 written for post_res
    assert work.mhc_bytes_per_step(c) == 3 * 10 * 13 * 3584 * 4 * T == 22_900_899_840


def test_readers_read_scopes_and_counters_and_nothing_where_there_are_none():
    cell = {"cfg": _cfg()}
    peaks = bench_run.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    scopes = {"model/hc_attn/maps": 19.0, "model/hc_mlp/maps": 18.0, "model/hc_attn/pre": 0.5,
              "model/hc_attn/post_res": 3.0, "model/hc_mlp/post_res": 5.0, "model/hc_out": 0.5,
              "model/mla/scores": 36.0, "model/mla/q_proj": 11.0, "model/moe/experts": 17.0,
              "model/moe/router": 6.0, "model/dense_mlp": 23.0, "loss/head": 12.0}
    run = {"cell": cell, "peaks": peaks, "scope_times": {"scopes": scopes},
           "counters_per_step": {"held_assignments": 9345.0, "expert_load_max_over_mean": 2.6}}
    got = {n: bench_run.read_layer_metric(n, run) for n in NEW + SHARED[:6]}
    assert got["mhc_device_ms"] == 46.0 and got["mhc_maps_device_ms"] == 37.0
    assert got["mhc_hbm_pct"] == pytest.approx(100 * 22_900_899_840 / 0.046 / 819e9)
    assert got["mla_device_ms"] == 47.0 and got["moe_device_ms"] == 23.0
    assert got["head_loss_device_ms"] == 12.0 and got["expert_load_max_over_mean"] == 2.6
    assert got["mla_scores_mfu_pct"] == pytest.approx(
        100 * work.scores_flops_per_step(cell["cfg"]) / 0.036 / 197e12)
    assert got["experts_mfu_pct"] == pytest.approx(
        100 * work.experts_flops(cell["cfg"], 9345.0) / 0.017 / 197e12)
    assert all(0 < got[n] < 100 for n in ("mhc_hbm_pct", "mla_scores_mfu_pct", "experts_mfu_pct"))
    # a program without the scopes (the parent), another model's run: nothing, no raise
    bare = {"cell": cell, "peaks": peaks, "scope_times": None}
    assert [bench_run.read_layer_metric(n, bare) for n in NEW] == [None, None, None]
    others = {"cell": {"cfg": bench_run.load_json("benchmark", "configs", "glm47_flash_ep8.json")},
              "peaks": peaks, "scope_times": {"scopes": {"model/mla/scores": 4.0}},
              "counters_per_step": {"held_assignments": 8192.0}}
    assert [bench_run.read_layer_metric(n, others) for n in NEW] == [None, None, None]


def test_toy_xing4_run_is_correct_and_its_controls_are_not():
    cell = toy_xing4.cell()
    result = bench_run.run_cell(cell, SPEC, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["checks"]["counter_gap"][0] == 0 and result["checks"]["logit_gap"][0] < 1e-4
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    ctl = control_xing4.readings(cell, 11)
    assert not ctl["bf16"]["correct"]
    assert {"early_loss_gap", "dense_delta_gap"} <= set(ctl["bf16"]["fails"])
    assert not ctl["sinkhorn2"]["correct"] and "dense_delta_gap" in ctl["sinkhorn2"]["fails"]
    # at the toy's widths the scores are a hundredth of the cell's and their scale hardly reaches the
    # loss: the fault shows in the attention leaves' moment, which the toy's limits do not hold
    assert ctl["no_mscale"]["values"]["dense_grad_gap_worst_leaf"] > 0.05
    assert all(ctl[c]["values"]["counter_gap"] == 0 for c in ("sinkhorn2", "no_mscale"))
    json.dumps(result)


def test_the_chip_readings_of_every_control_fail_the_cells_limits():
    """data/control_readings.xing4_29b_a4b_ep8.jsonl: what ``benchmark.control_xing4``
    read on a v5e at the cell's own widths and record (PR 42), the reference
    wholly in bfloat16, with 2 Sinkhorn rounds and with YaRN's factor left out
    of the softmax scale, each in the program's place."""
    limits = bench_run.load_json("benchmark", "limits", CELL + ".json")
    seen = set()
    for ln in open(os.path.join(os.path.dirname(__file__), "data",
                                "control_readings.xing4_29b_a4b_ep8.jsonl")):
        r = json.loads(ln)
        assert r["workload"] == CELL
        correct, checks = compare.judge(r["values"], limits)
        assert not correct, (r, checks)
        seen.add((r["seed"], r["control"]))
    assert {c for _, c in seen} == set(control_xing4.CONTROLS)
    assert all(sum(c == name for _, c in seen) >= 2 for name in control_xing4.CONTROLS)
