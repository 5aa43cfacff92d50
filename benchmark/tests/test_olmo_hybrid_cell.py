"""The Olmo-Hybrid cell (``olmo_hybrid_7b_hp3.pass_train``) in rehearsal on the
CPU: its files, its parameter count, its work counts, its readers and a
whole toy run with its controls.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import math
import os

import pytest

from benchmark import compare, control_olmo_hybrid, run as bench_run
from benchmark.tests import toy_olmo_hybrid
from benchmark.work import olmo_hybrid as work

SPEC = bench_run.load_json("BENCHMARK.json")
CELL = toy_olmo_hybrid.WORKLOAD
NEW = ["linear_attn_device_ms", "delta_rule_device_ms", "delta_rule_hbm_pct"]
SHARED = ["attn_device_ms", "full_scores_mfu_pct", "head_loss_device_ms", "train_mfu_pct",
          "superstep_peak_memory_gb", "model_device_ms"]
REDUCED = ["linear_num_key_heads", "linear_num_value_heads", "num_attention_heads",
           "num_hidden_layers", "num_key_value_heads", "vocab_size"]
HERE = os.path.dirname(os.path.dirname(__file__))


def _cfg():
    return bench_run.load_json("benchmark", "configs", "olmo_hybrid_7b_hp3.json")


def test_the_cell_resolves_to_files_that_exist_and_fit_each_other():
    cell = bench_run.resolve(SPEC, CELL)
    cfg, mix = cell["cfg"], cell["mix"]
    assert cell["chips"] == 1 and cfg["kind"] == "olmo_hybrid" and mix["driver"] == "pass_train_tokens"
    assert (mix["seq_len"], mix["vocab"], mix["zipf_s"]) == (cfg["seq_len"], cfg["vocab_size"], 1.0)
    assert (cfg["seq_len"], cfg["vocab_size"], cfg["batch_size"]) == (4096, 33451, 1)
    assert (mix["train_records"], mix["fill_records"], mix["n_files"]) == (32, 0, 1)
    assert math.gcd(7919, 33451) == 1  # the generator's scatter is one to one
    for d in ("models", "reference", "work"):
        assert os.path.exists(os.path.join(HERE, d, "olmo_hybrid.py"))
    mine = [m["name"] for m in bench_run.metrics_of(SPEC, "per_layer", CELL)]
    for name in mine:
        assert os.path.exists(os.path.join(HERE, "layer_metrics", name + ".py")), name
    assert os.path.exists(os.path.join(HERE, "limits", CELL + ".txt"))
    assert set(cell["limits"]) == {
        "early_loss_gap", "loss_gap", "logit_gap", "counter_gap", "sparse_grad_gap",
        "sparse_delta_gap", "dense_grad_gap", "dense_delta_gap"}  # no router: no flip share
    assert set(NEW + SHARED) <= set(mine)
    # nothing of another model's own: no experts, no latent attention, no window, no MTP
    assert not [n for n in mine if n.startswith(("experts_", "expert_", "moe_", "mla_", "window_",
                                                 "route_", "mtp_", "mhc_", "diffusion_", "loss_pos"))]
    assert [m["name"] for m in bench_run.metrics_of(SPEC, "end_to_end", CELL)] == [
        "train_samples_per_s", "setup_s"]
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    assert [m["name"] for m in SPEC["per_layer"][-3:]] == NEW
    assert all(listed[n]["workloads"] == [CELL] and listed[n]["layer"] == "Model"
               and listed[n]["moves"] == "train_samples_per_s" for n in NEW)
    assert all(listed[n]["workloads"][-1] == CELL for n in SHARED)
    assert [w["name"] for w in SPEC["workloads"]][-1] == CELL
    assert sum(w["chips"] == 4 for w in SPEC["workloads"]) == 0


def test_configuration_keeps_every_published_key_and_states_its_cut():
    cfg = _cfg()
    assert cfg["source"] == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json"
    published = dict(
        hidden_size=3840, intermediate_size=11008, linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True, rms_norm_eps=1e-6,
        model_type="olmo_hybrid", tie_word_embeddings=False, max_position_embeddings=65536,
        hidden_act="silu", attention_bias=False, rope_parameters={"rope_theta": None})
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8  # whole, as published
    assert sorted(cfg["reduced"]) == REDUCED
    assert cfg["published_counts"] == {
        "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30, "vocab_size": 100352}
    assert [cfg[k] for k in REDUCED] == [10, 10, 10, 4, 10, 33451]
    assert cfg["head_dim"] == 128 == 3840 // 30 and -(-100352 // 3) == 33451
    assert cfg["embedx_dim"] == cfg["hidden_size"] and cfg["num_slots"] == 1 and cfg["chunk"] == 64
    assert "3 that share each layer by heads" in cfg["deployment"] and "head_dim 128" in cfg["held_here"]
    assert len(cfg["assumed"]) >= 10 and any(a.startswith("seq_len 4096") for a in cfg["assumed"])
    glm = bench_run.load_json("benchmark", "configs", "glm47_flash_ep8.json")
    assert cfg["dense_opt"] == glm["dense_opt"] and cfg["sparse_opt"] == glm["sparse_opt"]
    entry = next(c for c in SPEC["configs"] if c["name"] == "olmo_hybrid_7b_hp3")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/olmo_hybrid_7b_hp3.json"


def test_the_dense_state_is_the_cuts_744_million_parameters():
    import jax

    from benchmark import program

    cfg = _cfg()
    build, ref, _ = program.kind_modules(cfg)
    model = build.build(cfg, 3 + 3840)
    mine = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(a.size for a in jax.tree.leaves(mine))
    linear = (2 * 3840 * 960 + 3840 * 1920 + 2 * 3840 * 10 + 2 * 10 + (960 + 960 + 1920) * 4
              + 3840 * 1920 + 192 + 1920 * 3840)
    full = 3 * 3840 * 1280 + 1280 * 3840 + 2 * 1280
    mlp, norms = 3 * 3840 * 11008, 2 * 3840
    head = 33451 * 3840 + 3840
    assert (linear, full, mlp) == (29_583_572, 19_663_360, 126_812_160)
    assert 3 * (linear + mlp + norms) + full + mlp + norms == 615_693_436  # one whole period
    assert n == 615_693_436 + head == 744_149_116  # x 16 B = 11.91 GB
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg, 3 + 3840), jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(shapes)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(shapes)]


def test_operation_and_byte_counts_against_hand_counts():
    c = _cfg()
    T = 4096
    lin = 2 * 3840 * (960 + 960 + 1920 + 10 + 10 + 1920 + 1920) + 6 * 10 * 96 * 192
    full = 2 * 3840 * 4 * 1280
    mlp = 2 * 3 * 3840 * 11008
    scores = 2 * 10 * 2 * 128 * T * (T + 1) / 2  # a full layer's causal QK^T and PV, a record
    fwd = (3 * lin + full + 4 * mlp + 2 * 3840 * 33451) * T + scores
    assert work.flops_per_sample(c) == pytest.approx(3 * fwd, rel=1e-12)
    assert 18.0e12 < work.flops_per_sample(c) < 19.0e12  # a step of one record
    assert work.full_scores_flops_per_step(c) == pytest.approx(3 * scores, rel=1e-12)
    # a token and head's forward: q, k (96 each), v (192), alpha, beta read, o (192) written
    assert work.delta_rule_bytes_per_step(c) == 3 * 2312 * T * 10 * 3 == 852_295_680


def test_readers_read_scopes_and_nothing_where_there_are_none():
    cell = {"cfg": _cfg()}
    peaks = bench_run.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    scopes = {"model/linear_attn/qkv_proj": 20.0, "model/linear_attn/short_conv": 4.0,
              "model/linear_attn/gates": 1.0, "model/linear_attn/delta_rule": 30.0,
              "model/linear_attn/out_norm_gate": 8.0, "model/linear_attn/out_proj": 7.0,
              "model/attn/qkv_proj": 5.0, "model/attn/qk_norm_rope": 1.0,
              "model/attn/scores_full": 4.0, "model/attn/out_proj": 2.0, "model/mlp": 150.0,
              "loss/head": 40.0}
    run = {"cell": cell, "peaks": peaks, "scope_times": {"scopes": scopes}}
    got = {n: bench_run.read_layer_metric(n, run) for n in NEW + SHARED[:3]}
    assert got["linear_attn_device_ms"] == 70.0 and got["delta_rule_device_ms"] == 30.0
    assert got["delta_rule_hbm_pct"] == pytest.approx(100 * 852_295_680 / 0.030 / 819e9)
    assert got["attn_device_ms"] == 12.0 and got["head_loss_device_ms"] == 40.0
    assert got["full_scores_mfu_pct"] == pytest.approx(
        100 * work.full_scores_flops_per_step(cell["cfg"]) / 0.004 / 197e12)
    assert all(0 < got[n] < 100 for n in ("delta_rule_hbm_pct", "full_scores_mfu_pct"))
    # a program without the scopes (the parent), another model's run: nothing, no raise
    bare = {"cell": cell, "peaks": peaks, "scope_times": None}
    assert [bench_run.read_layer_metric(n, bare) for n in NEW] == [None, None, None]
    for other in ("xing4_29b_a4b_ep8", "trinity_mini_ep8", "dcn_multislot"):
        run = {"cell": {"cfg": bench_run.load_json("benchmark", "configs", other + ".json")},
               "peaks": peaks, "scope_times": {"scopes": {"model/attn/scores_full": 4.0,
                                                          "model/mla/scores": 4.0}}}
        assert [bench_run.read_layer_metric(n, run) for n in NEW] == [None, None, None], other


def test_toy_olmo_hybrid_run_is_correct_and_its_controls_are_not():
    cell = toy_olmo_hybrid.cell()
    result = bench_run.run_cell(cell, SPEC, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["checks"]["counter_gap"][0] == 0 and result["checks"]["logit_gap"][0] < 1e-3
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    assert all(math.isfinite(v) for v, _ in result["checks"].values())
    ctl = control_olmo_hybrid.readings(cell, 11)
    for name in control_olmo_hybrid.CONTROLS:
        assert not ctl[name]["correct"] and "early_loss_gap" in ctl[name]["fails"], name
        assert ctl[name]["values"]["router_flip_share"] == 0.0  # no router: read, and no NaN
    assert ctl["bf16"]["values"]["dense_delta_gap"] > 10
    json.dumps(result)


def _readings():
    path = os.path.join(os.path.dirname(__file__), "data",
                        "control_readings.olmo_hybrid_7b_hp3.jsonl")
    return [json.loads(ln) for ln in open(path)] if os.path.exists(path) else []


def test_the_chip_readings_of_every_control_fail_the_cells_limits():
    """data/control_readings.olmo_hybrid_7b_hp3.jsonl: what
    ``benchmark.control_olmo_hybrid`` read on a v5e at the cell's own widths
    and record, the reference wholly in bfloat16, with beta = sigmoid(.) and
    with no decay, each in the program's place."""
    limits = bench_run.load_json("benchmark", "limits", CELL + ".json")
    seen = set()
    for r in _readings():
        assert r["workload"] == CELL
        correct, checks = compare.judge(r["values"], limits)
        assert not correct, (r, checks)
        seen.add((r["seed"], r["control"]))
    assert {c for _, c in seen} == set(control_olmo_hybrid.CONTROLS)
    assert all(sum(c == name for _, c in seen) >= 2 for name in control_olmo_hybrid.CONTROLS)
