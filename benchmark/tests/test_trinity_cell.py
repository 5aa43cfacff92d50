"""The Trinity cell (``trinity_mini_ep8.pass_train``) in rehearsal on the CPU:
its files, its work counts, its readers and a whole toy run with its controls.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import compare, control_afmoe, run as bench_run, scope_prefix
from benchmark.tests import toy_trinity
from benchmark.work import afmoe as work

SPEC = bench_run.load_json("BENCHMARK.json")
CELL = toy_trinity.WORKLOAD
NEW = ["attn_device_ms", "window_scores_mfu_pct", "full_scores_mfu_pct"]
SHARED = ["moe_device_ms", "head_loss_device_ms", "experts_mfu_pct", "expert_load_max_over_mean"]
SLIDING, FULL = "sliding_attention", "full_attention"


def test_the_cell_resolves_to_files_that_exist_and_fit_each_other():
    cell = bench_run.resolve(SPEC, CELL)
    cfg, mix = cell["cfg"], cell["mix"]
    assert cell["chips"] == 1 and cfg["kind"] == "afmoe" and mix["driver"] == "pass_train_tokens"
    assert (mix["seq_len"], mix["vocab"], mix["zipf_s"]) == (8192, 25024, 1.0)
    assert (mix["train_records"], mix["fill_records"], mix["n_files"]) == (32, 0, 1)
    assert (cfg["seq_len"], cfg["vocab_size"], cfg["batch_size"]) == (8192, 25024, 1)
    here = os.path.dirname(os.path.dirname(__file__))
    for d in ("models", "reference", "work"):
        assert os.path.exists(os.path.join(here, d, "afmoe.py"))
    for m in bench_run.metrics_of(SPEC, "per_layer", CELL):
        assert os.path.exists(os.path.join(here, "layer_metrics", m["name"] + ".py")), m["name"]
    assert os.path.exists(os.path.join(here, "limits", CELL + ".txt"))
    assert set(cell["limits"]) <= {
        "early_loss_gap", "loss_gap", "logit_gap", "counter_gap", "sparse_grad_gap",
        "sparse_delta_gap", "dense_grad_gap", "dense_delta_gap", "router_flip_share"}
    names = [m["name"] for m in bench_run.metrics_of(SPEC, "per_layer", CELL)]
    assert set(NEW + SHARED) <= set(names) and len(names) == 18 + len(NEW + SHARED)
    assert not [n for n in names if n.startswith(("mla_", "mtp_"))]  # GLM's own keep their lists
    assert [m["name"] for m in bench_run.metrics_of(SPEC, "end_to_end", CELL)] == [
        "train_samples_per_s", "setup_s"]


def test_configuration_keeps_every_published_width_and_states_its_cut():
    cfg = bench_run.load_json("benchmark", "configs", "trinity_mini_ep8.json")
    published = dict(
        hidden_size=2048, head_dim=128, num_attention_heads=32, num_key_value_heads=4,
        intermediate_size=6144, moe_intermediate_size=1024, num_experts_per_tok=8,
        num_shared_experts=1, sliding_window=2048, global_attn_every_n_layers=4,
        num_dense_layers=2, route_scale=2.826, route_norm=True, score_func="sigmoid",
        rope_theta=10000, rms_norm_eps=1e-5, mup_enabled=True, load_balance_coeff=0.001,
        max_position_embeddings=131072, model_type="afmoe", tie_word_embeddings=False)
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"] == ([SLIDING] * 3 + [FULL]) * 8  # as published, whole
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published_counts"] == {"num_hidden_layers": 32, "num_experts": 128,
                                       "vocab_size": 200192}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (5, 16, 25024)
    assert cfg["router_experts"] == 128 and cfg["vocab_size"] * 8 == 200192
    # published layers 1-5: a leading dense layer, then one whole period of expert layers
    assert cfg["held_layer_types"] == cfg["layer_types"][1:6] and cfg["held_dense_layers"] == 1
    assert cfg["held_layer_types"][1:] == [SLIDING, FULL, SLIDING, SLIDING]
    assert "8 that share each layer" in cfg["deployment"] and len(cfg["assumed"]) >= 6
    entry = next(c for c in SPEC["configs"] if c["name"] == "trinity_mini_ep8")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]


def test_the_dense_state_is_the_cuts_654_million_parameters():
    import jax

    from benchmark import program

    cfg = bench_run.load_json("benchmark", "configs", "trinity_mini_ep8.json")
    build, ref, _ = program.kind_modules(cfg)
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg, 3 + 2048), jax.random.PRNGKey(0))
    attn = 2048 * (4096 + 512 + 512 + 4096) + 4096 * 2048 + 2 * 128
    dense = attn + 4 * 2048 + 3 * 2048 * 6144
    expert = attn + 4 * 2048 + 2048 * 128 + 128 + 3 * 2048 * 1024 + 16 * 3 * 2048 * 1024
    assert (attn, dense, expert) == (27_263_232, 65_020_160, 134_488_448)
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == dense + 4 * expert + 25024 * 2048 + 2048 == 654_225_152
    mine = jax.eval_shape(build.build(cfg, 3 + 2048).init, jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(shapes)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(shapes)]


def test_operation_counts_against_hand_counts():
    c = bench_run.load_json("benchmark", "configs", "trinity_mini_ep8.json")
    T = 8192
    proj = 2 * 2048 * (4096 + 512 + 512 + 4096 + 4096)  # q, k, v, gate, o
    window_pairs = sum(min(t + 1, 2048) for t in range(T))
    full_pairs = T * (T + 1) // 2
    assert work.visible_pairs(c, True) == window_pairs and work.visible_pairs(c, False) == full_pairs
    assert window_pairs / full_pairs == pytest.approx(0.437, abs=1e-3)
    scores = lambda pairs: 2 * 32 * (128 + 128) * pairs  # noqa: E731  QK^T and PV, 32 heads
    expert = 2 * 3 * 2048 * 1024
    layer = 2 * 2048 * 128 + expert + 8 * 16 / 128 * expert  # router, shared, 8 x 16/128 routed
    fwd = (T * (5 * proj + 2 * 3 * 2048 * 6144 + 4 * layer + 2 * 2048 * 25024)
           + 4 * scores(window_pairs) + scores(full_pairs))
    assert work.flops_per_sample(c) == pytest.approx(3 * fwd, rel=1e-12)
    assert 18.0e12 < work.flops_per_sample(c) < 18.3e12  # a step of one record
    assert work.window_scores_flops_per_step(c) == pytest.approx(3 * 4 * scores(window_pairs), rel=1e-12)
    assert work.full_scores_flops_per_step(c) == pytest.approx(3 * scores(full_pairs), rel=1e-12)
    assert work.experts_flops(c, 1000.0) == 3 * expert * 1000.0


def test_new_readers_read_scopes_and_counters_and_nothing_where_there_are_none():
    cell = {"cfg": bench_run.load_json("benchmark", "configs", "trinity_mini_ep8.json")}
    peaks = bench_run.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    scopes = {"model/attn/scores_window": 40.0, "model/attn/scores_full": 25.0,
              "model/attn/qkvg_proj": 12.0, "model/attn/qk_norm_rope": 6.0,
              "model/attn/out_proj": 7.0, "model/moe/experts": 10.0, "model/moe/shared": 6.0,
              "loss/head": 30.0, "model/dense_mlp": 20.0}
    run = {"cell": cell, "peaks": peaks, "scope_times": {"scopes": scopes},
           "counters_per_step": {"held_assignments": 8192.0, "expert_load_max_over_mean": 2.5}}
    got = {n: bench_run.read_layer_metric(n, run) for n in NEW + SHARED}
    assert got["attn_device_ms"] == 90.0 and got["moe_device_ms"] == 16.0
    assert got["head_loss_device_ms"] == 30.0 and got["expert_load_max_over_mean"] == 2.5
    assert got["window_scores_mfu_pct"] == pytest.approx(
        100 * work.window_scores_flops_per_step(cell["cfg"]) / 0.040 / 197e12)
    assert got["full_scores_mfu_pct"] == pytest.approx(
        100 * work.full_scores_flops_per_step(cell["cfg"]) / 0.025 / 197e12)
    assert got["experts_mfu_pct"] == pytest.approx(
        100 * work.experts_flops(cell["cfg"], 8192.0) / 0.010 / 197e12)
    assert all(0 < got[n] < 100 for n in ("window_scores_mfu_pct", "full_scores_mfu_pct",
                                          "experts_mfu_pct"))
    # a program without the scopes (the parent), and another kind's cell: nothing, and no raise
    bare = {"cell": cell, "peaks": peaks, "scope_times": None}
    assert [bench_run.read_layer_metric(n, bare) for n in NEW] == [None] * len(NEW)
    glm = {"cell": {"cfg": bench_run.load_json("benchmark", "configs", "glm47_flash_ep8.json")},
           "peaks": peaks, "scope_times": {"scopes": {"model/mla/scores": 4.0, "loss/head": 9.0}}}
    assert [bench_run.read_layer_metric(n, glm) for n in NEW] == [None] * len(NEW)
    assert scope_prefix.ms(glm, lambda s: s.startswith("loss")) == 9.0
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    assert all(listed[n]["workloads"] == [CELL] for n in NEW)
    assert all(listed[n]["workloads"] == ["glm47_flash_ep8.pass_train", CELL] for n in SHARED)


def test_toy_trinity_run_is_correct_and_both_controls_are_not():
    cell = toy_trinity.cell()
    result = bench_run.run_cell(cell, SPEC, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["checks"]["counter_gap"][0] == 0 and result["checks"]["logit_gap"][0] < 1e-4
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    for seed in (11, 12):
        ctl = control_afmoe.readings(cell, seed)
        assert not ctl["bf16"]["correct"]
        assert {"early_loss_gap", "router_flip_share"} <= set(ctl["bf16"]["fails"])
        assert not ctl["window_ignored"]["correct"]
        assert {"early_loss_gap", "router_flip_share"} <= set(ctl["window_ignored"]["fails"])
        assert ctl["window_ignored"]["values"]["counter_gap"] == 0  # the fault is the model's alone
    json.dumps(result)


def test_the_chip_readings_of_both_controls_fail_the_cells_limits():
    """data/control_readings.trinity_mini_ep8.jsonl: what ``benchmark.control_afmoe``
    read on a v5e at the cell's own widths and batch (PR 33), the reference
    wholly in bfloat16 and with the window ignored in the program's place."""
    limits = bench_run.load_json("benchmark", "limits", CELL + ".json")
    seen = set()
    for ln in open(os.path.join(os.path.dirname(__file__), "data",
                                "control_readings.trinity_mini_ep8.jsonl")):
        r = json.loads(ln)
        assert r["workload"] == CELL
        correct, checks = compare.judge(r["values"], limits)
        assert not correct, (r, checks)
        seen.add((r["seed"], r["control"]))
    controls = {c for _, c in seen}
    assert controls == {"bf16", "window_ignored"}
    assert all(sum(c == name for _, c in seen) >= 4 for name in controls)
