"""The SmallThinker cell (``smallthinker_21b_ep8.pass_train``) in rehearsal on the
CPU: its files, its work counts, its readers and a whole toy run with its
controls.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import pytest

from benchmark import compare, control_smallthinker, run as bench_run
from benchmark.tests import toy_smallthinker
from benchmark.work import smallthinker as work

SPEC = bench_run.load_json("BENCHMARK.json")
CELL = toy_smallthinker.WORKLOAD
NEW = ["route_device_ms", "expert_block_fill_pct"]
SHARED = ["attn_device_ms", "window_scores_mfu_pct", "full_scores_mfu_pct", "moe_device_ms",
          "head_loss_device_ms", "experts_mfu_pct", "expert_load_max_over_mean"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg():
    return bench_run.load_json("benchmark", "configs", "smallthinker_21b_ep8.json")


def test_the_cell_resolves_to_files_that_exist_and_fit_each_other():
    cell = bench_run.resolve(SPEC, CELL)
    cfg, mix = cell["cfg"], cell["mix"]
    assert cell["chips"] == 1 and cfg["kind"] == "smallthinker"
    assert mix["driver"] == "pass_train_tokens"
    assert (mix["seq_len"], mix["vocab"], mix["zipf_s"]) == (16384, 18992, 1.0)
    assert (mix["train_records"], mix["fill_records"], mix["n_files"]) == (32, 0, 1)
    assert (cfg["seq_len"], cfg["vocab_size"], cfg["batch_size"]) == (16384, 18992, 1)
    assert 7919 % 2 and 18992 == 2 ** 4 * 1187  # the generator's scatter is one to one
    here = os.path.dirname(os.path.dirname(__file__))
    for d in ("models", "reference", "work"):
        assert os.path.exists(os.path.join(here, d, "smallthinker.py"))
    for m in bench_run.metrics_of(SPEC, "per_layer", CELL):
        assert os.path.exists(os.path.join(here, "layer_metrics", m["name"] + ".py")), m["name"]
    assert os.path.exists(os.path.join(here, "limits", CELL + ".txt"))
    assert set(cell["limits"]) == {
        "early_loss_gap", "loss_gap", "logit_gap", "counter_gap", "sparse_grad_gap",
        "sparse_delta_gap", "dense_grad_gap", "dense_delta_gap", "router_flip_share"}
    names = [m["name"] for m in bench_run.metrics_of(SPEC, "per_layer", CELL)]
    assert set(NEW + SHARED) <= set(names) and len(names) == 18 + len(NEW + SHARED)
    assert not [n for n in names if n.startswith(("mla_", "mtp_"))]  # GLM's own keep their lists
    assert [m["name"] for m in bench_run.metrics_of(SPEC, "end_to_end", CELL)] == [
        "train_samples_per_s", "setup_s"]
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    assert all(listed[n]["workloads"] == [CELL] for n in NEW)
    assert all(listed[n]["workloads"][-1] == CELL for n in SHARED)
    assert [m["name"] for m in SPEC["per_layer"][-2:]] == NEW  # new entries at the end
    assert SPEC["workloads"][-1]["name"] == CELL and SPEC["configs"][-1]["name"] == cfg["name"]


def test_configuration_keeps_every_published_key_and_states_its_cut():
    cfg = _cfg()
    if os.path.exists(CATALOG):  # every number of the catalog row, under the same key
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert cfg["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
        assert differs == sorted(cfg["reduced"])
    published = dict(
        hidden_size=2560, head_dim=128, num_attention_heads=28, num_key_value_heads=4,
        moe_ffn_hidden_size=768, moe_num_active_primary_experts=6, sliding_window_size=4096,
        rope_theta=1500000, rms_norm_eps=1e-6, max_position_embeddings=16384,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True, tie_word_embeddings=False,
        model_name="smallthinker_21b_instruct", rope_scaling=None)
    assert {k: cfg[k] for k in published} == published
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == [0, 1, 1, 1] * 13  # whole, 52
    assert sorted(cfg["reduced"]) == ["moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published_counts"] == {"num_hidden_layers": 52, "moe_num_primary_experts": 64,
                                       "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["moe_num_primary_experts"], cfg["vocab_size"]) == (
        4, 8, 18992)
    assert cfg["router_experts"] == 64 and cfg["vocab_size"] * 8 == 151936
    # published layers 0-3: one whole period, the full layer first
    assert cfg["held_layers"] == [0, 1, 2, 3]
    assert cfg["held_sliding_layout"] == cfg["sliding_window_layout"][:4] == [0, 1, 1, 1]
    assert cfg["held_rope_layout"] == cfg["rope_layout"][:4]
    assert cfg["seq_len"] == cfg["max_position_embeddings"] and cfg["embedx_dim"] == 2560
    assert "8 that share each layer" in cfg["deployment"] and len(cfg["assumed"]) >= 8
    trinity = bench_run.load_json("benchmark", "configs", "trinity_mini_ep8.json")
    assert cfg["dense_opt"] == trinity["dense_opt"]  # and the sparse rule but for the rows' range
    assert cfg["sparse_opt"] == {**trinity["sparse_opt"], "initial_range": 4.0}
    assert (cfg["attn_block"], cfg["loss_block"], cfg["expert_block"]) == (512, 1024, 4096)
    entry = next(c for c in SPEC["configs"] if c["name"] == "smallthinker_21b_ep8")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/smallthinker_21b_ep8.json"


def test_the_dense_state_is_the_cuts_322_million_parameters():
    import jax

    from benchmark import program

    cfg = _cfg()
    build, ref, _ = program.kind_modules(cfg)
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg, 3 + 2560), jax.random.PRNGKey(0))
    attn = 2560 * (3584 + 512 + 512) + 3584 * 2560
    experts = 8 * 3 * 2560 * 768
    layer = attn + 2 * 2560 + 2560 * 64 + experts
    assert (attn, experts, layer) == (20_971_520, 47_185_920, 68_326_400)
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == 4 * layer + 18992 * 2560 + 2560 == 321_927_680  # x 16 B = 5.15 GB
    mine = jax.eval_shape(build.build(cfg, 3 + 2560).init, jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(shapes)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(shapes)]


def test_operation_counts_against_hand_counts():
    c = _cfg()
    T = 16384
    proj = 2 * 2560 * (3584 + 512 + 512 + 3584)  # q, k, v, o
    window_pairs = sum(min(t + 1, 4096) for t in range(T))
    full_pairs = T * (T + 1) // 2
    assert work.visible_pairs(c, True) == window_pairs and work.visible_pairs(c, False) == full_pairs
    assert window_pairs / full_pairs == pytest.approx(0.4375, abs=1e-3)
    scores = lambda pairs: 2 * 28 * (128 + 128) * pairs  # noqa: E731  QK^T and PV, 28 heads
    expert = 2 * 3 * 2560 * 768
    layer = proj + 2 * 2560 * 64 + 6 * 8 / 64 * expert  # the router, 6 x 8/64 routed; no shared one
    fwd = T * (4 * layer + 2 * 2560 * 18992) + 3 * scores(window_pairs) + scores(full_pairs)
    assert work.flops_per_sample(c) == pytest.approx(3 * fwd, rel=1e-12)
    assert 28.0e12 < work.flops_per_sample(c) < 28.4e12  # a step of one record
    assert work.window_scores_flops_per_step(c) == pytest.approx(3 * 3 * scores(window_pairs), rel=1e-12)
    assert work.full_scores_flops_per_step(c) == pytest.approx(3 * scores(full_pairs), rel=1e-12)
    assert work.experts_flops(c, 1000.0) == 3 * expert * 1000.0
    # tiles of 512 a head: a full layer 528, a window layer 252 (a band of 8 + 1)
    tiles = lambda w: sum(min(i, w) + 1 for i in range(T // 512))  # noqa: E731
    assert (tiles(32), tiles(8)) == (528, 252)


def test_readers_read_scopes_and_counters_and_nothing_where_there_are_none():
    cell = {"cfg": _cfg()}
    peaks = bench_run.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    scopes = {"model/attn/scores_window": 100.0, "model/attn/scores_full": 70.0,
              "model/attn/qkv_proj": 30.0, "model/attn/qk_norm_rope": 12.0,
              "model/attn/out_proj": 15.0, "model/moe/experts": 40.0, "model/moe/router": 6.0,
              "model/moe/dispatch": 20.0, "model/moe/combine": 25.0, "loss/head": 30.0}
    run = {"cell": cell, "peaks": peaks, "scope_times": {"scopes": scopes},
           "counters_per_step": {"held_assignments": 49152.0, "expert_load_max_over_mean": 2.5,
                                 "block_rows": 65536.0, "unrouted_tokens": 28000.0}}
    got = {n: bench_run.read_layer_metric(n, run) for n in NEW + SHARED}
    assert got["route_device_ms"] == 26.0 and got["expert_block_fill_pct"] == 75.0
    assert got["attn_device_ms"] == 227.0 and got["moe_device_ms"] == 91.0
    assert got["head_loss_device_ms"] == 30.0 and got["expert_load_max_over_mean"] == 2.5
    assert got["window_scores_mfu_pct"] == pytest.approx(
        100 * work.window_scores_flops_per_step(cell["cfg"]) / 0.100 / 197e12)
    assert got["full_scores_mfu_pct"] == pytest.approx(
        100 * work.full_scores_flops_per_step(cell["cfg"]) / 0.070 / 197e12)
    assert got["experts_mfu_pct"] == pytest.approx(
        100 * work.experts_flops(cell["cfg"], 49152.0) / 0.040 / 197e12)
    assert all(0 < got[n] < 100 for n in ("window_scores_mfu_pct", "full_scores_mfu_pct",
                                          "experts_mfu_pct", "expert_block_fill_pct"))
    # a program without the scopes or the counters (the parent): nothing, and no raise
    bare = {"cell": cell, "peaks": peaks, "scope_times": None}
    assert [bench_run.read_layer_metric(n, bare) for n in NEW] == [None, None]
    trinitys = {"cell": cell, "scope_times": {"scopes": {"model/attn/qkvg_proj": 4.0}},
                "counters_per_step": {"held_assignments": 8192.0}}  # no block_rows counter
    assert [bench_run.read_layer_metric(n, trinitys) for n in NEW] == [None, None]


def test_toy_smallthinker_run_is_correct_and_its_controls_are_not():
    cell = toy_smallthinker.cell()
    result = bench_run.run_cell(cell, SPEC, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["checks"]["counter_gap"][0] == 0 and result["checks"]["logit_gap"][0] < 1e-4
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    for seed in (11, 12):
        ctl = control_smallthinker.readings(
            cell, seed, ("bf16", "router_after_attention", "kv_heads_swapped"))
        assert not ctl["bf16"]["correct"]
        assert {"early_loss_gap", "dense_delta_gap"} <= set(ctl["bf16"]["fails"])
        assert not ctl["router_after_attention"]["correct"]
        assert "router_flip_share" in ctl["router_after_attention"]["fails"]
        assert ctl["router_after_attention"]["values"]["counter_gap"] == 0  # the model's fault alone
        assert not ctl["kv_heads_swapped"]["correct"]  # a fault inside attention
        assert "early_loss_gap" in ctl["kv_heads_swapped"]["fails"]
    json.dumps(result)


def test_the_toys_counters_feed_the_fill_reader():
    from benchmark import spans
    from benchmark.drivers import pass_train_tokens

    run = pass_train_tokens.run(toy_smallthinker.cell(), spans.Recorder())
    counted = run["counters_per_step"]
    assert set(counted) == {"loss_in_window", "loss_past_window", "tokens", "held_assignments",
                            "expert_load_max_over_mean", "unrouted_tokens", "block_rows"}
    fill = bench_run.read_layer_metric("expert_block_fill_pct", run)
    assert fill == pytest.approx(100 * counted["held_assignments"] / counted["block_rows"])
    assert 0 < fill <= 100  # a step's rows are whole blocks of the toy's 8; this is their mean
    assert 0 < counted["unrouted_tokens"] < 4 * counted["tokens"]


def test_the_chip_readings_of_every_control_fail_the_cells_limits():
    """data/control_readings.smallthinker_21b_ep8.jsonl: what
    ``benchmark.control_smallthinker`` read on a v5e at the cell's own widths,
    record and rows of range 4 (PR 35), the reference wholly in bfloat16, with
    the router after attention and (one seed) with each fault inside attention
    in the program's place."""
    limits = bench_run.load_json("benchmark", "limits", CELL + ".json")
    seen = set()
    for ln in open(os.path.join(os.path.dirname(__file__), "data",
                                "control_readings.smallthinker_21b_ep8.jsonl")):
        r = json.loads(ln)
        assert r["workload"] == CELL
        correct, checks = compare.judge(r["values"], limits)
        assert not correct, (r, checks)
        seen.add((r["seed"], r["control"]))
    controls = {c for _, c in seen}
    assert controls == set(control_smallthinker.CONTROLS)
    assert all(sum(c == name for _, c in seen) >= 2 for name in ("bf16", "router_after_attention"))
