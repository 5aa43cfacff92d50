"""The SDAR cell (``sdar_30b_a3b_ep8.pass_train``) in rehearsal on the CPU: its
files, its work counts, its generator, its readers and a whole toy run with
its controls.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os

import numpy as np
import pytest

from benchmark import compare, control_sdar, gen_diffusion, run as bench_run
from benchmark.tests import toy_sdar
from benchmark.work import sdar as work

SPEC = bench_run.load_json("BENCHMARK.json")
CELL = toy_sdar.WORKLOAD
NEW = ["diffusion_scores_mfu_pct", "loss_positions_pct"]
SHARED = ["attn_device_ms", "moe_device_ms", "head_loss_device_ms", "experts_mfu_pct",
          "expert_load_max_over_mean", "route_device_ms", "expert_block_fill_pct"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg():
    return bench_run.load_json("benchmark", "configs", "sdar_30b_a3b_ep8.json")


def test_the_cell_resolves_to_files_that_exist_and_fit_each_other():
    cell = bench_run.resolve(SPEC, CELL)
    cfg, mix = cell["cfg"], cell["mix"]
    assert cell["chips"] == 1 and cfg["kind"] == "sdar"
    assert mix["driver"] == "pass_train_diffusion"
    shared = ("seq_len", "data_len", "block_length", "mask_id")
    assert [mix[k] for k in shared] == [cfg[k] for k in shared] == [16384, 8192, 4, 18991]
    assert (mix["vocab"], mix["zipf_s"]) == (cfg["vocab_size"], 1.0) == (18992, 1.0)
    assert (mix["train_records"], mix["fill_records"], mix["n_files"]) == (32, 0, 1)
    assert cfg["batch_size"] == 1 and cfg["seq_len"] == 2 * cfg["data_len"]
    assert 18991 == 7 * 2713 and 7919 not in (7, 2713)  # the generator's scatter is one to one
    here = os.path.dirname(os.path.dirname(__file__))
    for d in ("models", "reference", "work"):
        assert os.path.exists(os.path.join(here, d, "sdar.py"))
    mine = [m["name"] for m in bench_run.metrics_of(SPEC, "per_layer", CELL)]
    for name in mine:
        assert os.path.exists(os.path.join(here, "layer_metrics", name + ".py")), name
    assert os.path.exists(os.path.join(here, "limits", CELL + ".txt"))
    assert set(cell["limits"]) == {
        "early_loss_gap", "loss_gap", "logit_gap", "counter_gap", "sparse_grad_gap",
        "sparse_delta_gap", "dense_grad_gap", "dense_delta_gap", "router_flip_share"}
    assert set(NEW + SHARED) <= set(mine)
    # nothing of another model's own: no latent attention, no MTP, no window
    assert not [n for n in mine if n.startswith(("mla_", "mtp_", "window_", "full_"))]
    assert [m["name"] for m in bench_run.metrics_of(SPEC, "end_to_end", CELL)] == [
        "train_samples_per_s", "setup_s"]
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    assert all(listed[n]["workloads"] == [CELL] for n in NEW)
    assert all(CELL in listed[n]["workloads"] for n in SHARED)
    names = [m["name"] for m in SPEC["per_layer"]]
    assert names.index(NEW[0]) + 1 == names.index(NEW[1]) > max(names.index(n) for n in SHARED)
    assert [w["name"] for w in SPEC["workloads"]].count(CELL) == 1


def test_configuration_keeps_every_published_key_and_states_its_cut():
    cfg = _cfg()
    if os.path.exists(CATALOG):  # every number of the catalog row, under the same key
        row = next(r for r in map(json.loads, open(CATALOG)) if r["name"] == "SDAR-30B-A3B-Chat")
        assert cfg["source"] == row["source_url"]
        differs = sorted(k for k, v in row["config"].items() if k not in cfg or cfg[k] != v)
        assert differs == sorted(cfg["reduced"])
    published = dict(
        hidden_size=2048, head_dim=128, num_attention_heads=32, num_key_value_heads=4,
        moe_intermediate_size=768, intermediate_size=6144, num_experts_per_tok=8,
        rope_theta=1000000, rms_norm_eps=1e-6, max_position_embeddings=32768,
        norm_topk_prob=True, tie_word_embeddings=False, hidden_act="silu", attention_bias=False,
        decoder_sparse_step=1, mlp_only_layers=[], model_type="sdar_moe", rope_scaling=None,
        sliding_window=None, use_sliding_window=False, max_window_layers=48)
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published_counts"] == {"num_hidden_layers": 48, "num_experts": 128,
                                       "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (4, 16, 18992)
    assert cfg["router_experts"] == 128 and cfg["vocab_size"] * 8 == 151936
    assert cfg["held_layers"] == [0, 1, 2, 3] and cfg["experts_offset"] == 0
    assert cfg["mask_id"] == cfg["vocab_size"] - 1 and cfg["embedx_dim"] == 2048
    assert "8 that share each layer" in cfg["deployment"] and len(cfg["assumed"]) >= 6
    assert any("block_length 4" in a for a in cfg["assumed"])
    trinity = bench_run.load_json("benchmark", "configs", "trinity_mini_ep8.json")
    assert cfg["dense_opt"] == trinity["dense_opt"]  # and the sparse rule but for the rows' range
    assert cfg["sparse_opt"] == {**trinity["sparse_opt"], "initial_range": 4.0}
    # PERF.md section 6 (PR 39) and the file's ``assumed`` have the readings the last was chosen from
    assert (cfg["attn_block"], cfg["loss_block"], cfg["expert_block"]) == (512, 1024, 7168)
    assert any("expert_block 7,168" in a for a in cfg["assumed"])
    entry = next(c for c in SPEC["configs"] if c["name"] == "sdar_30b_a3b_ep8")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/sdar_30b_a3b_ep8.json"


def test_the_dense_state_is_the_cuts_417_million_parameters():
    import jax

    from benchmark import program

    cfg = _cfg()
    build, ref, _ = program.kind_modules(cfg)
    shapes = jax.eval_shape(lambda k: ref.init(k, cfg, 3 + 2048), jax.random.PRNGKey(0))
    attn = 2048 * (4096 + 512 + 512) + 4096 * 2048 + 2 * 128
    experts = 16 * 3 * 2048 * 768
    layer = attn + 2 * 2048 + 2048 * 128 + experts
    assert (attn, experts, layer) == (18_874_624, 75_497_472, 94_638_336)
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert n == 4 * layer + 18992 * 2048 + 2048 == 417_451_008  # x 16 B = 6.68 GB
    mine = jax.eval_shape(build.build(cfg, 3 + 2048).init, jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(shapes)
    assert [a.shape for a in jax.tree.leaves(mine)] == [a.shape for a in jax.tree.leaves(shapes)]


def test_operation_counts_against_hand_counts():
    c = _cfg()
    T, L = 16384, 8192
    proj = 2 * 2048 * (4096 + 512 + 512 + 4096)  # q, k, v, o
    # a clean query sees 4 (blk + 1) keys, a noisy one 4 blk + 4: L^2 + 4 L pairs a head
    pairs = sum(4 * (i // 4 + 1) for i in range(L)) * 2
    assert work.visible_pairs(c) == pairs == L * L + 4 * L == 67_141_632
    scores = 2 * 32 * (128 + 128) * pairs  # QK^T and PV, 32 heads
    expert = 2 * 3 * 2048 * 768
    layer = proj + 2 * 2048 * 128 + 8 * 16 / 128 * expert  # the router, 8 x 16/128 routed; no shared
    fwd = 4 * (T * layer + scores) + L * 2 * 2048 * 18992  # the head on the noisy half alone
    assert work.flops_per_sample(c) == pytest.approx(3 * fwd, rel=1e-12)
    assert 24.4e12 < work.flops_per_sample(c) < 24.6e12  # a step of one record
    assert work.diffusion_scores_flops_per_step(c) == pytest.approx(3 * 4 * scores, rel=1e-12)
    assert work.diffusion_scores_flops_per_step(c) == 3 * 4 * 2 * 32 * 2 * 128 * 67_141_632
    assert work.experts_flops(c, 1000.0) == 3 * expert * 1000.0
    # tiles of 512 a head: 136 clean to clean, 136 noisy to clean, 16 noisy to noisy; a causal 2 L 528
    n = L // 512
    assert (n * (n + 1) // 2 * 2 + n, 2 * n * (2 * n + 1) // 2) == (288, 528)
    assert pairs / (288 * 512 * 512) == pytest.approx(0.889, abs=1e-3)  # what of the tiles is visible


def test_the_generators_records_obey_the_rule():
    mix = bench_run.load_json("benchmark", "traffic", "pass_diffusion.sdar.json")
    mix["train_records"] = 4
    _, recs = gen_diffusion.make_pass(None, mix, 3_900_000_077)  # a seed past 2**31
    L, n, mask = mix["data_len"], mix["block_length"], mix["mask_id"]
    assert recs.shape == (4, 2 * L) and recs.min() >= 0 and recs.max() == mask
    clean, noisy = recs[:, :L], recs[:, L:]
    assert clean.max() < mask  # data ids are the other 18,991
    masked = noisy == mask
    assert np.array_equal(noisy[~masked], clean[~masked])  # the second half is the first but for MASK
    per_block = masked.reshape(4, L // n, n).sum(-1)
    assert per_block.min() == 1 and per_block.max() == n  # every block 1 .. 4 masked
    counts = np.bincount(per_block.ravel(), minlength=n + 1)[1:] / per_block.size
    assert counts == pytest.approx([0.25] * 4, abs=0.02)  # the count uniform on 1 .. 4
    assert masked.mean() == pytest.approx(0.625, abs=0.01)  # 31% of the slot is one key
    assert masked.reshape(-1, n).mean(0) == pytest.approx([0.625] * 4, abs=0.02)  # any position alike
    # the power law over the data ids, scattered: the most frequent id is rank 1's
    top = np.bincount(clean.ravel(), minlength=mask).argmax()
    assert top == 0 and np.array_equal(recs, gen_diffusion.make_pass(None, mix, 3_900_000_077)[1])
    with pytest.raises(ValueError, match="not the last"):
        gen_diffusion.make_pass(None, {**mix, "mask_id": 5}, 1)


def test_readers_read_scopes_and_counters_and_nothing_where_there_are_none():
    cell = {"cfg": _cfg()}
    peaks = bench_run.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    scopes = {"model/attn/scores_diffusion": 150.0, "model/attn/qkv_proj": 30.0,
              "model/attn/qk_norm_rope": 12.0, "model/attn/out_proj": 15.0,
              "model/moe/experts": 40.0, "model/moe/router": 6.0, "model/moe/dispatch": 20.0,
              "model/moe/combine": 25.0, "loss/head": 30.0}
    run = {"cell": cell, "peaks": peaks, "scope_times": {"scopes": scopes},
           "counters_per_step": {"held_assignments": 65536.0, "expert_load_max_over_mean": 2.5,
                                 "block_rows": 98304.0, "unrouted_tokens": 21000.0,
                                 "tokens": 16384.0, "masked_positions": 5120.0}}
    got = {n: bench_run.read_layer_metric(n, run) for n in NEW + SHARED}
    assert got["loss_positions_pct"] == 31.25 and got["route_device_ms"] == 26.0
    assert got["attn_device_ms"] == 207.0 and got["moe_device_ms"] == 91.0
    assert got["head_loss_device_ms"] == 30.0 and got["expert_load_max_over_mean"] == 2.5
    assert got["expert_block_fill_pct"] == pytest.approx(100 * 65536 / 98304)
    assert got["diffusion_scores_mfu_pct"] == pytest.approx(
        100 * work.diffusion_scores_flops_per_step(cell["cfg"]) / 0.150 / 197e12)
    assert got["experts_mfu_pct"] == pytest.approx(
        100 * work.experts_flops(cell["cfg"], 65536.0) / 0.040 / 197e12)
    assert all(0 < got[n] < 100 for n in ("diffusion_scores_mfu_pct", "experts_mfu_pct",
                                          "expert_block_fill_pct", "loss_positions_pct"))
    # a program without the scope or the counter (the parent), another model's run: nothing, no raise
    bare = {"cell": cell, "peaks": peaks, "scope_times": None}
    assert [bench_run.read_layer_metric(n, bare) for n in NEW] == [None, None]
    others = {"cell": {"cfg": bench_run.load_json("benchmark", "configs", "trinity_mini_ep8.json")},
              "peaks": peaks, "scope_times": {"scopes": {"model/attn/scores_full": 4.0}},
              "counters_per_step": {"held_assignments": 8192.0, "tokens": 8192.0}}
    assert [bench_run.read_layer_metric(n, others) for n in NEW] == [None, None]


def test_toy_sdar_run_is_correct_and_its_controls_are_not():
    cell = toy_sdar.cell()
    result = bench_run.run_cell(cell, SPEC, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["checks"]["counter_gap"][0] == 0 and result["checks"]["logit_gap"][0] < 1e-4
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    for seed in (11, 12):
        ctl = control_sdar.readings(cell, seed)
        assert not ctl["bf16"]["correct"]
        assert {"early_loss_gap", "dense_delta_gap"} <= set(ctl["bf16"]["fails"])
        assert not ctl["leak"]["correct"]  # this architecture's own fault
        assert "dense_delta_gap" in ctl["leak"]["fails"]
        assert ctl["leak"]["values"]["counter_gap"] == 0  # the model's fault alone
    json.dumps(result)


def test_the_toys_counters_feed_both_new_readers():
    from benchmark import spans
    from benchmark.drivers import pass_train_diffusion

    cell = toy_sdar.cell()
    run = pass_train_diffusion.run(cell, spans.Recorder())
    counted = run["counters_per_step"]
    assert set(counted) == {"loss_first_half", "loss_second_half", "tokens", "held_assignments",
                            "expert_load_max_over_mean", "unrouted_tokens", "block_rows",
                            "masked_positions"}
    B, T = cell["cfg"]["batch_size"], cell["cfg"]["seq_len"]
    assert counted["tokens"] == B * T and run["ids_per_step"] == B * T and run["samples"] == run["steps"] * B
    share = bench_run.read_layer_metric("loss_positions_pct", run)
    assert share == pytest.approx(100 * counted["masked_positions"] / counted["tokens"])
    assert 25 < share < 37.5  # 31.25 in expectation: 62.5% of the noisy half
    fill = bench_run.read_layer_metric("expert_block_fill_pct", run)
    assert fill == pytest.approx(100 * counted["held_assignments"] / counted["block_rows"])
    assert 0 < fill <= 100 and 0 < counted["unrouted_tokens"] < 4 * counted["tokens"]
    with pytest.raises(ValueError, match="do not fit the configuration"):
        pass_train_diffusion.run({**cell, "mix": {**cell["mix"], "mask_id": 5}}, spans.Recorder())


def test_the_chip_readings_of_every_control_fail_the_cells_limits():
    """data/control_readings.sdar_30b_a3b_ep8.jsonl: what ``benchmark.control_sdar``
    read on a v5e at the cell's own widths and record (PR 39), the reference
    wholly in bfloat16 and with the leak in the program's place."""
    limits = bench_run.load_json("benchmark", "limits", CELL + ".json")
    seen = set()
    for ln in open(os.path.join(os.path.dirname(__file__), "data",
                                "control_readings.sdar_30b_a3b_ep8.jsonl")):
        r = json.loads(ln)
        assert r["workload"] == CELL
        correct, checks = compare.judge(r["values"], limits)
        assert not correct, (r, checks)
        seen.add((r["seed"], r["control"]))
    assert {c for _, c in seen} == set(control_sdar.CONTROLS)
    assert all(sum(c == name for _, c in seen) >= 2 for name in control_sdar.CONTROLS)
