"""The token cell (``glm47_flash_ep8.pass_train``) in rehearsal on the CPU:
its generator, its work counts, its readers and a whole toy run.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import tempfile

import numpy as np
import pytest

from benchmark import compare, control_tokens, gen_tokens, run as bench_run, scope_prefix
from benchmark.drivers import pass_train_tokens
from benchmark.tests import toy_tokens
from benchmark.work import glm_moe_lite as work

SPEC = bench_run.load_json("BENCHMARK.json")
CELL = "glm47_flash_ep8.pass_train"
NEW = ["mla_device_ms", "moe_device_ms", "mtp_device_ms", "head_loss_device_ms",
       "mla_scores_mfu_pct", "experts_mfu_pct", "expert_load_max_over_mean"]


def test_configuration_keeps_every_published_width_and_states_its_cut():
    cfg = bench_run.load_json("benchmark", "configs", "glm47_flash_ep8.json")
    published = dict(
        hidden_size=2048, intermediate_size=10240, moe_intermediate_size=1536,
        num_attention_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=1.8, first_k_dense_replace=1, num_nextn_predict_layers=1,
        rope_theta=1000000, rms_norm_eps=1e-5, norm_topk_prob=True, topk_method="noaux_tc")
    assert {k: cfg[k] for k in published} == published
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published_counts"] == {"num_hidden_layers": 47, "n_routed_experts": 64,
                                       "vocab_size": 154880}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 8, 19360)
    assert cfg["router_experts"] == 64 and cfg["vocab_size"] * 8 == 154880
    entry = next(c for c in SPEC["configs"] if c["name"] == "glm47_flash_ep8")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    mix = bench_run.load_json("benchmark", "traffic", "pass_tokens.glm47.json")
    assert (mix["seq_len"], mix["vocab"]) == (cfg["seq_len"], cfg["vocab_size"])


def test_operation_counts_against_hand_counts():
    c = bench_run.load_json("benchmark", "configs", "glm47_flash_ep8.json")
    mla = 2 * (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048)
    scores = 2 * 20 * (256 + 256) * 4097 / 2  # QK^T and PV over the causal half
    expert = 2 * 3 * 2048 * 1536
    layer = 2 * 2048 * 64 + expert + 0.5 * expert  # router, shared, 4 x 8/64 routed
    fwd = (6 * (mla + scores) + 2 * 3 * 2048 * 10240 + 5 * layer + 2 * 4096 * 2048
           + 2 * 2 * 2048 * 19360)
    assert work.flops_per_sample(c) == pytest.approx(3 * fwd * 4096, rel=1e-12)
    assert 23e12 < 2 * work.flops_per_sample(c) < 24e12  # a step of two records
    assert work.scores_flops_per_step(c) == pytest.approx(3 * 6 * scores * 8192, rel=1e-12)
    assert work.experts_flops(c, 1000.0) == 3 * expert * 1000.0


def test_generator_draws_token_records_that_load_through_the_dataset():
    c = toy_tokens.cell()
    mix = {**c["mix"], "vocab": 19360, "seq_len": 256, "train_records": 48}
    _, ids = gen_tokens.make_pass(None, mix, 2**31 + 11)
    assert ids.shape == (48, 256) and ids.min() >= 0 and ids.max() < 19360
    # exponent 1 over V ids: P(rank 1) = ln 2 / ln(V + 1); rank 1 is id 0, rank 2 is id 7919
    assert np.mean(ids == 0) == pytest.approx(np.log(2) / np.log(19361), rel=0.1)
    assert np.mean(ids == 7919) == pytest.approx(np.log(1.5) / np.log(19361), rel=0.15)
    assert np.array_equal(gen_tokens.make_pass(None, mix, 2**31 + 11)[1], ids)
    with tempfile.TemporaryDirectory() as d:
        files, ids = gen_tokens.make_pass(d, c["mix"], c["seed"])
        first = open(files[0]).readline().split()
        _, ds = pass_train_tokens.make_dataset(c["cfg"], c["seed"])
        ds.set_filelist(files)
        ds.load_into_memory()
        ds.begin_pass()
    T = c["cfg"]["seq_len"]
    assert first[:3] == ["1", "0.0", str(T)] and first[3 + T] == str(T)
    assert [float(v) for v in first[3:3 + T]] == ids[0].tolist()
    assert [int(v) for v in first[4 + T:]] == (ids[0] + gen_tokens.KEY_BASE).tolist()
    assert ds.store is not None and np.all(ds.store.key_counts() == T)
    assert np.array_equal(ds.ws.sorted_keys, np.unique(ids + gen_tokens.KEY_BASE))
    assert np.array_equal(ds.store.float_slot_matrix(1, T), ids)


def test_new_readers_read_scopes_and_counters_and_nothing_where_there_are_none():
    cell = {"cfg": bench_run.load_json("benchmark", "configs", "glm47_flash_ep8.json")}
    peaks = bench_run.load_json("benchmark", "peaks.json")["TPU v5 lite"]
    scopes = {"model/mla/scores": 40.0, "model/mla/q_proj": 5.0, "model/mtp/mla/scores": 10.0,
              "model/moe/experts": 8.0, "model/mtp/moe/experts": 2.0, "model/moe/shared": 6.0,
              "model/mtp/eh_proj": 2.0, "loss/head": 30.0, "model/dense_mlp": 20.0}
    run = {"cell": cell, "peaks": peaks, "scope_times": {"scopes": scopes},
           "counters_per_step": {"held_assignments": 20480.0, "expert_load_max_over_mean": 1.3}}
    got = {n: bench_run.read_layer_metric(n, run) for n in NEW}
    assert got["mla_device_ms"] == 45.0 and got["moe_device_ms"] == 14.0
    assert got["mtp_device_ms"] == 14.0 and got["head_loss_device_ms"] == 30.0
    assert got["mla_scores_mfu_pct"] == pytest.approx(
        100 * work.scores_flops_per_step(cell["cfg"]) / 0.050 / 197e12)
    assert got["experts_mfu_pct"] == pytest.approx(
        100 * work.experts_flops(cell["cfg"], 20480.0) / 0.010 / 197e12)
    assert 0 < got["mla_scores_mfu_pct"] < 100 and 0 < got["experts_mfu_pct"] < 100
    assert got["expert_load_max_over_mean"] == 1.3
    # a program without the scopes or the counters (the parent): nothing, and no raise
    bare = {"cell": cell, "peaks": peaks, "scope_times": None}
    assert [bench_run.read_layer_metric(n, bare) for n in NEW] == [None] * len(NEW)
    ctr = {"cell": {"cfg": bench_run.load_json("benchmark", "configs", "dcn_multislot.json")},
           "peaks": peaks, "scope_times": {"scopes": {"model": 4.0, "push/merge": 9.0}}}
    assert [bench_run.read_layer_metric(n, ctr) for n in NEW] == [None] * len(NEW)
    assert scope_prefix.ms(ctr, lambda s: s.startswith("push")) == 9.0
    listed = {m["name"]: m for m in SPEC["per_layer"]}
    assert all(listed[n]["workloads"] == [CELL] for n in NEW)


def test_toy_token_run_is_correct_and_control_and_fault_are_not():
    cell = toy_tokens.cell()
    result = bench_run.run_cell(cell, SPEC, require_tpu=False)
    assert result["correct"], result["checks"]
    assert result["checks"]["counter_gap"][0] == 0 and result["checks"]["logit_gap"][0] < 1e-4
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_samples_per_s", "setup_s"}
    for seed in (11, 12):
        ctl = control_tokens.readings(cell, seed)
        assert not ctl["bf16"]["correct"] and {"logit_gap", "early_loss_gap"} <= set(ctl["bf16"]["fails"])
        assert not ctl["half_batch"]["correct"]
        assert {"counter_gap", "early_loss_gap"} <= set(ctl["half_batch"]["fails"])
    json.dumps(result)


def test_the_chip_readings_of_control_and_fault_fail_the_token_cells_limits():
    """data/control_readings.glm47_flash_ep8.jsonl: what ``benchmark.control_tokens``
    read on a v5e at the cell's own widths and batches (PR 28, eight seeds), the
    reference wholly in bfloat16 and with half of every batch left out in the
    program's place. A file of its own: ``control_readings.jsonl`` is the two
    accepted cells', and not a program PR's to append to."""
    limits = bench_run.load_json("benchmark", "limits", CELL + ".json")
    seen = set()
    for ln in open(os.path.join(os.path.dirname(__file__), "data",
                                "control_readings.glm47_flash_ep8.jsonl")):
        r = json.loads(ln)
        assert r["workload"] == CELL
        correct, checks = compare.judge(r["values"], limits)
        assert not correct, (r, checks)
        failing = {k for k, (v, lim) in checks.items() if not v <= lim}
        # the two steady numbers each catch both, on every seed; beside them the
        # logits and the expert choices catch bfloat16, the token rows' gradient the fault
        assert {"early_loss_gap", "counter_gap"} <= failing, r
        assert checks["early_loss_gap"][0] > 10 * checks["early_loss_gap"][1], r
        assert {"bf16": {"logit_gap", "router_flip_share"},
                "half_batch": {"sparse_grad_gap"}}[r["control"]] <= failing, r
        seen.add((r["seed"], r["control"]))
    assert len(seen) == 16 and {c for _, c in seen} == {"bf16", "half_batch"}
