"""chip_smoke.py's phase functions at tiny sizes on the virtual CPU mesh
(Pallas in interpret mode), and its refusal to start without a TPU. The
full-width run is the script itself, on the chip."""

import json

import pytest

jax = pytest.importorskip("jax")

import chip_smoke  # noqa: E402
from paddlebox_tpu.parallel import make_mesh  # noqa: E402

TINY = dict(
    num_slots=4,
    embedx_dim=4,
    hidden=(8,),
    batch=32,
    auc_buckets=100,
    n_files=2,
    records_per_file=256,
    key_space=4000,
    hot_space=64,
    round_to=64,
    host_shards=4,
    score_request_records=4,
)


@pytest.fixture(scope="module")
def counter():
    return chip_smoke.CompileCounter()


@pytest.fixture(scope="module")
def one_chip_day(tmp_path_factory, counter):
    work = tmp_path_factory.mktemp("smoke")
    files = chip_smoke.write_day(str(work), TINY, seed=0)
    root = str(work / "ckpt")
    return files, root, chip_smoke.run_day(TINY, files, root, counter)


def test_main_refuses_to_start_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out and "platform=cpu" in out[0] and f"jax={jax.__version__}" in out[0]
    with pytest.raises(ValueError):  # no result line was printed
        json.loads(out[-1])


def test_result_line_has_the_contract_keys_and_no_others():
    from paddlebox_tpu.utils import backendguard

    doc = json.loads(chip_smoke.result_line(backendguard.bring_up()))
    assert doc["ok"] is True and set(doc) == {"ok", "device"}
    first = jax.devices()[0]
    assert doc["device"] == {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(jax.devices())}


KERNEL_TINY = dict(batch=1, seq=256, heads=2, head_dim=128, block=128)  # test_fused_attention's


def test_pallas_kernels_match_xla_ops_in_interpret_mode():
    rec = chip_smoke.check_pallas_kernels(**KERNEL_TINY, interpret=True)
    assert rec["kernel"] == "causal_attention" and rec["interpret"]
    assert set(rec["rel_gap"]) == {"o", "dq", "dk", "dv"}
    assert 0 < rec["rel_gap"]["o"] < chip_smoke.KERNEL_OUT_RTOL  # two forms, not one twice
    json.dumps(rec)  # the record is the summary's ``pallas_kernels``


def test_pallas_kernel_check_fails_when_kernel_and_oracle_disagree(monkeypatch):
    sound = chip_smoke.causal_attention
    monkeypatch.setattr(
        chip_smoke, "causal_attention",
        lambda q, k, v, scale, block, interpret: sound(q, k, v, 1.1 * scale, block, interpret))
    with pytest.raises(AssertionError, match="from the blocked form"):
        chip_smoke.check_pallas_kernels(**KERNEL_TINY, interpret=True)


def test_day_takes_the_paths_it_means_to(one_chip_day):
    _, _, day = one_chip_day
    passes = day["record"]["passes"]
    assert len(passes) == chip_smoke.N_PASSES
    # a save drains the carrier, so only the unsaved boundary splices
    assert [p["spliced"] for p in passes] == [False, False, True]
    assert passes[2]["boundary_compiles"] > 0  # the eager splice compiles
    # the pass's arrays are constants of the scan program: every pass builds its own
    assert all(p["train_compiles"][0] >= 1 for p in passes)
    json.dumps(day["record"])  # the record is the JSON line's payload


def test_reload_and_serving_match_the_live_trainer(one_chip_day):
    files, root, day = one_chip_day
    assert chip_smoke.check_reload(TINY, day, root)["delta_idx"] == 1
    with open(files[0][0]) as f:
        probe = f.readlines()[: 7 * TINY["score_request_records"]]
    rec = chip_smoke.check_serving(TINY, day, root, probe)
    assert rec["bitwise_equal"] and rec["requests"] == 7


def test_mesh_day_shards_the_table_and_agrees_with_one_chip(
    one_chip_day, tmp_path, counter
):
    files, _, day = one_chip_day
    plan = make_mesh(4)
    mesh = chip_smoke.run_day(TINY, files, str(tmp_path / "ckpt"), counter, plan)
    for p in mesh["record"]["passes"]:
        assert p["sharding"]["table_devices"] == 4
        assert p["sharding"]["resident_devices"] == 4
    assert mesh["record"]["passes"][2]["spliced"]
    keys = day["sample_keys"]
    assert (mesh["sample_keys"] == keys).all()
    agree = chip_smoke.compare_days(
        day["record"], mesh["record"],
        day["box"].table.pull_or_create(keys),
        mesh["box"].table.pull_or_create(keys),
    )
    # the CPU bounds of tests/test_sharded.py, far inside the script's own
    assert agree["pass_loss_max_rel_diff"] < 6e-3
    assert agree["embed_max_abs_diff"] < 1e-3
