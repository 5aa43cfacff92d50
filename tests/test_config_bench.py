"""tools/config_bench.py smoke: all five BASELINE configs run end to end
through the trainer machinery and emit valid JSON."""

import json
import os
import subprocess
import sys

import pytest

pytest.importorskip("jax")


def test_all_five_configs_run(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(repo, "tools", "config_bench.py"),
            "--rows", "4096",
            "--batches", "3",
        ],
        capture_output=True,
        text=True,
        timeout=540,
        cwd=repo,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert len(lines) == 5
    names = [l["config"] for l in lines]
    assert names == [
        "1-lr-criteo",
        "2-widedeep",
        "3-deepfm-small",
        "4-dcn-multislot",
        "5-mmoe",
    ]
    for l in lines:
        assert "error" not in l, l
        assert l["samples_per_sec"] > 0
        assert 0.0 <= l["auc"] <= 1.0


def test_all_five_configs_run_real_format(tmp_path):
    """--data-dir: every config trains the converted Kaggle-format fixture
    (3k lines incl. malformed — reject path exercised), so the day real
    CTR data appears nothing breaks (dist_fleet_ctr.py:1 parity)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    data_dir = tmp_path / "criteo"
    data_dir.mkdir()
    import shutil

    shutil.copy(
        os.path.join(repo, "tests", "fixtures", "criteo_train_sample.txt"),
        data_dir / "train.txt",
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(repo, "tools", "config_bench.py"),
            "--batches", "3",
            "--data-dir", str(data_dir),
        ],
        capture_output=True,
        text=True,
        timeout=540,
        cwd=repo,
        env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    head, results = lines[0], lines[1:]
    assert head["accepted"] == 3020 and head["rejected"] == 60
    assert len(results) == 5
    for l in results:
        assert "error" not in l, l
        assert l["real_format"] is True
        assert l["rejected_lines"] == 60
        assert l["slots"] == 39
        assert l["samples_per_sec"] > 0
        assert 0.0 <= l["auc"] <= 1.0
