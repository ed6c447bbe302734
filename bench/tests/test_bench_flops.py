"""Operation counts, the peak table and the command's refusal without a chip."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import flops, run

ROOT = Path(__file__).resolve().parents[2]


def _config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_model_flops_phi4_mini_by_hand():
    cfg = _config("phi4-mini.1chip")
    # Per layer: wq and wo 3072 x 3072, wk and wv 3072 x 1024, three MLP
    # matrices 3072 x 8192; the tied head 3072 x 50176 (50016 padded to 256).
    per_layer = 2 * 3072 * 3072 + 2 * 3072 * 1024 + 3 * 3072 * 8192
    params = 4 * per_layer + 3072 * 50176
    assert flops.matmul_params(cfg) == params == 556_793_856
    assert flops.model_flops_per_token(cfg, 4096) == 6 * params + 6 * 4 * 4096 * 3072


def test_model_flops_granite_by_hand():
    cfg = _config("granite-8b.dp2tp2")
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    params = 4 * per_layer + 4096 * 49152
    assert flops.matmul_params(cfg) == params
    assert flops.model_flops_per_token(cfg, 4096) == 6 * params + 6 * 4 * 4096 * 4096


def _ns(m, n):
    m, n = min(m, n), max(m, n)
    return 5 * (2 * m * m * n + 2 * m ** 3 + 2 * m * m * n)


def test_ns_flops_one_chip_by_hand():
    cfg = _config("phi4-mini.1chip")
    per_layer = (2 * _ns(3072, 3072) + 2 * _ns(3072, 1024) + 3 * _ns(3072, 8192))
    norms = 2 * _ns(4, 3072)
    assert flops.ns_flops(cfg, "full") == 4 * per_layer + norms
    # One shard: a block is the whole matrix.
    assert flops.ns_flops(cfg, "block") == flops.ns_flops(cfg, "full")


def test_ns_flops_granite_per_device_by_hand():
    cfg = _config("granite-8b.dp2tp2")
    # ZeRO-1 over data=2: each device orthogonalizes 2 of the 4 layers; full
    # steps take whole matrices, block steps one model-parallel half.
    full = 2 * (2 * _ns(4096, 4096) + 2 * _ns(4096, 1024) + 3 * _ns(4096, 14336))
    block = 2 * (_ns(4096, 2048) + 2 * _ns(4096, 512) + _ns(2048, 4096)
                 + 2 * _ns(4096, 7168) + _ns(7168, 4096))
    norms = 2 * _ns(4, 4096)
    assert flops.ns_flops(cfg, "full") == full + norms
    assert flops.ns_flops(cfg, "block") == block + norms


def test_peak_table_v5e_and_unknown_kind():
    peak = run.load_peak("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in peak["source"]
    with pytest.raises(KeyError):
        run.load_peak("TPU v9 imaginary")


def test_command_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "phi4-mini.1chip.muonbp", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_command_refuses_in_a_bare_checkout(tmp_path):
    """With only BENCHMARK.json and bench/ (no program), the run fails and
    prints no result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi4-mini.1chip.muonbp",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
