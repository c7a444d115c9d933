"""The launcher refuses a CPU and an unknown device kind; the runner prints
no result line without the cell's chips."""
import os
import subprocess
import sys
import types

import chip_child
import pytest
from conftest import ROOT


def fake_devices(n, platform="tpu", kind="TPU v5 lite"):
    return [types.SimpleNamespace(platform=platform, device_kind=kind) for _ in range(n)]


def test_the_launcher_refuses_a_cpu(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: fake_devices(1, "cpu", "cpu"))
    assert chip_child.check_device(1, allow_cpu=False) is None
    assert chip_child.check_device(1, allow_cpu=True)["platform"] == "cpu"


def test_the_launcher_refuses_an_unknown_kind_and_a_wrong_count(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: fake_devices(1, kind="TPU v9"))
    assert chip_child.check_device(1, allow_cpu=False) is None
    monkeypatch.setattr(jax, "devices", lambda: fake_devices(1))
    assert chip_child.check_device(1, allow_cpu=False) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert chip_child.check_device(4, allow_cpu=False) is None

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    assert chip_child.check_device(1, allow_cpu=False) is None


def test_peaks_cover_the_v5e_and_name_their_source():
    import json

    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        peaks = json.load(f)
    row = peaks["tpu v5 lite"]
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert row["source"].startswith("https://")


@pytest.mark.parametrize("extra", [[], ["--trace-typo"]])
def test_without_a_chip_the_runner_exits_nonzero_and_prints_no_result(extra):
    """Here JAX has only the CPU: the child is told to use the TPU whatever
    was inherited, fails to find one, and the parent prints nothing."""
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
           "--workload", "qwen2-0.5b.train-2k", "--seed", "0", "--seconds", "1",
           "--trace", "0"] + extra
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_flops_per_token_match_the_hand_count():
    import json

    import flops

    with open(os.path.join(ROOT, "benchmarks", "configs", "qwen2-0.5b.json")) as f:
        cfg = json.load(f)
    # per layer: q,o 2*896*896*2; k,v 2*896*128*2; attention 4*ctx*896; MLP 6*896*4864
    per_layer = 3_211_264 + 458_752 + 4 * 1024.5 * 896 + 26_148_864
    assert flops.forward_flops_per_token(cfg, 1024.5) == pytest.approx(
        24 * per_layer + 2 * 896 * 151936)
    assert flops.train_flops_per_token(cfg, 208.5) == pytest.approx(
        3 * flops.forward_flops_per_token(cfg, 208.5))
