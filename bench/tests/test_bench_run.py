"""The command's refusals: no TPU, an unknown device kind, too few chips,
and a checkout that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from bench import chip
from bench.tests.cells import ROOT

CMD = [sys.executable, "bench/run.py", "--workload",
       "airline-80m.knn10-closed", "--seed", str(2**33 + 1), "--seconds",
       "1", "--trace", "0"]


def _no_result(stdout: str) -> bool:
    return '"correct"' not in stdout


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(CMD, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode != 0 and _no_result(r.stdout)
    assert "no TPU" in r.stderr


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    r = subprocess.run(CMD, cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0 and _no_result(r.stdout)


def _tpu(kind="TPU v5 lite"):
    return NS(platform="tpu", device_kind=kind)


def test_device_check():
    assert chip.check_devices([_tpu()], 1)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(chip.Refused, match="not in peaks.json"):
        chip.check_devices([_tpu("TPU v99")], 1)
    with pytest.raises(chip.Refused, match="needs 4 chips"):
        chip.check_devices([_tpu()], 4)
    with pytest.raises(chip.Refused, match="no TPU"):
        chip.check_devices([NS(platform="cpu", device_kind="cpu")], 1)


def test_peaks_name_their_source():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert v5e["bf16_flops_per_s"] == 197e12
