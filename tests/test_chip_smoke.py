"""``chip_smoke.py`` on the CPU: its phases, checks and refusals.

The phase function runs at 5,000 rows with the Pallas kernel forced into
interpret mode through ``device_opts``, so the script's control flow and
its exactness, fallback and dispatch checks are exercised on every change.
Any other kernel route is refused before a wave is served.  ``main``
itself must refuse to run without a TPU and without the repo's sources
next to it, printing no result line either way.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_phases_exact_on_interpret_kernel():
    lines = []
    s = chip_smoke.run_phases(
        5_000, seed=7, n_queries=128,
        device_opts={"use_pallas": True, "interpret": True},
        log=lambda **kv: lines.append(kv))
    assert [l["stage"] for l in lines] == ["setup"] + ["phase"] * 4
    assert lines[0]["use_pallas"] and lines[0]["interpret"]
    phases = s["phases"]
    assert list(phases) == ["a_knn10", "b_knn100", "c_writes", "d_compacted"]
    for p in phases.values():
        assert p["waves"] == p["device_waves"] == p["dispatches"] == 2
        assert p["fallback_waves"] == 0
        assert p["exact_vs_numpy"] == 128 and p["exact_vs_fullscan"] == 8
    # the compacted epoch re-enters the pre-write wave shape
    assert phases["d_compacted"]["compiled_shapes"] == 0
    assert s["dispatches"] == 8


@pytest.mark.parametrize("opts", [
    {"use_pallas": False},                        # the jnp oracle route
    {"use_pallas": True, "interpret": False},     # not the platform's mode
])
def test_phases_refuse_other_kernel_routes(opts):
    with pytest.raises(RuntimeError, match="not the Pallas kernel"):
        chip_smoke.run_phases(5_000, seed=7, n_queries=8, device_opts=opts,
                              log=lambda **kv: None)


def test_main_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py", "--rows", "5000"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_script_alone_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
