"""``repro.compile_cache``: where the persistent compile cache lands.

Each case runs the helper in a fresh interpreter (JAX opens its cache once
per process) from a copy of the module placed at ``<tmp>/src/repro``, so
``<repo>/.jax_cache`` resolves to ``<tmp>/.jax_cache`` and the real
checkout is left alone.  One trivial jitted function is compiled, and the
expected directory must then hold entries.
"""
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.compile_cache import DEFAULT_CACHE_DIR

ROOT = Path(__file__).resolve().parents[1]

_CHILD = textwrap.dedent("""
    import importlib.util, sys
    spec = importlib.util.spec_from_file_location("compile_cache", sys.argv[1])
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    print(mod.enable_compile_cache())
    import jax, jax.numpy as jnp
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
""")


@pytest.mark.parametrize("from_env", [False, True])
def test_cache_lands_where_expected(tmp_path, from_env):
    mod = tmp_path / "src" / "repro" / "compile_cache.py"
    mod.parent.mkdir(parents=True)
    shutil.copy(ROOT / "src" / "repro" / "compile_cache.py", mod)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = tmp_path / ".jax_cache"
    if from_env:
        want = tmp_path / "env_cache"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    r = subprocess.run([sys.executable, "-c", _CHILD, str(mod)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(want)
    assert want.is_dir() and any(want.iterdir())
    if from_env:
        assert not (tmp_path / ".jax_cache").exists()


def test_default_dir_is_repo_root():
    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
