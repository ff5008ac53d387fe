"""Where the launcher's persistent compilation cache lands.

Each case runs in a subprocess: the cache directory is process-wide JAX
state, and the test's own process must keep its configuration."""

import os
import subprocess
import sys

import pytest

_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_CODE = r"""
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from repro.launch import train
train.REPO_ROOT = Path(sys.argv[1])
print(train.enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones((8,))).block_until_ready()
"""


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "default"])
def test_cache_entries_land_in_one_directory(tmp_path, from_env):
    root = tmp_path / "repo"
    root.mkdir()
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = _SRC
    env["JAX_PLATFORMS"] = "cpu"
    want = tmp_path / "x" if from_env else root / ".jax_cache"
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", _CODE, str(root)],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir()), "no cache entry written"
    # nothing anywhere else under the temp tree: the repo root (default
    # case) or nothing but the env directory (env case)
    others = [p for p in tmp_path.rglob("*")
              if p.is_file() and want not in p.parents]
    assert others == [], others
