"""The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, and nowhere else; without it, to one fixed directory in the
checkout (the path is part of the cache key, so it must not move)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_placement(tmp_path, from_env):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    if from_env:
        want = str(tmp_path / "placed")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    else:
        want = str(REPO / ".jax_cache")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from repro.launch.compile_cache import "
         "enable_compile_cache as e; print(e()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    returned, configured = out.stdout.split()
    assert returned == configured == want
