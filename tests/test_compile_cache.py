"""Where the persistent compilation cache goes (repro.utils.compile_cache).

Each case runs the helper in a child interpreter: calling it here would turn
the cache on for the test process, and the tests keep it off."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

PROBE = ("import jax\n"
         "from repro.utils.compile_cache import enable_compile_cache\n"
         "print(enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n")


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env-var-set", "env-var-unset"])
def test_compile_cache_directory(tmp_path, from_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    want = str(ROOT / ".jax_cache")
    if from_env:
        want = str(tmp_path / "placed")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    assert out.stdout.split() == [want, want]
