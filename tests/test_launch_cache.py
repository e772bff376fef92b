"""The persistent compilation cache: where the entry points put it, and that
importing the package turns nothing on.

Each case runs in a fresh interpreter so the cache setting never leaks
into the test process's own compiles.
"""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

PROBE = r"""
import jax
import repro.kernels.ops, repro.train  # importing must not touch the cache
before = jax.config.jax_compilation_cache_dir
from repro.launch.cache import enable_compile_cache
path = enable_compile_cache()
print(before)
print(path)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe(env_dir):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE], env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-3:]


@pytest.mark.parametrize("env_set", [False, True], ids=("default", "env"))
def test_enable_compile_cache_directory(tmp_path, env_set):
    before, path, active = _probe(tmp_path if env_set else None)
    if env_set:
        # JAX reads the variable itself; nothing sets another directory
        assert before == path == active == str(tmp_path)
    else:
        assert before == "None"
        assert path == active == str(REPO / ".jax_cache")
