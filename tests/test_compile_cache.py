"""Where the entry points keep JAX's persistent compilation cache.

Each case runs in a fresh interpreter: the cache directory is read once,
at a process's first compile, and the test process must keep its own.
"""
import os
import subprocess
import sys

import pytest

from repro.compile_cache import REPO_CACHE_DIR

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
PROBE = """
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.arange(8.0)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=SRC,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c",
                          PROBE.format(compile=compile_)],
                         env=env, capture_output=True, text=True, check=True)
    return out.stdout.split()


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, from_env):
    if from_env:
        # the directory placed from outside is used, and nothing else
        used, configured = _probe(str(tmp_path), True)
        assert used == configured == str(tmp_path)
        assert os.listdir(tmp_path), "no compiled program landed there"
    else:
        # a fixed path inside the checkout, which git ignores
        used, configured = _probe(None, False)
        assert used == configured == REPO_CACHE_DIR
        assert os.path.basename(REPO_CACHE_DIR) == ".jax_cache"
        root = os.path.dirname(REPO_CACHE_DIR)
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
