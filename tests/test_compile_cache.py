"""``repro.launch.cache.use_compile_cache``: where compiled programs land.

Each case runs in its own process, since the cache directory is
process-wide JAX configuration.
"""

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(tmp_path, from_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch.cache import use_compile_cache
        print(use_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
    """)
    if from_env:      # compile only where the cache is the test's own dir
        code += "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()\n"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    returned, configured = proc.stdout.split()[:2]
    want = str(tmp_path) if from_env else str(ROOT / ".jax_cache")
    assert returned == configured == want
    if from_env:
        assert any(tmp_path.iterdir()), "no cache entry written"
