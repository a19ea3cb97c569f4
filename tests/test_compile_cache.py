"""The entry points' persistent compile cache goes where the environment
says, or to one fixed directory in the checkout — never both.

Each case runs in a child interpreter: enabling the cache changes JAX's
process-wide config, which must not leak into the test process.
"""

import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent

_CHILD = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
print(path)
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


def _run(compile_: bool, cache_env=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_env)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(compile=compile_)],
        capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    returned, configured = proc.stdout.split()[:2]
    return returned, configured


def test_env_dir_is_used_and_nothing_else(tmp_path):
    cache = tmp_path / "jax-cache"
    returned, configured = _run(True, cache_env=cache)
    assert returned == configured == str(cache)
    assert any(p.is_file() for p in cache.rglob("*")), "no cache entry written"


def test_default_is_the_fixed_checkout_dir():
    returned, configured = _run(False)
    assert returned == configured == str(ROOT / ".jax_cache")
