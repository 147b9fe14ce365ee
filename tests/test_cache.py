"""utils/cache.py: where the persistent compile cache lives.

The directory is part of the cache key, so it must be the same on every run:
``JAX_COMPILATION_CACHE_DIR`` where the environment sets it (jax reads the
variable itself), ``<checkout>/.jax_cache`` otherwise — never a path built
from a temporary name, a pid or the time.
"""

import os
import subprocess
import sys

import jax
import pytest

from orion_tpu.utils.cache import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_environment_places_the_cache():
    """jax reads the variable at import, so this case needs an interpreter
    of its own (the test process imported jax long ago)."""
    probe = (
        "import jax\n"
        "from orion_tpu.utils.cache import enable_compile_cache\n"
        "enable_compile_cache()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR="/some/dir/cache")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", probe], env=env, cwd="/", text=True,
        capture_output=True, timeout=120, check=True,
    )
    assert out.stdout.strip().splitlines()[-1] == "/some/dir/cache"


@pytest.mark.parametrize("env", [
    {},
    # the private variable is gone: jax's own is the only one that places it
    {"ORION_TPU_CACHE": "/elsewhere"},
], ids=["env-unset", "no-private-variable"])
def test_checkout_cache_otherwise(monkeypatch, env):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    for key, value in env.items():
        monkeypatch.setenv(key, value)
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache"
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", prev[1]
        )
