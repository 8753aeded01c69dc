"""The entry points' compile-cache helper: with ``JAX_COMPILATION_CACHE_DIR``
set the cache lands there and the helper changes nothing; unset, it lands
in ``<root>/.jax_cache``.  Each case runs in a child process, because JAX
fixes the cache directory at the process's first compilation."""

import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_CHILD = """
import sys; sys.path.insert(0, {src!r})
import jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
print(use_compile_cache({root!r}))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((32, 32))).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_location(tmp_path, env_set):
    root = tmp_path / "checkout"
    root.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = root / ".jax_cache"
    if env_set:
        want = tmp_path / "from_env"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD.format(src=SRC, root=str(root))],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert any(p.name.endswith("-cache") for p in want.iterdir())
    if env_set:
        assert not (root / ".jax_cache").exists()
