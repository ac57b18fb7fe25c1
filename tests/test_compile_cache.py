"""Where the entry points' persistent compilation cache lands.

Each case compiles a small function in a fresh process (the cache is set
up once per process, at the first compile) and lists what was written.
"""
import os
import subprocess
import sys
from pathlib import Path

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]

# Compiles one function after enable_compile_cache(); ``checkout`` replaces
# the in-checkout default so the test writes nothing into the repository.
CODE = r"""
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch import compile_cache
compile_cache.CHECKOUT_CACHE = sys.argv[1]
print("CACHE", compile_cache.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.arange(8.0)).block_until_ready()
"""


def _compile(env_dir, checkout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop(compile_cache.ENV_VAR, None)
    if env_dir is not None:
        env[compile_cache.ENV_VAR] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", CODE, str(checkout)], capture_output=True,
        text=True, timeout=120, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def _entries(path: Path) -> list:
    return sorted(p.name for p in path.iterdir()) if path.exists() else []


def test_cache_goes_to_the_env_dir_when_set(tmp_path):
    env_dir, checkout = tmp_path / "env_cache", tmp_path / "checkout_cache"
    stdout = _compile(env_dir, checkout)
    assert f"CACHE {env_dir}" in stdout
    assert _entries(env_dir), "nothing was cached in $JAX_COMPILATION_CACHE_DIR"
    assert _entries(checkout) == []


def test_cache_goes_to_the_checkout_when_unset(tmp_path):
    checkout = tmp_path / "checkout_cache"
    stdout = _compile(None, checkout)
    assert f"CACHE {checkout}" in stdout
    assert _entries(checkout), "nothing was cached in the checkout directory"


def test_checkout_cache_is_fixed_and_ignored():
    """The default is ``<checkout>/.jax_cache``: no temp, pid or time."""
    assert compile_cache.CHECKOUT_CACHE == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
