"""Placement of the persistent compilation cache: where
``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``."""
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import jax, jax.numpy as jnp
from repro.runtime.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache
d = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == d, (d, jax.config.jax_compilation_cache_dir)
if {compile!r}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
print(d)
print(CHECKOUT_CACHE_DIR)
"""


def _probe(env_dir, compile_):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c",
                          _PROBE.format(compile=compile_)], env=env,
                         capture_output=True, text=True, timeout=120,
                         check=True)
    return out.stdout.split()


def test_cache_goes_where_the_variable_says(tmp_path):
    cache = tmp_path / "xla_cache"
    used, checkout_dir = _probe(cache, compile_=True)
    assert used == str(cache)
    assert any(cache.iterdir())               # compiles landed there
    assert Path(checkout_dir) == ROOT / ".jax_cache"


def test_cache_defaults_to_the_checkout():
    used, checkout_dir = _probe(None, compile_=False)
    assert used == checkout_dir == str(ROOT / ".jax_cache")
