"""Where `launch.compile_cache.enable_compile_cache` puts JAX's persistent
compilation cache. Each case runs in a fresh interpreter: turning the cache
on is process-wide and must not leak into other tests."""
import os
import subprocess
import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"

PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
print(path)
print(jax.config.jax_compilation_cache_dir)
print(DEFAULT_DIR)
"""


def _probe(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c", PROBE], capture_output=True,
                          text=True, env=env, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


def test_cache_lands_in_the_directory_the_environment_names(tmp_path):
    path, config_dir, default = _probe(tmp_path / "cache")
    assert path == config_dir == str(tmp_path / "cache")
    assert path != default
    assert any((tmp_path / "cache").iterdir())


def test_cache_defaults_to_the_fixed_in_checkout_directory():
    path, config_dir, default = _probe(None)
    assert path == config_dir == default
    assert Path(default) == SRC.parent / ".jax_cache"
    ignored = (SRC.parent / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
