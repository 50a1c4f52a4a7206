"""The entry points' persistent compilation cache (repro.launch.compile_cache).

Each case runs in a child process: turning the cache on is process-wide,
and the test suite itself runs without it.
"""
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
DEFAULT_DIR = ROOT / ".jax_cache"


def _run(code: str, **env_extra) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               **env_extra)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.strip().splitlines()[-1]


def _listing(path: pathlib.Path):
    return sorted(p.name for p in path.iterdir()) if path.is_dir() else []


def test_cache_goes_where_the_environment_says(tmp_path):
    before = _listing(DEFAULT_DIR)
    code = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
where = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
print(where, jax.config.jax_compilation_cache_dir)
"""
    where, configured = _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path)
                             ).split()
    assert where == configured == str(tmp_path)
    assert any(tmp_path.iterdir())                # the program was cached
    assert _listing(DEFAULT_DIR) == before        # and nowhere else


def test_cache_defaults_to_the_checkout():
    code = """
import jax
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache(), jax.config.jax_compilation_cache_dir)
"""
    where, configured = _run(code).split()
    assert where == configured == str(DEFAULT_DIR)
