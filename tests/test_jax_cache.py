"""The entry points' persistent compilation cache: one fixed directory,
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch.jax_cache import REPO_CACHE_DIR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", [True, False])
def test_cache_dir_follows_env_else_repo(monkeypatch, tmp_path, env_dir,
                                         restore_cache_dir):
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO_CACHE_DIR)
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert str(REPO_CACHE_DIR.parent) == REPO


def test_cache_entries_land_in_env_dir_only(tmp_path):
    cache = tmp_path / "cache"
    code = textwrap.dedent("""
        import jax
        from repro.launch.jax_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        print(jax.jit(lambda x: x * 3 + 1)(2.0))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=os.path.join(REPO, "src"))
    before = (set(os.listdir(REPO_CACHE_DIR)) if REPO_CACHE_DIR.exists()
              else set())
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "7.0"
    assert cache.is_dir() and any(cache.iterdir())
    after = (set(os.listdir(REPO_CACHE_DIR)) if REPO_CACHE_DIR.exists()
             else set())
    assert after == before
