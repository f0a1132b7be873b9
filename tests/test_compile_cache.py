"""The entry points' persistent compilation cache location."""
from pathlib import Path

import jax

from repro.launch import compile_cache as cc

REPO_ROOT = Path(__file__).resolve().parent.parent


def test_cache_dir_env_wins_else_fixed_in_repo(monkeypatch, tmp_path):
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "from_env"))
    assert cc.compile_cache_dir() == tmp_path / "from_env"
    monkeypatch.delenv(cc.ENV_VAR)
    assert cc.compile_cache_dir() == REPO_ROOT / ".jax_cache"
    assert ".jax_cache/" in (REPO_ROOT / ".gitignore").read_text().split()


def test_enable_points_jax_at_the_env_directory(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "cache"))
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_enable_compilation_cache)
    try:
        assert cc.enable_compile_cache() == tmp_path / "cache"
        assert (tmp_path / "cache").is_dir()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cache")
        assert jax.config.jax_enable_compilation_cache
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_enable_compilation_cache", was[1])
        compilation_cache.reset_cache()
