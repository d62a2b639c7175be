"""repro.common.compile_cache: where entry points keep JAX's persistent
compilation cache."""
from pathlib import Path

import jax

from repro.common.compile_cache import CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parents[1]


def _restoring(fn):
    prev = jax.config.jax_compilation_cache_dir
    try:
        return fn(), jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_defaults_to_fixed_dir_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    used, configured = _restoring(use_compile_cache)
    assert used == configured == str(CACHE_DIR)
    assert CACHE_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_cache_dir_from_environment_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    used, configured = _restoring(use_compile_cache)
    assert used == str(tmp_path)
    assert configured == before        # nothing set in code
