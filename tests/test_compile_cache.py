"""Where the persistent compilation cache goes (repro.launch.compile_cache)."""
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_external_dir_is_kept(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.compile_cache_dir() == "/some/dir"
    assert compile_cache.setup_compile_cache() == "/some/dir"
    assert updates == []          # JAX reads the variable itself


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.setup_compile_cache()
    assert first == compile_cache.setup_compile_cache() \
        == compile_cache.compile_cache_dir() == str(REPO / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", first)] * 2
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert "/.jax_cache/" in ignored
