"""Where the entry points keep JAX's compile cache, and which kernel mode
each backend gets.  Nothing here turns the cache on."""

import jax
import pytest

from repro import compile_cache
from repro.kernels import interpret_mode


def test_cache_dir_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.cache_dir()
    assert first == compile_cache.cache_dir()
    assert first == str(compile_cache.REPO_CACHE_DIR)
    assert (compile_cache.REPO_CACHE_DIR.parent / "src" / "repro").is_dir()


@pytest.mark.parametrize(
    "backend,want", [("cpu", True), ("tpu", False), ("gpu", None)]
)
def test_interpret_mode_by_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError, match="no Pallas kernel path"):
            interpret_mode()
    else:
        assert interpret_mode() is want
