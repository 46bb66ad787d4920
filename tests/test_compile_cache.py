"""Where the persistent compilation cache is placed (repro.compile_cache)."""
import jax
import pytest

from repro.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: jax.config.values[k] for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_var_wins(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.values["jax_compilation_cache_dir"] == str(tmp_path)
    assert jax.config.values["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_default_is_fixed_dir_in_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = use_compile_cache()
    assert path == str(CHECKOUT_CACHE_DIR)
    # the repo root holds src/, and the directory is gitignored there
    root = CHECKOUT_CACHE_DIR.parent
    assert (root / "src" / "repro" / "compile_cache.py").is_file()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
