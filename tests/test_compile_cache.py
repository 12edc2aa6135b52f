"""The persistent compilation cache: `JAX_COMPILATION_CACHE_DIR` when set,
else one fixed directory inside the checkout."""
import pathlib

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.models import compat

ROOT = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_set", [False, True])
def test_enable_compile_cache_directory(env_set, tmp_path, monkeypatch,
                                        restore_cache_config):
    before = jax.config.jax_compilation_cache_dir
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compat.enable_compile_cache()
    if env_set:
        # JAX reads the variable itself; no other directory is set
        assert path == tmp_path
        assert jax.config.jax_compilation_cache_dir == before
    else:
        assert path == ROOT / "benchmarks" / "results" / ".xla_cache"
        assert path.is_dir()
        assert jax.config.jax_compilation_cache_dir == str(path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
