import os
import sys

import pytest

# the benchmark's package lives at the checkout's root, beside src/
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


@pytest.fixture
def compile_cache(tmp_path, monkeypatch):
    """Runs keep their compile cache in the test's own directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()
