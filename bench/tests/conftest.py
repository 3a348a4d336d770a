"""Every benchmark test runs as the benchmark does: float32, x64 off.

Other test modules of this suite turn x64 on for their whole process when
they are imported, and the harness points JAX's compilation cache at the
checkout; each test here puts both back when it ends.
"""
import jax
import pytest

_SAVED = ("jax_enable_x64", "jax_compilation_cache_dir",
          "jax_persistent_cache_min_compile_time_secs",
          "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _benchmark_jax_config():
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in _SAVED}
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
