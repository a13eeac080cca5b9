import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parents[1] / "src")]

_CACHE_KEYS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _restore_compile_cache():
    """A run points JAX's persistent cache into its checkout; give later
    tests in the same worker the settings they had."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
