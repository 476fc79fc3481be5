"""CPU tests of the benchmark harness: no chip, tiny sizes."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


import pytest  # noqa: E402

_CACHE_FLAGS = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
                "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture(autouse=True)
def _restore_compile_cache():
    """A harness run points JAX's persistent cache into its checkout; give
    the process's setting back, so tests that run later in this worker
    do not write into a deleted temporary directory."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in _CACHE_FLAGS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()
