import os
import sys
from pathlib import Path

# NOTE: do NOT set xla_force_host_platform_device_count here — smoke tests
# and benches must see 1 device; multi-device tests spawn subprocesses with
# their own XLA_FLAGS (tests/_subproc.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def fresh_compile_cache():
    """Reset the process-global jit executable caches.

    The compile-cache accounting tests assert hit/miss counts derived
    from module-global trace counters, but jit caches are process-global:
    an identically-shaped solve in an EARLIER test warms the cache, so
    whether this test's first solve is a hit or a miss depends on pytest
    ordering.  Clearing the caches up front makes the first invocation
    deterministically a miss under any ordering (-p no:randomly not
    required, -k subsets safe)."""
    jax.clear_caches()
    yield
