"""The benchmark's tests run on the CPU: virtual devices for the shard_map
cell, the program's sources on the path, and JAX's global settings put
back after each test that runs the harness (it turns x64 off, points
the compilation cache into the checkout and caches every program)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import pytest  # noqa: E402

SRC = Path(__file__).resolve().parents[2] / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))


@pytest.fixture
def jax_settings():
    """Restore x64 and the compilation cache's settings after the test."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in
             ("jax_enable_x64", "jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs")}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
