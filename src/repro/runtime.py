"""Per-process JAX setup shared by the entry points.

Two decisions every entry point (`chip_smoke.py`, `launch/serve_spdc.py`,
`launch/serve_worker.py`, `benchmarks/run.py`, the examples) makes the
same way, through `init_process`:

  * x64 follows the platform. XLA:CPU computes float64 natively, so CPU
    runs keep the float64 protocol the rtol-1e-10 tests are calibrated
    for. A TPU has no float64 units: x64 stays off there, and a
    `dtype="float64"` request resolves to float32 (core.protocol.
    resolve_dtype) — every result still names the dtype it computed in.
  * One persistent compilation cache. `JAX_COMPILATION_CACHE_DIR`, when
    set, is used as it is and nothing else is configured; otherwise the
    cache lives at a fixed `.jax_cache/` inside the checkout (the path is
    part of the cache key, so it must not move between runs).

`init_process` runs at the start of an entry point's `main()`. When
`JAX_PLATFORMS` names one platform it answers from that and creates no
backend, so `repro.linalg` may still be imported afterwards: that import
switches off XLA:CPU async dispatch, which only takes effect before the
CPU backend exists (linalg.ops). With no platform named, JAX is asked and
the backends are created, so an entry point that jit-compiles the secure
linalg ops imports `repro.linalg` first. Entry points that do not use
those ops keep async dispatch.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout-local compilation cache used when the environment names none
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def on_cpu() -> bool:
    """True when this process's default JAX backend is the CPU.

    A single platform named by `jax_platforms` (JAX_PLATFORMS) answers
    without creating a backend; otherwise JAX is asked, which creates them.
    """
    named = (jax.config.jax_platforms or "").split(",")
    if len(named) == 1 and named[0]:
        return named[0] == "cpu"
    return jax.default_backend() == "cpu"


def init_process(x64: bool = True) -> bool:
    """Configure x64 and the compilation cache (module docstring).

    x64=False keeps x64 off on the CPU too (the float32 protocol shape).
    Returns whether x64 ended up on.
    """
    x64 = x64 and on_cpu()
    jax.config.update("jax_enable_x64", x64)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return x64
