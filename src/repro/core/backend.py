"""Backend names for the advisor's engines, and jax's process settings.

Each engine (CostEngine, the compression codec kernels, EstimationEngine,
PlannerEngine) takes a backend name:

* ``"numpy"`` — the float64 parity reference.
* ``"jax"``  — Pallas kernels (repro.kernels.codec_bytes /
  planner_score) plus jax.jit scoring kernels: compiled on a TPU, and
  run in Pallas interpret mode where jax's default backend is the CPU
  (`pallas_interpret`, the one kernel-platform decision).  Codec kernels
  do exact int32-safe math through uint32 planes.

`resolve` validates a name; unknown names raise ValueError.

`enable_compile_cache` points jax's persistent compilation cache at a
fixed directory; entry points (the chip benchmark `bench/run.py`, the
benchmark scripts) call it, importing the package never does.
"""
from __future__ import annotations

import os
from pathlib import Path

BACKENDS = ("numpy", "jax")

# <checkout>/.jax_cache: src/repro/core/backend.py is three levels down
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def resolve(backend: str) -> str:
    """Validate `backend` and return it."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r} (expected one of "
                         f"{BACKENDS})")
    return backend


def pallas_interpret() -> bool:
    """True when Pallas kernels must run in interpret mode: jax's default
    backend is the CPU.  On an accelerator the kernels compile."""
    import jax
    return jax.default_backend() == "cpu"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins and jax reads it itself;
    otherwise the cache lives at the fixed `DEFAULT_CACHE_DIR` inside the
    checkout (a fixed path, because the path is part of the cache key)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
