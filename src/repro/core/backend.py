"""Unified accelerator-backend resolution for the advisor stack.

One backend knob — ``AdvisorOptions(backend=...)`` — threads through every
engine (CostEngine, compression codec kernels, EstimationEngine,
PlannerEngine) and the fleet service.  This module is the single place
that decides whether a requested backend can actually run:

* ``"numpy"`` — the float64 parity reference.  Always available.
* ``"jax"``  — Pallas kernels (repro.kernels.codec_bytes /
  planner_score) plus jax.jit scoring kernels.  Requires jax; compiled
  on a TPU, and run in Pallas interpret mode only where jax's default
  backend is the CPU (`pallas_interpret`, the one kernel-platform
  decision).  The old int64/x64 gate is gone: codec kernels do exact
  int32-safe math through uint32 planes.

Fallback semantics: when ``"jax"`` is requested but jax is unavailable,
`resolve` downgrades to ``"numpy"`` — but never silently.  Each resolving
engine gets a one-time `BackendFallbackWarning` (once per call site per
process) and counts the event in its ``stats()["backend_fallbacks"]``.
Unknown backend names always raise ValueError.  `fallback_count()` totals
the fallbacks of every engine in the process.

`enable_compile_cache` points jax's persistent compilation cache at a
fixed directory; entry points (chip_smoke.py, the benchmark scripts) call
it, importing the package never does.
"""
from __future__ import annotations

import os
import warnings
from pathlib import Path
from typing import Optional, Tuple

try:  # repro.kernels idiom: gate, don't require
    import jax  # noqa: F401
    HAVE_JAX = True
except Exception:  # pragma: no cover - jax is baked into the image
    HAVE_JAX = False

BACKENDS = ("numpy", "jax")


class BackendFallbackWarning(UserWarning):
    """A requested accelerator backend was unavailable; numpy ran instead."""


_warned_sites = set()
_fallbacks = 0

# <checkout>/.jax_cache: src/repro/core/backend.py is three levels down
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def available(backend: str) -> bool:
    """True when `backend` can actually run in this process."""
    return backend == "numpy" or (backend == "jax" and HAVE_JAX)


def resolve(backend: str, site: Optional[str] = None) -> Tuple[str, bool]:
    """Validate `backend` and downgrade to numpy if it cannot run.

    Returns (resolved_backend, fell_back).  With `site` set, an
    unavailable backend emits a one-time BackendFallbackWarning per site;
    site=None resolves quietly (for callers that only need the answer,
    e.g. WhatIfOptimizer deciding whether a rebuild is needed).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r} (expected one of "
                         f"{BACKENDS})")
    global _fallbacks
    if available(backend):
        return backend, False
    if site is not None:
        _fallbacks += 1
        if site not in _warned_sites:
            _warned_sites.add(site)
            warnings.warn(
                f"{site}: backend={backend!r} requested but unavailable "
                f"(jax import failed); falling back to numpy. This warning "
                f"is emitted once per site.", BackendFallbackWarning,
                stacklevel=3)
    return "numpy", True


def fallback_count() -> int:
    """Engine backends (sited resolutions) downgraded to numpy so far in
    this process."""
    return _fallbacks


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
