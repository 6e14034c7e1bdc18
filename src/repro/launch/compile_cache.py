"""JAX's persistent compilation cache at one fixed place per checkout.

`enable()` is called by the entry points that compile full-size programs
(`chip_smoke.py`, `benchmarks/run.py`, `benchmarks/chip/run.py`) before
their first compile:

* where `JAX_COMPILATION_CACHE_DIR` is set, JAX already keeps its cache
  there, and nothing else is set;
* otherwise the cache goes to `<checkout>/.jax_cache` (gitignored) — a
  fixed path, never a temporary, per-process or time-stamped one, so a
  later run of the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]  # <checkout>/src/repro/launch/


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
