"""Where JAX keeps its persistent compilation cache.

Every entry point (`chip_smoke.py`, `benchmarks/serve_latency.py`,
`repro.launch.train`, the examples) calls `use_compile_cache()` once,
before its first compile; nothing calls it at import.  A cache entry is
keyed by, among other things, the cache's own path, so the directory is
fixed: `JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX reads
that itself, and this module then sets nothing), else `.jax_cache/` at the
root of the checkout.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
