"""Entry-point process setup: the platform decision and the compile cache.

Both are called from entry points (``serve_diffusion.main``,
``router._main``, ``chip_smoke.py``, the benches), never at import time.

* :func:`cpu_forced` decides, BEFORE JAX initialises, whether this process
  runs on the host CPU — only then may it simulate host devices (an
  ``XLA_FLAGS`` decision) or start child processes that need a backend.
  On an accelerator host a chip belongs to one process: a child started
  after the parent has touched JAX cannot open it, so everything that
  needs the chip runs in-process there.
* :func:`use_compile_cache` turns on JAX's persistent compilation cache.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: a fixed path, since the path is part of the key
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def cpu_forced() -> bool:
    """True iff ``JAX_PLATFORMS`` is explicitly ``cpu`` (read, not probed)."""
    return os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def use_compile_cache() -> str:
    """Persist compiled executables across processes; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here; otherwise the cache lives in the checkout's
    ``.jax_cache``.
    """
    path = os.environ.get(CACHE_ENV)
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
