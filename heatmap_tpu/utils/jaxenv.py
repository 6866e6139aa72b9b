"""Process-level JAX set-up shared by every entry point.

Two rules, one place:

- **Compile cache at a fixed path.**  The cache key includes the
  directory, so a path made from tmp, a pid or the time never hits.
  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
  nothing here overrides it; otherwise the cache lives at
  ``<checkout>/.jax_cache`` (gitignored).
- **No silent CPU.**  With ``JAX_PLATFORMS`` unset, JAX that finds no
  accelerator quietly runs on the CPU.  An entry point that calls
  :func:`require_accelerator` refuses that: the CPU runs only when the
  operator chose it with ``JAX_PLATFORMS=cpu`` (as the tests do).
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    import jax

    chosen = os.environ.get(CACHE_ENV)
    if chosen:
        return chosen
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_accelerator() -> str:
    """Return the JAX backend name; raise when it is a CPU nobody chose."""
    import jax

    backend = jax.default_backend()
    chosen = (jax.config.jax_platforms or "").split(",")
    if backend == "cpu" and "cpu" not in chosen:
        raise RuntimeError(
            "JAX found no accelerator and fell back to the CPU; set "
            "JAX_PLATFORMS=cpu to run on the CPU deliberately")
    return backend
