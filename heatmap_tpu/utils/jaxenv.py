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
import re

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    The cache is keyed on the programs' metadata too (op names, named
    scopes, source locations), so an executable read back from it
    carries the names of the code that asked for it: keyed without them,
    two versions that differ only in their scopes would share entries,
    and a device trace would name ops after whichever compiled first.
    Source locations are relative to the checkout, so the same code
    checked out in another directory finds the same entries."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
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
