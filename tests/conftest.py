"""Test harness config: run JAX on CPU with 8 virtual devices.

The tests choose the CPU explicitly (``JAX_PLATFORMS=cpu``), the one
way the entry points accept to run without an accelerator.  The
multi-chip sharding path (SURVEY.md §4(d)) is exercised on the 8
virtual devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

from heatmap_tpu.utils.jaxenv import enable_compile_cache  # noqa: E402

# persistent compile cache: the suite is dominated by CPU XLA compiles
enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _quiesce_stack_sampler():
    """Stop the process-wide stack sampler (obs.prof) after any test
    that started it — directly, via /debug/stacks, or via a
    flightrec-armed runtime.  In production it is designed to stay
    running; across a test SESSION a sampler left over from one test
    holds µs-scale frame references into every later test, which is
    exactly the cross-test coupling a hermetic suite can't have."""
    yield
    from heatmap_tpu.obs import prof

    if prof._SAMPLER is not None:
        prof._SAMPLER.stop()
