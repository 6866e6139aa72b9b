"""Process-level JAX set-up (heatmap_tpu.utils.jaxenv) and the absence
of fallbacks that would hide a missing device.

- The compile cache sits where JAX_COMPILATION_CACHE_DIR says, and
  otherwise at one fixed path in the checkout, whichever process asks.
- An entry point that finds no accelerator fails unless the operator
  chose the CPU with JAX_PLATFORMS=cpu.
- An explicit Pallas request that cannot run raises; it never becomes
  the XLA snap.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))

_PRINT_CACHE = (
    "import jax\n"
    "from heatmap_tpu.utils.jaxenv import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n")


def _python(code_or_args, env_over=None, drop=(), timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["PYTHONPATH"] = REPO
    env.update(env_over or {})
    args = (["-c", code_or_args] if isinstance(code_or_args, str)
            else code_or_args)
    return subprocess.run([sys.executable, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_cache_env_is_honoured_and_nothing_else_set(tmp_path):
    want = str(tmp_path / "chosen-cache")
    r = _python(_PRINT_CACHE, {"JAX_COMPILATION_CACHE_DIR": want})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [want, want]


def test_cache_defaults_to_one_checkout_path_across_processes():
    want = os.path.join(REPO, ".jax_cache")
    seen = []
    for _ in range(2):
        r = _python(_PRINT_CACHE, drop=("JAX_COMPILATION_CACHE_DIR",))
        assert r.returncode == 0, r.stderr
        seen.append(r.stdout.split())
    assert seen == [[want, want], [want, want]]


def test_cache_helper_in_process_matches_jax_config(monkeypatch):
    import jax

    from heatmap_tpu.utils import jaxenv

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jaxenv.enable_compile_cache() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache")


def test_chosen_cpu_is_accepted():
    from heatmap_tpu.utils.jaxenv import require_accelerator

    assert require_accelerator() == "cpu"  # conftest chose the CPU


@pytest.mark.parametrize("argv", [
    ["-m", "heatmap_tpu.stream", "synthetic_backfill", "--max-batches",
     "1"],
    ["-m", "heatmap_tpu.models.demo", "--events", "1000"],
    ["bench.py"],
    ["chip_smoke.py"],
], ids=["stream", "demo", "bench", "chip_smoke"])
def test_entry_point_without_accelerator_fails(argv):
    """JAX_PLATFORMS unset and no accelerator: JAX itself would quietly
    run on the CPU; the entry points refuse."""
    r = _python(argv, drop=("JAX_PLATFORMS",), timeout=180)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "events/sec" not in r.stdout


def test_explicit_pallas_request_raises_off_tpu(tmp_path, monkeypatch):
    from heatmap_tpu.config import load_config
    from heatmap_tpu.engine import step as engine_step
    from heatmap_tpu.hexgrid import pallas_kernel
    from heatmap_tpu.sink import MemoryStore
    from heatmap_tpu.stream import MemorySource, MicroBatchRuntime

    assert pallas_kernel.pallas_available() is False  # CPU: no raise
    monkeypatch.setattr(engine_step, "SNAP_IMPL", None)
    monkeypatch.setenv("HEATMAP_H3_IMPL", "pallas")
    cfg = load_config({}, batch_size=256, state_capacity_log2=10,
                      store="memory", checkpoint_dir=str(tmp_path))
    with pytest.raises(RuntimeError, match="Pallas"):
        MicroBatchRuntime(cfg, MemorySource([]), MemoryStore())


def test_checkpoint_keyed_pallas_raises_off_tpu(tmp_path, monkeypatch):
    """A resume whose checkpoint was keyed with the Pallas snap cannot
    continue on the XLA snap: it raises instead of re-keying."""
    from heatmap_tpu.config import load_config
    from heatmap_tpu.engine import step as engine_step
    from heatmap_tpu.sink import MemoryStore
    from heatmap_tpu.stream import MemorySource, MicroBatchRuntime

    monkeypatch.setattr(engine_step, "SNAP_IMPL", None)
    monkeypatch.setenv("HEATMAP_H3_IMPL", "auto")
    cfg = load_config({}, batch_size=256, state_capacity_log2=10,
                      store="memory", checkpoint_dir=str(tmp_path))
    rt = MicroBatchRuntime(cfg, MemorySource([]), MemoryStore())
    try:
        with pytest.raises(RuntimeError, match="Pallas"):
            rt._pin_snap_impl("pallas")
    finally:
        engine_step.SNAP_IMPL = None
        rt.close()
