"""Multihost bank-skew agreement: two REAL processes whose local
hardware banks (HEATMAP_HW_BANK) disagree must converge on the same trace-time choices
(r5: hwbank measured-winner defaults).  A skewed checkout would
otherwise compile DIFFERENT lockstep programs per host (divergent merge
impls) or key f32 cell-edge events per ingesting host (divergent
snaps).  The startup collective (stream/runtime.py) demotes the merge
pin to None unless every host's verdict matches; when the banks agree,
the unanimous pin must SURVIVE the collective."""

import pytest
import json
import os
import socket
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = textwrap.dedent("""
    import json, os, sys, tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")
    try:
        jax.config.update("jax_num_cpu_devices", 4)
    except AttributeError:  # older jaxlib: XLA flag at lazy backend init
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=4")

    pid = int(sys.argv[1])
    coord = sys.argv[2]
    out_path = sys.argv[3]
    bank_path = os.environ["HEATMAP_HW_BANK"]

    def write_bank():
        units = {f"merge_{s}": {"data": {"winner": "probe",
                                         "_platform": "cpu"}, "ts": "t"}
                 for s in ("stream", "backfill", "balanced")}
        with open(bank_path, "w") as fh:
            json.dump({"units": units, "attempts": {}, "log": []}, fh)

    if pid == 0:
        write_bank()  # host 1 has NO bank file yet -> skew

    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=2, process_id=pid)

    from heatmap_tpu.config import load_config
    from heatmap_tpu.engine import step as engine_step
    from heatmap_tpu.parallel import make_mesh
    from heatmap_tpu.sink import MemoryStore
    from heatmap_tpu.stream import MicroBatchRuntime
    from heatmap_tpu.stream.source import MemorySource

    mesh = make_mesh()
    GLOBAL_BATCH = 256

    def build_runtime(tag):
        cfg = load_config({}, batch_size=GLOBAL_BATCH, store="memory",
                          checkpoint_dir=tempfile.mkdtemp(prefix=tag),
                          state_capacity_log2=10, bucket_factor=16.0)
        src = MemorySource([])
        src.finish()
        rt = MicroBatchRuntime(cfg, src, MemoryStore(), mesh=mesh,
                               checkpoint_every=0)
        pin = engine_step.MERGE_BANK_PIN
        rt.writer.close()
        return "LIVE" if pin is engine_step._BANK_LIVE else pin

    # scenario A: banks skewed -> collective must demote BOTH to None
    pin_skewed = build_runtime("skew")
    # scenario B: equalize the banks -> unanimous verdict must survive
    write_bank()
    pin_equal = build_runtime("eq")

    with open(out_path, "w") as fh:
        json.dump({"pin_skewed": pin_skewed, "pin_equal": pin_equal,
                   "snap": engine_step.SNAP_IMPL}, fh)
""")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow  # tier-1 budget: see pyproject markers
def test_two_process_bank_skew_agreement(tmp_path):
    coord = f"127.0.0.1:{_free_port()}"
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER)

    def worker_env(pid: int) -> dict:
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        env.pop("JAX_PLATFORMS", None)
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / f"cache{pid}")
        env["HEATMAP_HW_BANK"] = str(tmp_path / f"bank{pid}.json")
        env.pop("HEATMAP_MERGE_IMPL", None)
        env.pop("HEATMAP_H3_IMPL", None)
        return env

    procs = [
        subprocess.Popen(
            [sys.executable, "-u", str(worker_py), str(pid), coord,
             str(tmp_path / f"out{pid}.json")],
            env=worker_env(pid), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE)
        for pid in (0, 1)
    ]
    outs = [p.communicate(timeout=900) for p in procs]
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, se.decode()[-2000:]

    results = [json.load(open(tmp_path / f"out{pid}.json"))
               for pid in (0, 1)]
    # A: host 0's probe verdict was not unanimous -> demoted EVERYWHERE
    assert [r["pin_skewed"] for r in results] == [None, None]
    # B: identical banks -> the unanimous verdict survives the collective
    assert [r["pin_equal"] for r in results] == ["probe", "probe"]
    # the in-program snap resolved identically on both hosts
    assert results[0]["snap"] == results[1]["snap"] == "xla"
