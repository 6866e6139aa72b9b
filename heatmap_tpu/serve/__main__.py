"""Standalone server: ``python -m heatmap_tpu.serve [--workers N]``.

Reads the same env config as the reference's app.py (MONGO_URI/MONGO_DB/
REFRESH_MS) and serves the store selected by HEATMAP_STORE.

``--workers N`` (or ``HEATMAP_SERVE_WORKERS``) runs a multi-process
serve fleet on ONE port: the parent supervises N child processes that
each bind the same (host, port) with ``SO_REUSEPORT`` — the kernel
balances accepted connections across their listen queues, so the tier
scales past the GIL without a fronting load balancer.  Each worker
runs its own ``ReplicaViewFollower`` off the shared
``HEATMAP_REPL_FEED`` and publishes its own fleet member snapshot
(tag ``serve<pid>``), so ``/fleet/healthz|metrics|audit`` on any
worker see every worker — including each worker's own PR 12 digest
verification.  The parent restarts crashed workers (short backoff) and
fans SIGTERM/SIGINT out for a clean fleet stop.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import socket
import subprocess
import sys
import time

log = logging.getLogger("heatmap_tpu.serve")


def _hold_port(host: str) -> tuple[socket.socket, int]:
    """Pick a free port and KEEP the (REUSEPORT) holder socket open:
    the workers bind the same port alongside it, and the holder never
    listens, so it receives no connections — but releasing it before
    every worker bound would let another process steal the port."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    except (AttributeError, OSError):
        pass
    s.bind((host, 0))
    return s, s.getsockname()[1]


def _spawn_worker(host: str, port: int) -> subprocess.Popen:
    env = dict(os.environ)
    env["HEATMAP_SERVE_REUSEPORT"] = "1"
    return subprocess.Popen(
        [sys.executable, "-m", "heatmap_tpu.serve", "--workers", "1",
         "--host", host, "--port", str(port)],
        env=env)


def supervise(workers: int, host: str, port: int,
              core: str = "thread") -> int:
    holder = None
    if port == 0:
        holder, port = _hold_port(host)
    log.info("serve fleet: %d workers on http://%s:%d/ "
             "(SO_REUSEPORT, %s core)", workers, host, port, core)
    procs = [_spawn_worker(host, port) for _ in range(workers)]
    stopping = {"flag": False}

    def _stop(signum, _frame):
        stopping["flag"] = True
        for p in procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    try:
        while True:
            time.sleep(0.5)
            if stopping["flag"]:
                break
            for i, p in enumerate(procs):
                rc = p.poll()
                if rc is not None:
                    # a worker died underneath the fleet: restart it
                    # (backoff so a boot-crash loop can't spin); the
                    # dead worker's member file ages to STALE on
                    # /fleet/healthz in the meantime
                    log.warning("serve worker pid=%d exited rc=%s; "
                                "restarting", p.pid, rc)
                    time.sleep(0.5)
                    if not stopping["flag"]:
                        procs[i] = _spawn_worker(host, port)
    finally:
        for p in procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + 10
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
        if holder is not None:
            holder.close()
    return 0


def main(argv=None) -> int:
    from heatmap_tpu.config import load_config

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s")
    ap = argparse.ArgumentParser(
        prog="python -m heatmap_tpu.serve", description=__doc__)
    ap.add_argument("--workers", type=int, default=None,
                    help="serve worker processes sharing one "
                         "SO_REUSEPORT port (default: "
                         "HEATMAP_SERVE_WORKERS, 1)")
    ap.add_argument("--host", default=None)
    ap.add_argument("--port", type=int, default=None)
    args = ap.parse_args(argv)

    cfg = load_config()
    workers = (args.workers if args.workers is not None
               else cfg.serve_workers)
    host = args.host or cfg.serve_host
    port = args.port if args.port is not None else cfg.serve_port
    if workers > 1:
        # children inherit HEATMAP_SERVE_CORE through the environment;
        # naming the core here makes a mixed-core fleet (a config bug)
        # visible in the supervisor log
        return supervise(workers, host, port, core=cfg.serve_core)

    from heatmap_tpu.serve.api import serve_forever
    from heatmap_tpu.sink import make_store
    from heatmap_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    log.info("serve core: %s", cfg.serve_core)

    # read-side: under a sharded jsonl config, load the union of every
    # shard's log — a serve worker must present the whole city, never
    # one shard's slice
    serve_forever(make_store(cfg, writer=False), cfg, host=host,
                  port=port,
                  reuse_port=os.environ.get(
                      "HEATMAP_SERVE_REUSEPORT") == "1")
    return 0


if __name__ == "__main__":
    sys.exit(main())
