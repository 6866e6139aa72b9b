"""Standalone streaming job: ``python -m heatmap_tpu.stream [pipeline]``.

The counterpart of the reference's ``spark-submit heatmap_stream.py``
(reference: heatmap_stream.py:241-249): consume the configured source,
aggregate on device, upsert the store, checkpoint, repeat until
interrupted.  ``pipeline`` is one of heatmap_tpu.models.pipelines (default
``mbta_default``); env config is the same flat set the reference reads.
"""

import argparse
import logging

# light imports only (pipelines/source/config carry no jax): the
# supervisor parent must never touch a device
from heatmap_tpu.models.pipelines import PIPELINES, build_runtime, get_pipeline


def install_flightrec_handlers(rt) -> None:
    """Flight-recorder wiring for a standalone streaming job (no-op when
    the runtime has no recorder armed — HEATMAP_FLIGHTREC_DIR unset).

    SIGTERM becomes a SystemExit raised in the main thread, so run()'s
    finally reaches rt.close(), which sees the unwinding exception and
    writes the flight record before the process dies (the supervisor's
    kill path and any orchestrator stop signal both land here).  The
    atexit hook is the backstop for exits that bypass close(); it is a
    no-op once close() dumped or disarmed the recorder."""
    rec = getattr(rt, "flightrec", None)
    if rec is None:
        return
    import atexit
    import signal

    def _on_term(signum, frame):  # noqa: ARG001
        raise SystemExit(143)

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:  # not the main thread (embedded use)
        pass
    atexit.register(
        lambda: rec.dump("atexit: interpreter exit bypassed close()"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("pipeline", nargs="?", default="mbta_default",
                    choices=sorted(PIPELINES))
    ap.add_argument("--max-batches", type=int, default=None)
    ap.add_argument("--supervise", action="store_true",
                    help="run the job as a supervised child: restart on "
                         "crash AND on heartbeat stall (wedged device op),"
                         " resuming from the checkpoint; policy via "
                         "HEATMAP_SUPERVISE_* (stream/supervisor.py)")
    ap.add_argument("--shards", type=int, default=None,
                    help="with --supervise: fan out N H3-partitioned "
                         "runtime shard children (stream/shardmap.py), "
                         "each folding a disjoint cell space into the "
                         "shared store; defaults to HEATMAP_SHARDS (1)")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(levelname)s %(name)s %(message)s")
    import os

    shards = (args.shards if args.shards is not None
              else int(os.environ.get("HEATMAP_SHARDS", "1") or 1))
    if args.shards is not None and args.shards > 1 and not args.supervise:
        # the flag means "fan out a fleet", which only the supervisor
        # does; a standalone single-shard run is instead configured via
        # HEATMAP_SHARDS + HEATMAP_SHARD_INDEX in the env (each
        # orchestrator-managed shard process does exactly that)
        raise SystemExit("--shards needs --supervise (the fleet "
                         "supervisor spawns one child per shard)")
    if args.shards == 1 and args.supervise:
        # an explicit --shards 1 must WIN over an inherited fleet env
        # (HEATMAP_SHARDS=4 exported from a prior fleet run): the
        # single-child Supervisor passes the env through unchanged, and
        # a child silently folding 1/4 of the stream as shard 0 of a
        # phantom fleet is exactly the footgun the flag exists to close
        os.environ["HEATMAP_SHARDS"] = "1"
        os.environ["HEATMAP_SHARD_INDEX"] = "0"
    if args.supervise:
        # the PARENT never touches JAX: each child claims the chip itself
        import sys

        from heatmap_tpu.stream.supervisor import supervise_cli

        child = [sys.executable, "-m", "heatmap_tpu.stream", args.pipeline]
        if args.max_batches is not None:
            child += ["--max-batches", str(args.max_batches)]
        raise SystemExit(supervise_cli(child, shards=shards))

    from heatmap_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    p = get_pipeline(args.pipeline)
    rt, store = build_runtime(p)
    install_flightrec_handlers(rt)
    log = logging.getLogger("stream")
    log.info("pipeline %s: %s", p.name, p.description)
    try:
        # run() checkpoints and closes the runtime in its own finally
        rt.run(max_batches=args.max_batches)
    except KeyboardInterrupt:
        log.info("interrupted; shutting down")
    finally:
        store.close()


if __name__ == "__main__":
    main()
