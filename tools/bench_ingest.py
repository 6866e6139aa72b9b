#!/usr/bin/env python
"""Ingest-stack bench: publisher -> own-process wire broker -> KafkaSource.

Measures end-to-end Kafka ingest throughput (produce + fetch + decode to
EventColumns) per HEATMAP_EVENT_FORMAT on this host, isolating the
stream-side ingest ceiling from the device fold (SURVEY.md §7 hard part
3).  The mock broker speaks the real wire protocol over real sockets, so
this exercises exactly the consumer path production uses.

Usage: python tools/bench_ingest.py [n_events]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


def _broker_child(info_q, stop_evt) -> None:
    """Own OS process for the mock broker: in-process, its handler
    threads contend for the GIL with the consume loop's Python and the
    measured rate understates the consumer (a real broker is off-host
    anyway)."""
    from heatmap_tpu.testing.mock_kafka import MockKafkaBroker

    broker = MockKafkaBroker()
    info_q.put(broker.bootstrap)
    stop_evt.wait()
    broker.close()


class _ProcBroker:
    """MockKafkaBroker-compatible context manager over the child."""

    def __init__(self):
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        self._q = ctx.Queue()
        self._stop = ctx.Event()
        self._proc = ctx.Process(target=_broker_child,
                                 args=(self._q, self._stop), daemon=True)
        self._proc.start()
        self.bootstrap = self._q.get(timeout=60)

    def __enter__(self) -> str:
        return self.bootstrap

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._proc.join(timeout=10)
        if self._proc.is_alive():
            self._proc.terminate()


def bench_format(fmt: str, n: int) -> tuple[float, float]:
    """(publish ev/s, consume ev/s) for one format."""
    os.environ["HEATMAP_EVENT_FORMAT"] = fmt
    # pin the framework's wire client: the mock broker doesn't speak the
    # consumer-group APIs an installed confluent/kafka-python would use
    os.environ["HEATMAP_KAFKA_IMPL"] = "wire"
    from heatmap_tpu.producers.base import KafkaPublisher
    from heatmap_tpu.stream.events import EventColumns
    from heatmap_tpu.stream.source import KafkaSource

    evs = [{"provider": "mbta", "vehicleId": f"veh-{i % 5000}",
            "lat": 42.3 + (i % 100) * 1e-4, "lon": -71.05,
            "speedKmh": 30.0, "bearing": 0.0, "accuracyM": 5.0,
            "ts": 1_700_000_000 + (i % 600)} for i in range(n)]
    with _ProcBroker() as bootstrap:
        src = KafkaSource(bootstrap, "bench")
        pub = KafkaPublisher(bootstrap, "bench", event_format=fmt)
        # 64k-event publish chunks: the producer's chunk size IS the
        # record-batch size, and per-record costs (strtab, framing, CRC
        # per RecordBatch) amortize with it (VERDICT r4 item 5).  Live
        # producers deliver however much a poll returned; a backfill
        # replay controls this directly (tools/replay_to_kafka.py).
        chunk = 1 << 16
        t0 = time.perf_counter()
        for k in range(0, n, chunk):
            pub.publish(evs[k:k + chunk])
            pub.flush()
        t_pub = time.perf_counter() - t0

        got = 0
        t0 = time.perf_counter()
        while got < n:
            polled = src.poll(1 << 17)
            if isinstance(polled, EventColumns):
                got += len(polled)
            else:
                got += len(polled or [])
            if not polled:
                break
        t_con = time.perf_counter() - t0
        pub.close()
        src.close()
    assert got == n, (fmt, got, n)
    return n / t_pub, n / t_con


def _events(n: int) -> list:
    """The deterministic bench event set, spread over a wide box so an
    H3 partition of it touches every shard."""
    return [{"provider": "mbta", "vehicleId": f"veh-{i % 5000}",
             "lat": 42.3 + (i % 100) * 1e-4 + (i % 193) * 1e-3,
             "lon": -71.05 - (i % 97) * 1e-3,
             "speedKmh": 30.0, "bearing": 0.0, "accuracyM": 5.0,
             "ts": 1_700_000_000 + (i % 600)} for i in range(n)]


def _shard_consumer_child(q, bootstrap, index, expect, go_evt) -> None:
    """Own OS process: one shard's consumer draining its OWN partition
    topic (produce-side H3 partitioning — the GeoFlink shape — means a
    shard's consumer never sees, fetches, or decodes foreign rows)."""
    import time as _time

    os.environ["HEATMAP_EVENT_FORMAT"] = "columnar"
    os.environ["HEATMAP_KAFKA_IMPL"] = "wire"
    from heatmap_tpu.stream.source import KafkaSource

    src = KafkaSource(bootstrap, f"bench-s{index}")
    q.put(("ready", index))
    go_evt.wait()
    got = 0
    t0 = _time.perf_counter()
    while got < expect:
        polled = src.poll(1 << 17)
        got += len(polled) if polled is not None else 0
    t = _time.perf_counter() - t0
    src.close()
    q.put(("done", index, expect, t))


def bench_sharded(n: int, n_shards: int) -> dict:
    """Partitioned-topic columnar ingest: the publisher partitions the
    stream by H3 parent cell (stream/shardmap.py) into one topic per
    shard, and N consumer processes drain their partitions
    CONCURRENTLY.  Aggregate consume ev/s = total events over the
    slowest shard's drain — every event is fetched + decoded exactly
    once fleet-wide, so ingest scales with cores instead of hitting
    the one-core consume ceiling."""
    import multiprocessing as mp
    import numpy as np

    os.environ["HEATMAP_EVENT_FORMAT"] = "columnar"
    os.environ["HEATMAP_KAFKA_IMPL"] = "wire"
    from heatmap_tpu.producers.base import KafkaPublisher
    from heatmap_tpu.stream.shardmap import ShardMap

    evs = _events(n)
    sm = ShardMap(n_shards, 0, 8)
    lat = np.radians([e["lat"] for e in evs]).astype(np.float32)
    lng = np.radians([e["lon"] for e in evs]).astype(np.float32)
    shard_of = sm.shard_of_cells(sm.cells_of(lat, lng))
    parts: list = [[] for _ in range(n_shards)]
    for e, s in zip(evs, shard_of):
        parts[s].append(e)
    with _ProcBroker() as bootstrap:
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        go = ctx.Event()
        procs = [ctx.Process(target=_shard_consumer_child,
                             args=(q, bootstrap, i, len(parts[i]), go),
                             daemon=True)
                 for i in range(n_shards)]
        for p in procs:
            p.start()
        for _ in procs:
            kind, _ = q.get(timeout=120)
            assert kind == "ready"
        # partition + publish is ONE producer-side measurement: the H3
        # partitioner runs where GeoFlink runs it, in the produce path
        t0 = time.perf_counter()
        for i in range(n_shards):
            pub = KafkaPublisher(bootstrap, f"bench-s{i}",
                                 event_format="columnar")
            chunk = 1 << 16
            for k in range(0, len(parts[i]), chunk):
                pub.publish(parts[i][k:k + chunk])
                pub.flush()
            pub.close()
        t_pub = time.perf_counter() - t0
        go.set()
        per_shard = {}
        for _ in procs:
            kind, i, got, t = q.get(timeout=600)
            assert kind == "done"
            per_shard[i] = {"shard": i, "events": got,
                            "consume_eps": round(got / t, 1),
                            "drain_s": round(t, 3)}
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
    slowest = max(r["drain_s"] for r in per_shard.values())
    return {
        "metric": "sharded columnar ingest (partitioned-topic, "
                  "concurrent consumers)",
        "shards": n_shards,
        "n_events": n,
        "publish_eps": round(n / t_pub, 1),
        "per_shard": [per_shard[i] for i in sorted(per_shard)],
        "aggregate_consume_eps": round(n / slowest, 1),
    }


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("n_events", type=int, nargs="?", default=500_000)
    ap.add_argument("--shards", type=int, default=1,
                    help=">1 benches the H3-PARTITIONED ingest stack "
                    "(stream/shardmap.py): the publisher partitions by "
                    "parent cell into one topic per shard and N "
                    "consumer processes drain concurrently; prints one "
                    "JSON line with per-shard and aggregate ev/s")
    args = ap.parse_args()
    n = args.n_events
    if args.shards > 1:
        import json

        print(json.dumps(bench_sharded(n, args.shards)))
        return
    print(f"# {n:,} events per format, single core, wire broker in its own process")
    for fmt in ("json", "binary", "columnar"):
        pub_eps, con_eps = bench_format(fmt, n)
        print(f"{fmt:9s} publish {pub_eps / 1e6:6.2f}M ev/s   "
              f"consume {con_eps / 1e6:6.2f}M ev/s")


if __name__ == "__main__":
    import jax

    # ingest only — the CPU, chosen explicitly, keeps the chip free
    jax.config.update("jax_platforms", "cpu")
    main()
