#!/usr/bin/env python
"""Recorded end-to-end run: produce → Kafka → aggregate → Mongo → serve.

Drives the reference's full deployment loop (README.md:75-161) through the
framework's own wire clients and prints a structured, timestamped run log.

Topology is chosen per service and LABELED in the log:
- a reachable broker at KAFKA_BOOTSTRAP and/or mongod at MONGO_URI is used
  as-is (this is the first off-box command — see README "first command to
  run off-box");
- otherwise the in-process wire-level fakes stand in (testing.mock_kafka /
  testing.mock_mongod), which speak the same bytes but are NOT real
  servers — a log recorded this way is evidence for the client code paths,
  not for real-broker interop.

Usage:
    python tools/e2e_run.py [--events N] [--out run.log]
    KAFKA_BOOTSTRAP=host:9092 MONGO_URI=mongodb://host:27017 \
        python tools/e2e_run.py          # against real services
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import socket
import sys
import time
import urllib.request
import uuid

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


def _reachable(hostport: str, default_port: int) -> bool:
    from urllib.parse import urlparse

    u = urlparse(hostport if "://" in hostport else f"x://{hostport}")
    try:
        with socket.create_connection(
                (u.hostname or "127.0.0.1", u.port or default_port), 1.5):
            return True
    except (OSError, ValueError):
        return False


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--events", type=int, default=6000)
    ap.add_argument("--out", default=None,
                    help="also append the log lines to this file")
    args = ap.parse_args()

    lines: list[str] = []

    def log(msg: str) -> None:
        line = f"[{time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime())}] {msg}"
        print(line)
        lines.append(line)

    try:
        return _run(args, log, lines)
    finally:
        # the log must survive EVERY exit path — failures and crashes
        # are exactly the runs worth recording
        if args.out and lines:
            with open(args.out, "a", encoding="utf-8") as fh:
                fh.write("\n".join(lines) + "\n")


def _run(args, log, lines) -> int:
    import jax

    from heatmap_tpu.utils.jaxenv import (enable_compile_cache,
                                          require_accelerator)

    enable_compile_cache()
    require_accelerator()

    bootstrap = os.environ.get("KAFKA_BOOTSTRAP", "127.0.0.1:9092")
    mongo_uri = os.environ.get("MONGO_URI", "mongodb://127.0.0.1:27017")
    real_kafka = _reachable(bootstrap, 9092)
    real_mongo = _reachable(mongo_uri, 27017)

    with contextlib.ExitStack() as stack:
        if not real_kafka:
            from heatmap_tpu.testing.mock_kafka import MockKafkaBroker

            bootstrap = stack.enter_context(MockKafkaBroker())
        if not real_mongo:
            from heatmap_tpu.testing.mock_mongod import MockMongod

            mongo_uri = stack.enter_context(MockMongod())
        log(f"topology: kafka={'REAL ' + bootstrap if real_kafka else 'wire-level fake (in-process)'}"
            f", mongo={'REAL ' + mongo_uri if real_mongo else 'wire-level fake (in-process)'}")
        log(f"device: {jax.devices()[0].platform} "
            f"{jax.devices()[0].device_kind}")

        from heatmap_tpu.config import load_config
        from heatmap_tpu.producers.base import KafkaPublisher
        from heatmap_tpu.sink.mongo import MongoStore, _WireBackend
        from heatmap_tpu.serve import start_background
        from heatmap_tpu.stream import MicroBatchRuntime
        from heatmap_tpu.stream.source import KafkaSource

        topic = f"e2e-{uuid.uuid4().hex[:8]}"
        db = f"heatmap_e2e_{uuid.uuid4().hex[:8]}"
        n = args.events
        t0 = int(time.time()) - 120

        # 1. produce (the reference's mbta_to_kafka role, synthetic data)
        pub = KafkaPublisher(bootstrap, topic)
        evs = [{"provider": "e2e", "vehicleId": f"veh-{i % 40}",
                "lat": 42.3 + (i % 60) * 1e-3, "lon": -71.06 + (i % 7) * 1e-3,
                "speedKmh": 10.0 + i % 70, "bearing": 0.0, "accuracyM": 5.0,
                "ts": t0 + i % 100} for i in range(n)]
        for k in range(0, n, 500):
            pub.publish(evs[k:k + 500])
            pub.flush()
        log(f"produced {n} events to {topic} (murmur2 keyed)")

        # 2. aggregate (the reference's spark-submit role)
        src = KafkaSource(bootstrap, topic)
        try:
            # discover the topic's REAL partition list with the wire
            # client (impl-agnostic: the consumer may be confluent/
            # kafka-python, whose internals differ) — a real broker's
            # num.partitions may be anything
            from heatmap_tpu.kafka import KafkaClient

            kc = KafkaClient(bootstrap)
            parts = kc.partitions(topic)
            kc.close()
        except Exception:
            parts = [0, 1, 2]
        src.seek({p: 0 for p in parts})
        store = MongoStore(mongo_uri, db, ensure_indexes=True,
                          backend=_WireBackend(mongo_uri, db))
        cfg = load_config({}, batch_size=1024, state_capacity_log2=12,
                          store="mongo", serve_port=0,
                          checkpoint_dir=f"/tmp/e2e-ckpt-{uuid.uuid4().hex}")
        rt = MicroBatchRuntime(cfg, src, store, checkpoint_every=4)
        t_run = time.monotonic()
        got = 0
        deadline = time.time() + 120
        while got < n and time.time() < deadline:
            rt.step_once()
            got = rt.metrics.snapshot().get("events_valid", 0)
        rt.close()
        snap = rt.metrics.snapshot()
        log(f"aggregated {got}/{n} events in {time.monotonic() - t_run:.2f}s "
            f"(p50 batch {snap.get('batch_latency_p50_ms', 0):.0f} ms, "
            f"{snap.get('checkpoints', 0)} checkpoints committed)")
        if got != n:
            log("FAIL: not all events aggregated")
            return 1

        # 3. upserted state (the reference's mongosh check)
        ws = store.latest_window_start()
        tiles = list(store.tiles_in_window(ws))
        positions = list(store.all_positions())
        log(f"mongo {db}: latest window {ws} holds {len(tiles)} tiles; "
            f"{len(positions)} latest positions")

        # 4. serve (the reference's app.py role) — read back over HTTP
        httpd, _t, port = start_background(store, cfg)
        base = f"http://127.0.0.1:{port}"
        fc = json.loads(urllib.request.urlopen(
            base + "/api/tiles/latest", timeout=10).read())
        pc = json.loads(urllib.request.urlopen(
            base + "/api/positions/latest", timeout=10).read())
        httpd.shutdown()
        log(f"served GET /api/tiles/latest -> {len(fc['features'])} "
            f"Polygon features; /api/positions/latest -> "
            f"{len(pc['features'])} Point features")
        n_vehicles = min(n, 40)
        ok = (len(fc["features"]) == len(tiles)
              and len(pc["features"]) == len(positions) == n_vehicles)
        log("RESULT: OK — produce → aggregate → upsert → serve round-trip "
            "complete" if ok else "RESULT: FAIL — served counts diverge")

        store.close()
        pub.close()
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
