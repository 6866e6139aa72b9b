"""heatmap_tpu — a TPU-native real-time mobility heatmap framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of
``panosporf99/real-time-mobility-heatmap`` (see SURVEY.md): live GPS feeds are
ingested in micro-batches, snapped to H3 hexagonal cells by a vectorized
device kernel, and aggregated into time-windowed (count, avgSpeed, centroid)
tiles by a sharded scatter-add/segment-sum across TPU cores, then served
through the same MongoDB-document / GeoJSON / Leaflet contracts as the
reference (reference: heatmap_stream.py, app.py, mbta_to_kafka.py).

Layout
------
- ``hexgrid``   — H3 icosahedral hex-grid math (device + host), the TPU-native
                  replacement for the C ``h3`` library
                  (reference: heatmap_stream.py:65-75, app.py:19-41).
- ``engine``    — windowing + device aggregation state
                  (reference: heatmap_stream.py:112-133).
- ``parallel``  — mesh/shard_map multi-chip aggregation (replaces the Spark
                  shuffle, reference: heatmap_stream.py:44,112-117).
- ``stream``    — micro-batch runtime, sources, checkpoint/resume (replaces
                  Spark Structured Streaming, reference: heatmap_stream.py:79-86,241-249).
- ``sink``      — storage writers with the reference's Mongo upsert contract
                  (reference: heatmap_stream.py:150-237).
- ``serve``     — REST API + embedded Leaflet UI (reference: app.py).
- ``producers`` — MBTA / OpenSky / synthetic producers
                  (reference: mbta_to_kafka.py; README.md:111-117).
- ``models``    — the five benchmark pipeline configurations (BASELINE.json).
- ``kafka``     — the Kafka wire protocol, in-framework (no client library).
- ``native``    — C++ host components via ctypes: JSON/binary event decode,
                  Kafka RecordBatch decode + CRC32C, columnar→BSON tile ops.
"""

__version__ = "0.1.0"

from heatmap_tpu.config import Config, load_config  # noqa: F401
