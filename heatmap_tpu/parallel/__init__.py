"""parallel — the partitioned multi-chip mesh.

The reference brings all events of one (cellId, window) group together with a
hash-partitioned JVM shuffle across Spark tasks (reference:
heatmap_stream.py:44 ``spark.sql.shuffle.partitions=4``, :112-117 groupBy).
Here the feed partitions each batch by H3 cell instead, so every group's
events already share one device: each device of a ``jax.sharding.Mesh``
folds its own cell space with the single-device program, and no collective
runs between them.
"""

from heatmap_tpu.parallel.partitioned import (  # noqa: F401
    PartitionedAggregator,
    make_mesh,
)
