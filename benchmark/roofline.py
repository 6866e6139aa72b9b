"""The least bytes one fold step must move through HBM, from shapes and
counters.

Per batch the fused step reads the feed lanes of every row it was sent
(latitude, longitude, speed and timestamp as 4-byte lanes, a 1-byte
valid mask, and two 4-byte key lanes per resolution when the host
snapped), reads and writes once the state row of every group the batch
touched, and writes one packed emit row per touched group.  Anything
else it moves (sorting, probing, untouched rows, the snap's own
arithmetic) is above this floor, so bytes over the chip's bandwidth is
the least time the step could take and the share of it in the measured
step time cannot pass 100%.
"""

from __future__ import annotations

FEED_LANE_BYTES = 4 * 4 + 1    # lat, lng, speed, ts + valid
KEY_LANE_BYTES = 2 * 4         # host-snapped (hi, lo) per resolution


def state_row_bytes(state) -> int:
    """Bytes of one group's row across the leaves of a state slab whose
    leaves all have the slab's rows as their first axis."""
    total = 0
    for leaf in state:
        n = 1
        for d in leaf.shape[1:]:
            n *= int(d)
        total += n * leaf.dtype.itemsize
    return total


def fold_bytes(batch_rows: int, host_snap_res: int, groups_touched: float,
               row_bytes: int, emit_row_bytes: int) -> float:
    """Least HBM bytes of one fold step."""
    feed = batch_rows * (FEED_LANE_BYTES + KEY_LANE_BYTES * host_snap_res)
    return feed + groups_touched * (2 * row_bytes + emit_row_bytes)


def roofline_share(bytes_moved: float, device_s: float,
                   hbm_bytes_per_s: float) -> float | None:
    """Per cent of the memory roofline; None without a device time."""
    if device_s <= 0:
        return None
    return 100.0 * bytes_moved / (hbm_bytes_per_s * device_s)
