"""Fold step: per cent of the memory roofline.  The least bytes one
step must move (``benchmark.roofline.fold_bytes``: the feed lanes of the
batch, the state row of every group it touched read and written once,
one packed emit row per touched group) over the chip's HBM bandwidth
times the step's device time from the trace.  The snap's arithmetic is
not counted: the trace gives it no scope of its own yet."""

from benchmark import roofline

EMIT_ROW_BYTES = 13 * 4


def read(run):
    t = run.trace
    if not t or not t.get("steps") or not run.batches or not run.peaks:
        return None
    rt = run.rt
    agg = rt._agg()
    states = getattr(agg, "states", None)
    if not states:
        return None
    touched = run.counters.get("tiles_emitted", 0) / run.batches
    host_res = len(agg._uniq_res) if rt._host_snap is not None else 0
    moved = roofline.fold_bytes(rt._feed_batch, host_res, touched,
                                roofline.state_row_bytes(states[0]),
                                EMIT_ROW_BYTES)
    return roofline.roofline_share(moved, t["step_device_s"] / t["steps"],
                                   run.peaks["hbm_bytes_per_s"])
