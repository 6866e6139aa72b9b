"""Feed layer: host milliseconds per dispatched batch spent polling the
source, padding the lanes, the host snap (where it runs) and handing
the lanes to the device (``poll``, ``pad``, ``snap``, ``partition`` and
``transfer`` spans of ``heatmap_batch_span_seconds``, summed over the
window)."""


def read(run):
    return run.span_ms_per_batch("poll", "pad", "snap", "partition",
                                 "transfer")
