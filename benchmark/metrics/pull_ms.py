"""Emit ring and pull: host milliseconds per dispatched batch spent in
the deferred pull of the parked emits (``pull`` span), which waits out
the folds still running on the device."""


def read(run):
    return run.span_ms_per_batch("pull")
