"""Sink and view: milliseconds per dispatched batch that the sink
writer's thread spends applying writes to the store and the view (the
program's ``sink_tiles``, ``sink_positions`` and ``sink_view`` spans;
the queue wait that ``sink_commit_ms`` also holds is left out).  None
where the program has no such spans."""

NAMES = ("sink_tiles", "sink_positions", "sink_view")


def read(run):
    if not any(n in run.spans for n in NAMES):
        return None
    return run.span_ms_per_batch(*NAMES)
