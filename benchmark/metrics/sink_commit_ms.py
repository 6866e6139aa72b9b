"""Sink and view: mean milliseconds from a batch's flush to the writer's
commit of every doc it emitted (lineage ``t_flush`` to ``t_sink``)."""


def read(run):
    return run.lineage_ms("t_flush", "t_sink")
