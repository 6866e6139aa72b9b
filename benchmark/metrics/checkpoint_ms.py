"""Checkpoint: host milliseconds per dispatched batch that the step
thread spends in checkpoints (the program's ``checkpoint`` span: the
flush barrier, the join of the previous commit and the dispatch of the
device snapshot; outside the step's own spans).  None where the
program has no such span."""


def read(run):
    if "checkpoint" not in run.spans:
        return None
    return run.span_ms_per_batch("checkpoint")
