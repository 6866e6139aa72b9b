"""Mesh partition: the busiest shard's rows over the mean shard's, in
per cent (100 reads even), from the program's per-shard counters of
rows folded over the window (``mesh_rows.<shard>``).  None off the
partitioned mesh, where the program keeps no such counters."""


def read(run):
    rows = [v for k, v in run.counters.items() if k.startswith("mesh_rows.")]
    if not rows or not sum(rows):
        return None
    return 100.0 * max(rows) * len(rows) / sum(rows)
