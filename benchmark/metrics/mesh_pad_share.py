"""Mesh partition: per cent of the rows dispatched to the devices over
the window that were padding (``mesh_pad_rows.<shard>``) and not
events (``mesh_rows.<shard>``): each device's block is padded to the
feed's batch shape.  None off the partitioned mesh, where the program
keeps no such counters."""


def _total(run, prefix):
    return sum(v for k, v in run.counters.items() if k.startswith(prefix))


def read(run):
    pad = _total(run, "mesh_pad_rows.")
    dispatched = pad + _total(run, "mesh_rows.")
    if not dispatched:
        return None
    return 100.0 * pad / dispatched
