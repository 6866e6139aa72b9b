"""Shard-per-device mesh aggregation, partitioned by H3 cell.

The feed (stream/shardmap.MeshPartition) splits each batch by H3 parent
cell, so every device already holds exactly its own cell space and no
event has to move between devices.  Each mesh device runs the fused
single-device program (engine.multi.MultiAggregator) over its own rows,
with every argument committed to that device: no collectives, no
lockstep, no shared dispatch stream.  Cell spaces are disjoint, so the
devices' emits merge upsert-only at the view, exactly as the process
fleet's shards do (HEATMAP_SHARDS).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from heatmap_tpu.config import MULTI_RES_REFUSAL
from heatmap_tpu.engine.state import TileState
from heatmap_tpu.engine.step import AggParams

AXIS = "shards"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D ``shards`` mesh over the first ``n_devices`` devices, in id
    order."""
    if devices is None:
        devices = jax.devices()
    devices = sorted(devices, key=lambda d: d.id)
    if n_devices:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (AXIS,))


class PartitionedAggregator:
    """One fused single-device program per mesh device.

    Each device folds the row block the feed partitioned to it, with
    every input committed to that device (the watermark cutoff too), so
    the async per-device folds overlap.  Each device's packed emits
    stay resident on its own chip, which lets the runtime keep one
    independently flushed EmitRing and one independently governed
    BatchGovernor per shard.  Single-process meshes only: a pod runs
    one runtime per host, split by HEATMAP_SHARDS.  The pairs share one
    resolution, the one the feed partitions by: a multi-resolution
    pyramid is refused, since the partition would split the cells of
    its other resolutions over two devices."""

    def __init__(
        self,
        mesh: Mesh,
        params: AggParams | Sequence[AggParams],
        capacity_per_shard: int,
        batch_size: int,
        hist_bins: int = 0,
    ):
        if len({d.process_index for d in mesh.devices.ravel()}) > 1:
            raise ValueError(
                "the partitioned mesh is single-process: run one runtime "
                "per host, split by HEATMAP_SHARDS/HEATMAP_SHARD_INDEX")
        plist = ([params] if isinstance(params, AggParams) else list(params))
        if len({(p.res, p.window_s) for p in plist}) != len(plist):
            raise ValueError(f"duplicate (res, window) pairs: "
                             f"{[(p.res, p.window_s) for p in plist]}")
        if len({p.emit_capacity for p in plist}) != 1:
            raise ValueError("all pairs must share emit_capacity "
                             "(packed blocks stack uniformly)")
        if len({p.res for p in plist}) != 1:
            raise ValueError(MULTI_RES_REFUSAL.format(
                what=f"a {len(mesh.devices.ravel())}-device mesh",
                res=sorted({p.res for p in plist})))
        from heatmap_tpu.engine.multi import MultiAggregator

        self.mesh = mesh
        self.devices = sorted(mesh.devices.ravel().tolist(),
                              key=lambda d: d.id)
        self.n_shards = len(self.devices)
        # a LIST on purpose: grow() mutates it in place so callers
        # holding a reference read updated emit capacities
        self.params_list = list(plist)
        self.params = self.params_list[0]
        self.pairs = [(p.res, p.window_s) for p in self.params_list]
        self.batch_size = batch_size
        self.capacity_per_shard = capacity_per_shard
        self.shards = [
            MultiAggregator(
                self.pairs, capacity=capacity_per_shard,
                batch_size=batch_size,
                emit_capacity=plist[0].emit_capacity,
                hist_bins=hist_bins,
                speed_hist_max=plist[0].speed_hist_max,
                device=d,
            )
            for d in self.devices
        ]
        self._uniq_res = self.shards[0]._uniq_res
        self.n_steps = 0

    def instrument(self, wrap) -> None:
        """Wrap every device program's jitted entry points with the
        compile tracker — a retrace on ANY shard (slab growth, shape
        flap) must be visible, and the per-shard governors' shared
        retrace guardrail latches off this one tracker."""
        for i, sub in enumerate(self.shards):
            sub._step = wrap(f"mesh{i}_step", sub._step)
            sub._step_pre = wrap(f"mesh{i}_step_pre", sub._step_pre)

    def step_shard(self, shard: int, lat_rad, lng_rad, speed, ts, valid,
                   watermark_cutoff, prekeys=None):
        """Fold one pre-partitioned row block into shard ``shard``'s
        states; returns that device's packed (P, E+1, 13) emit matrix,
        device-resident (park it in the shard's EmitRing).  The caller
        commits the feed arrays to the shard's device ahead of time for
        H2D/compute overlap; host arrays work too (MultiAggregator
        commits them)."""
        # n_steps counts BATCHES like MultiAggregator, not chunks — the runtime bumps it once per dispatched batch
        return self.shards[shard].step_packed_all(
            lat_rad, lng_rad, speed, ts, valid, watermark_cutoff,
            prekeys=prekeys)

    def grow(self, new_capacity: int) -> None:
        """Resize every shard's slab (uniform capacity keeps checkpoint
        blocks splittable); next step per shard retraces, exactly like
        the single-device grow."""
        for sub in self.shards:
            sub.grow(new_capacity)
        self.capacity_per_shard = new_capacity
        new_emit = min(self.batch_size, new_capacity)
        self.params_list[:] = [
            p._replace(emit_capacity=max(p.emit_capacity, new_emit))
            for p in self.params_list
        ]
        self.params = self.params_list[0]

    # --- checkpoint interface: one concatenated (n_shards * cap, …) slab
    # per pair, split back per device on restore ------------------------

    def view(self, res: int, window_s: int) -> "PartitionedPairView":
        return PartitionedPairView(self, self.pairs.index((res, window_s)))

    def snapshot(self, idx: int = 0) -> TileState:
        from heatmap_tpu.engine.state import to_host

        snaps = [to_host(sub.states[idx]) for sub in self.shards]
        return TileState(*[
            np.concatenate([np.asarray(getattr(s, f)) for s in snaps])
            for f in TileState._fields
        ])

    def device_snapshot(self, idx: int = 0) -> list:
        """Fresh-buffer on-device copies, one per shard (the step
        programs donate the slabs, so references don't survive);
        ``snapshot_to_host`` concatenates them later, off the step
        thread."""
        from heatmap_tpu.engine.state import device_copy

        return [device_copy(sub.states[idx]) for sub in self.shards]

    @staticmethod
    def snapshot_to_host(snap) -> TileState:
        from heatmap_tpu.engine.state import to_host

        if isinstance(snap, TileState):
            return to_host(snap)
        snaps = [to_host(s) for s in snap]
        return TileState(*[
            np.concatenate([np.asarray(getattr(s, f)) for s in snaps])
            for f in TileState._fields
        ])

    def restore(self, st: TileState, idx: int = 0) -> None:
        cap = self.capacity_per_shard
        want_rows = self.n_shards * cap
        got = (st.key_hi.shape, st.hist.shape)
        want = ((want_rows,),
                (want_rows, self.shards[0].states[idx].hist.shape[1]))
        if got != want:
            raise ValueError(f"state shape {got} != configured {want}")
        for i, sub in enumerate(self.shards):
            block = TileState(*[np.asarray(leaf)[i * cap:(i + 1) * cap]
                                for leaf in st])
            sub.states[idx] = TileState(*[sub._put(leaf)
                                          for leaf in block])


class PartitionedPairView:
    """Checkpoint adapter for one pair of a PartitionedAggregator (the
    snapshot/restore surface of engine.multi.PairView)."""

    def __init__(self, agg: PartitionedAggregator, idx: int):
        self._agg = agg
        self._idx = idx

    @property
    def capacity_per_shard(self) -> int:  # tracks growth
        return self._agg.capacity_per_shard

    @property
    def state(self) -> TileState:
        return self._agg.shards[0].states[self._idx]

    def snapshot(self) -> TileState:
        return self._agg.snapshot(self._idx)

    def device_snapshot(self) -> list:
        return self._agg.device_snapshot(self._idx)

    @staticmethod
    def to_host(snap) -> TileState:
        return PartitionedAggregator.snapshot_to_host(snap)

    @property
    def n_shards(self) -> int:
        return self._agg.n_shards

    def restore(self, st: TileState) -> None:
        self._agg.restore(st, self._idx)
