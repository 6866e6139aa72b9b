"""H3-parent stream partitioning: which runtime shard owns an event.

GeoFlink's grid-based spatial stream partitioning (PAPERS.md) is the
template: the event stream is split by the H3 PARENT cell of each
event's snapped location, so N runtime shards each fold a DISJOINT cell
space and the merged view is a plain union (upsert-only fan-in at the
materialized view — no cross-shard conflicts by construction).

The assignment must be a pure, stable function of the cell index alone:
every producer, shard, and tool that ever partitions the same stream
must agree, across processes and runs (Python's salted ``hash`` is
exactly what this must NOT be).  ``shard_of_cells`` therefore derives
the parent by H3 index bit surgery (the same exact, geometry-free
operation the query pyramid uses) and maps it through a fixed 64-bit
integer mix (murmur3 fmix64) mod N.

Knobs (flat env, read by ``config.load_config``):

- ``HEATMAP_SHARDS``       total shard count N (1 = unsharded, default)
- ``HEATMAP_SHARD_INDEX``  this process's shard in ``0..N-1``
- ``HEATMAP_SHARD_RES``    parent resolution of the partition key
  (coarser = better locality per shard, finer = better balance).
  Default -1 = the snap resolution itself (parent == cell: maximal
  balance, still exact).  Must not exceed the snap resolution.

Exactness contract (what the differential test pins): the partitioner
snaps each event at the fold resolution with the same host snap the
fold itself uses, so every (cell, window) group lands wholly in one
shard (any window set) and the N-shard merged emits are byte-identical
to the 1-shard fold.  That holds for one resolution only: H3 children
are not geometrically contained in their parents, so a partition by
one resolution's cells would split cells of another resolution over two
shards.  A multi-resolution pyramid is therefore refused on a fleet
(config.load_config) and on a mesh (parallel.PartitionedAggregator).
"""

from __future__ import annotations

import logging

import numpy as np

log = logging.getLogger(__name__)

RES_SHIFT = 52
RES_MASK = np.uint64(0xF) << np.uint64(RES_SHIFT)

ENV_SHARDS = "HEATMAP_SHARDS"
ENV_SHARD_INDEX = "HEATMAP_SHARD_INDEX"
ENV_SHARD_RES = "HEATMAP_SHARD_RES"
ENV_SHARD_OVERSAMPLE = "HEATMAP_SHARD_OVERSAMPLE"


def parent_cells(cells: np.ndarray, res: int, parent_res: int) -> np.ndarray:
    """Vectorized H3 parent at ``parent_res`` for uint64 cell indices of
    uniform resolution ``res`` — the index bit surgery of
    query.pyramid.cell_to_parent (resolution field lowered, freed digits
    set to the invalid marker 7), exact for pentagons too."""
    if parent_res > res:
        raise ValueError(
            f"parent res {parent_res} finer than cell res {res}")
    cells = np.asarray(cells, np.uint64)
    out = (cells & ~RES_MASK) | (np.uint64(parent_res) << np.uint64(RES_SHIFT))
    for r in range(parent_res + 1, res + 1):
        out = out | (np.uint64(0x7) << np.uint64(3 * (15 - r)))
    return out


def _fmix64(x: np.ndarray) -> np.ndarray:
    """murmur3's 64-bit finalizer: a fixed, process-independent integer
    mix (no salted hashing anywhere near a partition key)."""
    x = np.asarray(x, np.uint64).copy()
    x ^= x >> np.uint64(33)
    x *= np.uint64(0xFF51AFD7ED558CCD)
    x ^= x >> np.uint64(33)
    x *= np.uint64(0xC4CEB9FE1A85EC53)
    x ^= x >> np.uint64(33)
    return x


def _snap_cells(lat_rad: np.ndarray, lng_rad: np.ndarray, res: int,
                host_snap) -> np.ndarray:
    """uint64 H3 cells at ``res`` for f32-radian coordinates — C++ host
    snap when a toolchain exists, else the exact Python host oracle
    (slow; tests and toolchain-less hosts only)."""
    lat_rad = np.asarray(lat_rad, np.float32)
    lng_rad = np.asarray(lng_rad, np.float32)
    if host_snap is not None:
        hi, lo = host_snap(lat_rad, lng_rad, res)
        return (hi.astype(np.uint64) << np.uint64(32)) \
            | lo.astype(np.uint64)
    from heatmap_tpu.hexgrid.host import latlng_to_cell_int

    return np.fromiter(
        (latlng_to_cell_int(float(la), float(lo_), res)
         for la, lo_ in zip(lat_rad, lng_rad)),
        np.uint64, count=len(lat_rad))


class ShardMap:
    """Stable H3-parent → shard assignment for one runtime shard.

    ``snap_res`` is the resolution events are snapped at for
    partitioning (the coarsest fold resolution); ``parent_res`` is the
    partition-key resolution (<= snap_res; -1 = snap_res)."""

    def __init__(self, n_shards: int, index: int, snap_res: int,
                 parent_res: int = -1):
        if n_shards < 1:
            raise ValueError(f"HEATMAP_SHARDS must be >= 1, got {n_shards}")
        if not 0 <= index < n_shards:
            raise ValueError(
                f"HEATMAP_SHARD_INDEX must be in 0..{n_shards - 1}, "
                f"got {index}")
        if not 0 <= snap_res <= 15:
            raise ValueError(f"snap res {snap_res} out of range")
        if parent_res == -1:
            parent_res = snap_res
        if not 0 <= parent_res <= snap_res:
            raise ValueError(
                f"HEATMAP_SHARD_RES must be in 0..{snap_res} (the snap "
                f"resolution), got {parent_res}")
        self.n_shards = int(n_shards)
        self.index = int(index)
        self.snap_res = int(snap_res)
        self.parent_res = int(parent_res)
        self._host_snap = None
        # the same host snap the fold's native path uses, so the
        # partition key derives from the very cell the fold will key on
        from heatmap_tpu.hexgrid import native_snap

        if native_snap.available():
            self._host_snap = native_snap.snap_arrays

    @classmethod
    def from_config(cls, cfg) -> "ShardMap | None":
        """The runtime's shard map, or None when unsharded."""
        if cfg.shards <= 1:
            return None
        return cls(cfg.shards, cfg.shard_index, min(cfg.resolutions),
                   cfg.shard_res)

    # ------------------------------------------------------------- keys
    def cells_of(self, lat_rad: np.ndarray, lng_rad: np.ndarray
                 ) -> np.ndarray:
        """uint64 H3 cells at ``snap_res`` for f32-radian coordinates —
        C++ host snap when a toolchain exists, else the exact Python
        host oracle (slow; tests and toolchain-less hosts only)."""
        return _snap_cells(lat_rad, lng_rad, self.snap_res,
                           self._host_snap)

    def shard_of_cells(self, cells: np.ndarray,
                       res: int | None = None) -> np.ndarray:
        """int32 shard id per uint64 cell (uniform resolution ``res``,
        default snap_res).  Pure function of (cell, n_shards): stable
        across runs, processes, and hosts."""
        parents = parent_cells(cells, self.snap_res if res is None else res,
                               self.parent_res)
        return (_fmix64(parents) % np.uint64(self.n_shards)).astype(np.int32)

    def owned_mask(self, lat_rad: np.ndarray, lng_rad: np.ndarray
                   ) -> np.ndarray:
        """bool mask of the rows this shard folds."""
        if len(np.asarray(lat_rad)) == 0:
            return np.zeros(0, bool)
        return self.shard_of_cells(self.cells_of(lat_rad, lng_rad)) \
            == self.index

    def filter_columns(self, cols):
        """(owned-rows EventColumns, n_out_of_shard, owned_cells).  Row
        order is preserved (the per-group f32 accumulation order is what
        the 1-vs-N differential byte-identity rests on); a fully-owned
        batch is returned untouched.

        The runtime accounts every filtered row under a CLOSED drop
        reason (``out_of_shard``, or ``oversample`` in
        HEATMAP_SHARD_OVERSAMPLE mode where foreign rows are the
        expected majority of each poll — stream.metrics.DROP_REASONS):
        an untagged drop here would be a permanent conservation-ledger
        residual at the feed/fold boundary (obs/audit.py).

        ``owned_cells`` are the surviving rows' uint64 H3 cells at
        ``snap_res`` when the NATIVE host snap computed the partition
        key, else None.  The runtime reuses them as the fold's pre-snap
        keys for that resolution (the same ``native_snap.snap_arrays``
        bits, just split back into hi/lo) — without the handoff a
        sharded feed pays the coarsest-resolution host snap twice per
        row, and the feed stage is the measured bottleneck."""
        if len(cols) == 0:
            return cols, 0, (np.zeros(0, np.uint64)
                             if self._host_snap is not None else None)
        cells = self.cells_of(cols.lat_rad, cols.lng_rad)
        mask = self.shard_of_cells(cells) == self.index
        n_foreign = int(len(mask) - np.count_nonzero(mask))
        owned_cells = cells if self._host_snap is not None else None
        if n_foreign == 0:
            return cols, 0, owned_cells
        keep = np.flatnonzero(mask)
        if owned_cells is not None:
            owned_cells = owned_cells[keep]
        from heatmap_tpu.stream.events import take_columns

        return take_columns(cols, keep), n_foreign, owned_cells

    def describe(self) -> str:
        return (f"shard {self.index}/{self.n_shards} "
                f"(snap res {self.snap_res}, partition parent res "
                f"{self.parent_res}, "
                f"{'native' if self._host_snap else 'python'} host snap)")


class MeshPartition:
    """Stable H3-parent → mesh-device assignment for the partitioned
    mesh fast path (parallel.PartitionedAggregator).

    Same exactness contract as :class:`ShardMap` — the partition key is
    the H3 parent (bit surgery) of the event's cell snapped at the
    COARSEST fold resolution with the fold's own host snap, mapped
    through murmur3 fmix64: a pure, stable function of the cell index,
    so every (cell, window) group lands wholly on one device and the
    merged per-device emits are byte-identical to the single-device
    fold (one resolution; a multi-resolution pyramid is refused).

    ``outer_shards`` composes with PROCESS-level H3 sharding
    (HEATMAP_SHARDS): a shard process already filtered its rows by
    ``fmix64(parent) % N``, so the device key must consume DIFFERENT
    hash bits — the quotient ``fmix64(parent) // N`` feeds the device
    modulus.  With correlated moduli (e.g. N == D == 2) the naive
    same-hash assignment would park every one of a process's rows on
    its first device."""

    def __init__(self, n_devices: int, snap_res: int,
                 parent_res: int = -1, outer_shards: int = 1):
        if n_devices < 1:
            raise ValueError(f"mesh device count must be >= 1, "
                             f"got {n_devices}")
        if not 0 <= snap_res <= 15:
            raise ValueError(f"snap res {snap_res} out of range")
        if parent_res == -1:
            parent_res = snap_res
        if not 0 <= parent_res <= snap_res:
            raise ValueError(
                f"mesh partition parent res must be in 0..{snap_res} "
                f"(the snap resolution), got {parent_res}")
        self.n_devices = int(n_devices)
        self.snap_res = int(snap_res)
        self.parent_res = int(parent_res)
        self.outer_shards = max(1, int(outer_shards))
        self._host_snap = None
        from heatmap_tpu.hexgrid import native_snap

        if native_snap.available():
            self._host_snap = native_snap.snap_arrays

    @property
    def native(self) -> bool:
        """True when the C++ host snap computes the partition key — the
        runtime then reuses the cells as the fold's pre-snap keys for
        the coarsest resolution (the PR 7 handoff, per device)."""
        return self._host_snap is not None

    def cells_of(self, lat_rad: np.ndarray, lng_rad: np.ndarray
                 ) -> np.ndarray:
        return _snap_cells(lat_rad, lng_rad, self.snap_res,
                           self._host_snap)

    def device_of_cells(self, cells: np.ndarray,
                        res: int | None = None) -> np.ndarray:
        """int32 mesh-device id per uint64 cell.  Pure function of
        (cell, outer_shards, n_devices): stable across runs/processes."""
        parents = parent_cells(
            cells, self.snap_res if res is None else res, self.parent_res)
        mix = _fmix64(parents) // np.uint64(self.outer_shards)
        return (mix % np.uint64(self.n_devices)).astype(np.int32)

    def partition(self, lat_rad: np.ndarray, lng_rad: np.ndarray,
                  cells: np.ndarray | None = None):
        """(device ids, cells) for a batch's rows.  ``cells`` may be the
        process-level ownership filter's already-snapped cells (same
        snap_res by construction — both partition at the coarsest fold
        resolution), in which case no second snap is paid."""
        if cells is None:
            cells = self.cells_of(lat_rad, lng_rad)
        return self.device_of_cells(cells), cells

    def block(self, lanes, sel: np.ndarray, cells: np.ndarray,
              bucket: int, resolutions) -> tuple:
        """One device's feed chunk on the host: the rows ``sel``
        (owned-row indices, in stream order) of ``lanes`` = (lat_rad,
        lng_rad, speed_kmh, ts_s) compacted to the front of ``bucket``
        zero-padded rows, and the ``valid`` mask, as ``(lat, lng,
        speed, ts, valid)``; plus pre-snap keys ``{res: (hi, lo)}`` for
        each of ``resolutions``, where the batch's partition ``cells``
        are reused at ``snap_res`` (no second snap), or None without
        the native snap."""
        n = int(sel.size)
        out = []
        for a, dtype in zip(lanes, (np.float32, np.float32, np.float32,
                                    np.int32)):
            col = np.zeros(bucket, dtype)
            col[:n] = a[sel]
            out.append(col)
        valid = np.zeros(bucket, bool)
        valid[:n] = True
        out.append(valid)
        if self._host_snap is None:
            return tuple(out), None
        lat, lng = out[0], out[1]
        prekeys = {}
        for r in resolutions:
            hi = np.zeros(bucket, np.uint32)
            lo = np.zeros(bucket, np.uint32)
            if r == self.snap_res:
                own = cells[sel]
                hi[:n] = (own >> np.uint64(32)).astype(np.uint32)
                lo[:n] = own.astype(np.uint32)
            else:
                hi[:n], lo[:n] = self._host_snap(lat[:n], lng[:n], r)
            prekeys[r] = (hi, lo)
        return tuple(out), prekeys

    def describe(self) -> str:
        return (f"{self.n_devices}-device mesh partition (snap res "
                f"{self.snap_res}, parent res {self.parent_res}, "
                f"outer shards {self.outer_shards}, "
                f"{'native' if self._host_snap else 'python'} host snap)")
