"""PartitionedAggregator: the mesh's fold, one fused single-device
program per mesh device over the rows the feed partitioned to it.

The feed splits each batch by H3 cell (stream/shardmap.MeshPartition),
so cell spaces are disjoint: each device's fold is exactly the one its
own MultiAggregator runs on its rows, no (cell, window) group lives on
two devices, and the devices' emits together cover every group the
batch touched.
"""

import collections

import numpy as np
import pytest

from heatmap_tpu.engine.multi import MultiAggregator, stats_from_packed
from heatmap_tpu.engine.state import TileState
from heatmap_tpu.engine.step import AggParams, unpack_emit
from heatmap_tpu.parallel import PartitionedAggregator, make_mesh
from heatmap_tpu.stream.shardmap import MeshPartition
from tests.test_engine import make_batch

N_DEV = 2
BATCH = 256
T0 = 1_700_000_000
I32_MIN = -(2**31)
RES = 9
PAIRS = [(RES, 300), (RES, 60)]
EMPTY = np.uint32(0xFFFFFFFF)


def _params(emit_capacity=BATCH):
    return [AggParams(res=r, window_s=w, emit_capacity=emit_capacity)
            for r, w in PAIRS]


def _blocks(seed, t0):
    """One batch split by its res-9 cell through the runtime's own feed
    chunk (MeshPartition.block): per device, the owned rows in stream
    order padded to BATCH, with host-snapped keys.  Also returns the
    batch's rows per (pair index, hi, lo, window start) group."""
    lat, lng, speed, ts, _ = make_batch(np.random.default_rng(seed), BATCH,
                                        t0=t0)
    part = MeshPartition(N_DEV, snap_res=RES)
    owner, cells = part.partition(lat, lng)
    groups = collections.Counter(
        (i, int(c >> np.uint64(32)), int(c & np.uint64(0xFFFFFFFF)),
         int(t) // w * w)
        for i, (_, w) in enumerate(PAIRS) for c, t in zip(cells, ts))
    blocks = [part.block((lat, lng, speed, ts), np.flatnonzero(owner == d),
                         cells, BATCH, [RES])
              for d in range(N_DEV)]
    return blocks, groups


def _live(st: TileState) -> dict:
    """{(hi, lo, ws): every leaf's row} of a slab's live groups."""
    st = TileState(*[np.asarray(leaf) for leaf in st])
    return {(int(st.key_hi[i]), int(st.key_lo[i]), int(st.key_ws[i])):
            tuple(np.asarray(leaf[i]).tobytes() for leaf in st)
            for i in np.flatnonzero(st.key_hi != EMPTY)}


def _shard_blocks(agg: PartitionedAggregator, idx: int) -> list:
    """Pair ``idx``'s checkpoint slab split back into per-device live
    groups."""
    cap = agg.capacity_per_shard
    snap = agg.snapshot(idx)
    return [_live(TileState(*[leaf[d * cap:(d + 1) * cap]
                              for leaf in snap]))
            for d in range(agg.n_shards)]


def test_step_shard_equals_each_shards_own_multiaggregator():
    """Every device's packed emits and slab are byte for byte what a
    MultiAggregator on that device folds from the same rows."""
    agg = PartitionedAggregator(make_mesh(N_DEV), _params(),
                                capacity_per_shard=512, batch_size=BATCH)
    refs = [MultiAggregator(PAIRS, capacity=512, batch_size=BATCH,
                            emit_capacity=BATCH, device=dev)
            for dev in agg.devices]
    for b, cutoff in enumerate([I32_MIN, T0 + 300]):
        blocks, _ = _blocks(b, T0 + 600 * b)
        for d, (lanes, prekeys) in enumerate(blocks):
            got = agg.step_shard(d, *lanes, cutoff, prekeys=prekeys)
            want = refs[d].step_packed_all(*lanes, cutoff, prekeys=prekeys)
            assert got.devices() == {agg.devices[d]}
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                          err_msg=f"batch {b} device {d}")
    for i in range(len(PAIRS)):
        assert _shard_blocks(agg, i) == [_live(r.states[i]) for r in refs]


def test_emits_cover_every_touched_group():
    """One batch: the devices' emitted groups are disjoint, together
    they are every (cell, window) group the batch touched, each with
    its whole count, and they are the groups the slabs hold."""
    agg = PartitionedAggregator(make_mesh(N_DEV), _params(),
                                capacity_per_shard=512, batch_size=BATCH)
    blocks, groups = _blocks(7, T0)
    emitted = [[] for _ in PAIRS]
    for d, (lanes, prekeys) in enumerate(blocks):
        packed = np.asarray(agg.step_shard(d, *lanes, I32_MIN,
                                           prekeys=prekeys))
        for i in range(len(PAIRS)):
            e = unpack_emit(packed[i])
            assert not e["overflowed"]
            assert stats_from_packed(packed[i]).n_valid == lanes[4].sum()
            emitted[i] += [((int(e["key_hi"][j]), int(e["key_lo"][j]),
                             int(e["key_ws"][j])), int(e["count"][j]))
                           for j in np.flatnonzero(e["valid"])]
    for i in range(len(PAIRS)):
        keys = [k for k, _ in emitted[i]]
        assert len(keys) == len(set(keys)), "a group on two devices"
        assert set(keys) == {g[1:] for g in groups if g[0] == i}
        assert sum(n for _, n in emitted[i]) == BATCH
        held = [k for blk in _shard_blocks(agg, i) for k in blk]
        assert sorted(held) == sorted(keys)


def test_grow_keeps_every_shards_state():
    """grow() keeps each device's live groups in its own block, and the
    grown aggregator then folds every row of the next batch into them:
    each group holds the rows of both batches."""
    agg = PartitionedAggregator(make_mesh(N_DEV), _params(),
                                capacity_per_shard=256, batch_size=BATCH)

    def fold(blocks):
        for d, (lanes, prekeys) in enumerate(blocks):
            agg.step_shard(d, *lanes, I32_MIN, prekeys=prekeys)

    first, rows = _blocks(3, T0)
    fold(first)
    before = [_shard_blocks(agg, i) for i in range(len(PAIRS))]
    assert all(blk for per_pair in before for blk in per_pair)
    agg.grow(1024)
    assert agg.capacity_per_shard == 1024
    assert all(sub.capacity_per_shard == 1024 for sub in agg.shards)
    assert [_shard_blocks(agg, i) for i in range(len(PAIRS))] == before
    second, more = _blocks(4, T0 + 120)
    fold(second)
    rows += more
    for i in range(len(PAIRS)):
        snap = agg.snapshot(i)
        live = np.flatnonzero(np.asarray(snap.key_hi) != EMPTY)
        held = collections.Counter()
        for j in live:
            held[(i, int(snap.key_hi[j]), int(snap.key_lo[j]),
                  int(snap.key_ws[j]))] += int(snap.count[j])
        assert len(held) == live.size, "a group on two devices"
        assert held == {g: n for g, n in rows.items() if g[0] == i}


REFUSED = {
    # the pairs of one fused program are unique and share one emit
    # capacity (their packed blocks stack)
    "duplicate_pairs": (lambda p: [p[0], p[0]], "duplicate"),
    "emit_capacity": (lambda p: [p[0], p[1]._replace(
        emit_capacity=BATCH // 2)], "emit_capacity"),
    # rows are partitioned by one resolution's cells: a pyramid's other
    # resolutions would have cells on two devices
    "multi_resolution": (lambda p: [p[0], p[1]._replace(res=RES - 1)],
                         "multi-resolution pyramid.*2-device mesh"),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_pair_list_is_refused(case):
    """A pair list the partitioned fold cannot run exactly is refused
    when the aggregator is built."""
    make, match = REFUSED[case]
    with pytest.raises(ValueError, match=match):
        PartitionedAggregator(make_mesh(N_DEV), make(_params()),
                              capacity_per_shard=64, batch_size=BATCH)
