"""Placement of a partitioned mesh step's arguments.

Each shard of ``PartitionedAggregator`` runs its own single-device
program.  Every argument of that program, the watermark cutoff
included, must already live on the shard's device: an argument made
on the default device makes each other device's step wait until the
default device's queue hands it over, so the per-device folds run one
after another instead of side by side.

The one-device aggregator (``device=None``) keeps its arguments
uncommitted: the cutoff stays a plain, not weak-typed ``int32[]``
scalar, so the one-chip programs are those it always compiled.
"""

import jax
import numpy as np
import pytest

from heatmap_tpu.engine.multi import MultiAggregator
from heatmap_tpu.engine.step import AggParams
from heatmap_tpu.parallel import PartitionedAggregator, make_mesh

N_DEV = 4
BATCH = 64
I32_MIN = -(2**31)
# one resolution: the partitioned mesh refuses a multi-resolution pyramid
PAIRS = [(9, 300), (9, 60)]


def _lanes(rng):
    lat = np.radians(rng.uniform(42.3, 42.5, BATCH)).astype(np.float32)
    lng = np.radians(rng.uniform(-71.2, -71.0, BATCH)).astype(np.float32)
    speed = rng.uniform(0, 80, BATCH).astype(np.float32)
    ts = rng.integers(1_700_000_000, 1_700_000_600, BATCH).astype(np.int32)
    valid = np.ones(BATCH, bool)
    return lat, lng, speed, ts, valid


def _prekeys(rng):
    return {r: (rng.integers(0, 2**32, BATCH, dtype=np.uint32),
                rng.integers(0, 2**32, BATCH, dtype=np.uint32))
            for r, _ in PAIRS}


def _array_leaves(args):
    return [x for x in jax.tree_util.tree_leaves(args)
            if isinstance(x, jax.Array)]


@pytest.mark.parametrize("cutoff", [I32_MIN, 1_700_000_000],
                         ids=["i32min", "watermark"])
@pytest.mark.parametrize("with_prekeys", [False, True],
                         ids=["snap", "prekeys"])
def test_mesh_step_arguments_live_on_their_shards_device(with_prekeys,
                                                         cutoff):
    """Every jax.Array argument of shard d's step is committed to
    device d alone, the cutoff included."""
    rng = np.random.default_rng(7)
    params = [AggParams(res=r, window_s=w, emit_capacity=BATCH)
              for r, w in PAIRS]
    agg = PartitionedAggregator(make_mesh(N_DEV), params,
                                capacity_per_shard=256, batch_size=BATCH,
                                hist_bins=8)
    lanes = _lanes(rng)
    prekeys = _prekeys(rng) if with_prekeys else None
    for d, shard in enumerate(agg.shards):
        fn, args = shard._step_call(*lanes, cutoff, prekeys)
        assert fn is (shard._step_pre if with_prekeys else shard._step)
        leaves = _array_leaves(args)
        assert len(leaves) == len(jax.tree_util.tree_leaves(args))
        for x in leaves:
            assert x.committed, (d, x.shape, x.dtype)
            assert x.devices() == {agg.devices[d]}, (d, x.shape, x.devices())
        cut = args[-1]
        assert cut.shape == () and cut.dtype == np.int32
        assert int(cut) == cutoff


@pytest.mark.parametrize("cutoff", [I32_MIN, 1_700_000_000],
                         ids=["i32min", "watermark"])
def test_one_device_step_cutoff_stays_uncommitted(cutoff):
    """The default-device aggregator hands its step the cutoff as an
    uncommitted, strongly typed int32[] scalar, as jnp.int32 makes it."""
    agg = MultiAggregator(PAIRS, capacity=256, batch_size=BATCH,
                          emit_capacity=BATCH, hist_bins=8)
    fn, args = agg._step_call(*_lanes(np.random.default_rng(3)), cutoff,
                              None)
    cut = args[-1]
    ref = jax.numpy.int32(cutoff)
    assert fn is agg._step
    assert isinstance(cut, jax.Array) and not cut.committed
    assert cut.aval == ref.aval and not cut.aval.weak_type
    assert int(cut) == cutoff
