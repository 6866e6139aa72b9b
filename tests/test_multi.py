"""MultiAggregator (engine.multi): the fused every-(res,window)-pair step
must agree exactly with independent SingleAggregators driven pair by pair,
and its packed head rows must carry the per-pair step stats."""

import numpy as np
import pytest

from heatmap_tpu.engine import AggParams
from heatmap_tpu.engine.multi import MultiAggregator, stats_from_packed
from heatmap_tpu.engine.single import SingleAggregator
from heatmap_tpu.engine.step import unpack_emit

from tests.test_engine import make_batch

PAIRS = [(7, 300), (8, 60), (8, 300), (9, 900)]
CAP = 4096
N = 512
BINS = 16


def _emit_as_dict(e):
    """unpacked emit -> {key: (count, sums..., p95)} over valid rows."""
    out = {}
    for i in np.nonzero(e["valid"])[0]:
        k = (int(e["key_hi"][i]), int(e["key_lo"][i]), int(e["key_ws"][i]))
        out[k] = (
            int(e["count"][i]),
            round(float(e["sum_speed"][i]), 3),
            round(float(e["sum_speed2"][i]), 1),
            round(float(e["sum_lat"][i]), 4),
            round(float(e["sum_lon"][i]), 4),
            round(float(e["p95"][i]), 3),
        )
    return out


@pytest.mark.slow  # tier-1 budget: see pyproject markers
def test_multi_matches_singles(rng):
    multi = MultiAggregator(PAIRS, capacity=CAP, batch_size=N,
                            emit_capacity=N, hist_bins=BINS)
    singles = {
        (r, w): SingleAggregator(
            AggParams(res=r, window_s=w, emit_capacity=N),
            capacity=CAP, batch_size=N, hist_bins=BINS,
        )
        for r, w in PAIRS
    }
    max_ts = -(2**31)
    for b in range(4):
        lat, lng, speed, ts, valid = make_batch(
            rng, N, t0=1_700_000_000 + b * 400, nan_frac=0.1)
        cutoff = max_ts - 600 if max_ts > -(2**31) else -(2**31)
        packed = multi.step_packed_all(lat, lng, speed, ts, valid, cutoff)
        bufs = np.asarray(packed)
        assert bufs.shape == (len(PAIRS), N + 1, 13)
        for idx, (r, w) in enumerate(PAIRS):
            sp, s_stats = singles[(r, w)].step_packed(
                lat, lng, speed, ts, valid, cutoff)
            e_multi = unpack_emit(bufs[idx])
            e_single = unpack_emit(np.asarray(sp))
            assert _emit_as_dict(e_multi) == _emit_as_dict(e_single), (r, w, b)
            m_stats = stats_from_packed(bufs[idx])
            s_stats = {f: int(np.asarray(getattr(s_stats, f)))
                       for f in ("n_valid", "n_late", "n_evicted", "n_active",
                                 "state_overflow", "batch_max_ts")}
            for f, v in s_stats.items():
                assert getattr(m_stats, f) == v, (r, w, b, f)
        max_ts = max(max_ts, stats_from_packed(bufs[0]).batch_max_ts)

    # states agree pairwise too (same slab after the same folds)
    for idx, (r, w) in enumerate(PAIRS):
        got = multi.view(r, w).snapshot()
        want = singles[(r, w)].snapshot()
        for g, s in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(s))


@pytest.mark.slow  # tier-1 budget: see pyproject markers
def test_pair_view_checkpoint_roundtrip(rng):
    multi = MultiAggregator(PAIRS[:2], capacity=CAP, batch_size=N,
                            emit_capacity=N, hist_bins=0)
    lat, lng, speed, ts, valid = make_batch(rng, N)
    multi.step_packed_all(lat, lng, speed, ts, valid, -(2**31))
    snap = {p: multi.view(*p).snapshot() for p in PAIRS[:2]}

    fresh = MultiAggregator(PAIRS[:2], capacity=CAP, batch_size=N,
                            emit_capacity=N, hist_bins=0)
    for p in PAIRS[:2]:
        fresh.view(*p).restore(snap[p])
    for a, b in zip(multi.states, fresh.states):
        for g, s in zip(a, b):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(s))

    # shape mismatch must refuse (config drift protection)
    import pytest

    small = MultiAggregator(PAIRS[:2], capacity=CAP // 2, batch_size=N,
                            emit_capacity=N, hist_bins=0)
    with pytest.raises(ValueError):
        small.view(*PAIRS[0]).restore(snap[PAIRS[0]])


def test_step_names_its_stages():
    """The lowered fused step of a res 7/8/9 pyramid carries the named
    scopes a device trace attributes its ops by: one snap per
    resolution, one merge per pair, the pack, and inside each merge the
    probe and the three tiers of the fast path."""
    import jax.numpy as jnp

    pairs = [(7, 300), (8, 300), (9, 300)]
    multi = MultiAggregator(pairs, capacity=CAP, batch_size=N,
                            emit_capacity=N, hist_bins=BINS)
    zf = jnp.zeros(N, jnp.float32)
    text = multi._step.lower(
        tuple(multi.states), zf, zf, zf, jnp.zeros(N, jnp.int32),
        jnp.zeros(N, bool), jnp.int32(0)).as_text(debug_info=True)
    for res in (7, 8, 9):
        assert f"snap_r{res}/" in text, res
        assert f"merge_r{res}_w5/" in text, res
    for scope in ("pack/", "probe/", "tier_fast/", "tier_insert/",
                  "tier_slow/"):
        assert scope in text, scope
