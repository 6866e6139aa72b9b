"""HEATMAP_MERGE_IMPL=rank: the batch-only-sort rank merge must be
bit-identical to the default full merge-sort across every behavior the
fold has — watermark eviction, duplicates, invalid rows, capacity
overflow, emits, and stats."""

import functools
from unittest import mock

import numpy as np
import pytest

from heatmap_tpu.engine import AggParams, init_state
from heatmap_tpu.engine.step import (
    _merge_probe,
    _merge_rank,
    _merge_sort,
    merge_batch,
    snap_and_window,
)
from tests.test_engine import make_batch

P = AggParams(res=8, window_s=300, emit_capacity=256)


def run_pair(rng, n_batches=5, n=256, cap=1024, bins=8, cutoff_fn=None,
             nan_frac=0.1, params=P, impl_b=_merge_rank):
    a = init_state(cap, bins)
    b = init_state(cap, bins)
    max_ts = -(2**31)
    for k in range(n_batches):
        lat, lng, speed, ts, valid = make_batch(
            rng, n, t0=1_700_000_000 + k * 400, nan_frac=nan_frac)
        hi, lo, ws = snap_and_window(lat, lng, ts, valid, params)
        cutoff = np.int32(cutoff_fn(max_ts) if cutoff_fn else -2**31)
        args = (hi, lo, ws, speed, np.degrees(lat.astype(np.float64)),
                np.degrees(lng.astype(np.float64)), ts, valid, cutoff, params)
        a, ea, ta = _merge_sort(a, *args)
        b, eb, tb = impl_b(b, *args)
        for fa, fb, name in zip(a, b, a._fields):
            np.testing.assert_array_equal(
                np.asarray(fa), np.asarray(fb), err_msg=f"{name} step {k}")
        for f in ta._fields:
            assert int(getattr(ta, f)) == int(getattr(tb, f)), (f, k)
        for f in ea._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(ea, f)), np.asarray(getattr(eb, f)),
                err_msg=f"emit {f} step {k}")
        max_ts = max(max_ts, int(ta.batch_max_ts))
    return a, b


def test_rank_matches_sort_basic(rng):
    run_pair(rng)


def test_rank_matches_sort_with_watermark(rng):
    run_pair(rng, cutoff_fn=lambda m: m - 600 if m > -2**31 else -2**31)


def test_rank_matches_sort_overflow(rng):
    # capacity far below distinct groups: both impls drop the same rows
    run_pair(rng, n=512, cap=64, bins=0)


@pytest.mark.slow  # tier-1 budget: see pyproject markers
def test_rank_matches_sort_all_invalid(rng):
    a = init_state(256, 0)
    b = init_state(256, 0)
    lat, lng, speed, ts, valid = make_batch(rng, 128)
    valid[:] = False
    hi, lo, ws = snap_and_window(lat, lng, ts, valid, P)
    a, ea, ta = _merge_sort(a, hi, lo, ws, speed, np.degrees(lat),
                            np.degrees(lng), ts, valid, np.int32(-2**31), P)
    b, eb, tb = _merge_rank(b, hi, lo, ws, speed, np.degrees(lat),
                            np.degrees(lng), ts, valid, np.int32(-2**31), P)
    assert int(ta.n_valid) == int(tb.n_valid) == 0
    assert int(ta.n_active) == int(tb.n_active) == 0
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_env_var_read_at_trace_time(rng, monkeypatch):
    """HEATMAP_MERGE_IMPL set AFTER import is honored (round-3 advisor
    footgun: the old import-time snapshot silently ignored it) — and the
    MERGE_IMPL override slot still wins over the env var."""
    from heatmap_tpu.engine import step as step_mod

    monkeypatch.setenv("HEATMAP_MERGE_IMPL", "rank")
    assert step_mod._resolve_merge_impl() == "rank"
    monkeypatch.setenv("HEATMAP_MERGE_IMPL", "probe")
    assert step_mod._resolve_merge_impl() == "probe"
    with mock.patch("heatmap_tpu.engine.step.MERGE_IMPL", "sort"):
        assert step_mod._resolve_merge_impl() == "sort"


def test_env_dispatch(rng):
    """merge_batch honors the MERGE_IMPL override slot."""
    with mock.patch("heatmap_tpu.engine.step.MERGE_IMPL", "rank"):
        st = init_state(512, 0)
        lat, lng, speed, ts, valid = make_batch(rng, 128)
        hi, lo, ws = snap_and_window(lat, lng, ts, valid, P)
        st, emit, stats = merge_batch(st, hi, lo, ws, speed,
                                      np.degrees(lat), np.degrees(lng),
                                      ts, valid, np.int32(-2**31), P)
        assert int(stats.n_valid) == 128
        # slab stays sorted by the compressed key (the rank impl's core
        # invariant): live prefix keys strictly increase
        from heatmap_tpu.engine.step import _compress_key
        import jax.numpy as jnp

        live = np.asarray(st.key_hi) != 0xFFFFFFFF
        k1 = np.asarray(_compress_key(
            jnp.asarray(st.key_hi), jnp.asarray(st.key_ws),
            jnp.asarray(~live), P))
        k2 = np.where(live, np.asarray(st.key_lo), 0xFFFFFFFF)
        n = int(live.sum())
        pairs = list(zip(k1[:n].tolist(), k2[:n].tolist()))
        assert pairs == sorted(pairs) and len(set(pairs)) == n


def test_probe_matches_sort_basic(rng):
    run_pair(rng, impl_b=_merge_probe)


def test_probe_matches_sort_with_watermark(rng):
    run_pair(rng, impl_b=_merge_probe,
             cutoff_fn=lambda m: m - 600 if m > -2**31 else -2**31)


def test_probe_matches_sort_overflow(rng):
    run_pair(rng, n=512, cap=64, bins=0, impl_b=_merge_probe)


def test_probe_matches_sort_many_uniques(rng):
    """More distinct keys than the probe's unique budget (floor 256):
    the in-kernel lax.cond fallback must take the sort route and stay
    bit-identical.  res 12 over a whole city makes nearly every event
    its own (cell, window) group."""
    run_pair(rng, n=512, cap=4096, bins=4, nan_frac=0.0,
             params=AggParams(res=12, window_s=300, emit_capacity=1024),
             impl_b=_merge_probe)


def test_probe_zero_rounds_falls_back(rng):
    """PROBE_ROUNDS=0 places nothing — every batch takes the fallback
    route and must still match sort exactly.  (The module constant is
    read at trace time, so the un-jitted function is traced fresh.)"""
    with mock.patch("heatmap_tpu.engine.step.PROBE_ROUNDS", 0):
        import jax

        fresh = jax.jit(_merge_probe.__wrapped__,
                        static_argnames=("params",))
        run_pair(rng, impl_b=fresh)


def _routing_calls(fn, *args):
    """``fn(*args)`` run op by op (so ``lax.cond`` runs only the branch
    it takes), and the host copy of every ``_apply_routing`` call's
    arguments and result."""
    import jax

    from heatmap_tpu.engine import step as step_mod

    real, calls = step_mod._apply_routing, []

    def spy(*a):
        out = real(*a)
        calls.append(jax.tree_util.tree_map(np.asarray, (a[:-1], out))
                     + (a[-1],))
        return out

    with jax.disable_jit(), \
            mock.patch.object(step_mod, "_apply_routing", spy):
        out = fn(*args)
    return out, calls


def _scatter_epilogue(args, params):
    """The slab rebuild as the scatter formulation wrote it, in NumPy:
    kept state rows scatter-added (keys and anchors set) into fresh
    arrays by ``state_seg``, the batch's rows on top by ``batch_seg``."""
    (st, ev_hi, ev_lo, ev_ws, ev_speed, ev_lat, ev_lon, _ts, ev_valid,
     _late, evict, state_seg, batch_seg, _n) = args
    C, B = st.key_hi.shape[0], st.hist.shape[1]
    live = st.key_hi != np.uint32(0xFFFFFFFF)
    keep = live & ~evict
    ks, kb = state_seg < C, batch_seg < C     # the drop bin is C
    s_to, s_from = state_seg[ks], np.nonzero(ks)[0]
    b_to, b_from = batch_seg[kb], np.nonzero(kb)[0]

    def scatter_set(fill, dtype, st_lane, ev_lane):
        out = np.full(C, fill, dtype)
        out[s_to] = st_lane[s_from]
        out[b_to] = ev_lane[b_from]
        return out

    f32 = np.float32
    one = ev_valid.astype(np.int32)
    key_hi = scatter_set(0xFFFFFFFF, np.uint32, st.key_hi, ev_hi)
    key_lo = scatter_set(0xFFFFFFFF, np.uint32, st.key_lo, ev_lo)
    key_ws = scatter_set(2**31 - 1, np.int32, st.key_ws, ev_ws)
    count = np.zeros(C, np.int32)
    np.add.at(count, s_to, np.where(keep, st.count, 0)[s_from])
    np.add.at(count, b_to, one[b_from])

    def anchor(ev, stored):
        a = np.full(C, np.inf, f32)
        np.minimum.at(a, b_to, np.where(ev_valid, ev, f32(np.inf))[b_from])
        a[s_to] = np.where(keep, stored, f32(np.inf))[s_from]
        return a

    anc = [anchor(ev, s) for ev, s in ((ev_speed, st.anchor_speed),
                                       (ev_lat, st.anchor_lat),
                                       (ev_lon, st.anchor_lon))]
    gi = np.clip(batch_seg, 0, C - 1)
    with np.errstate(invalid="ignore"):   # dropped rows may read inf
        r = [np.where(ev_valid, ev - a[gi], f32(0)).astype(f32)
             for ev, a in zip((ev_speed, ev_lat, ev_lon), anc)]
    ev_vals = np.stack([r[0], r[0] * r[0], r[1], r[2]], axis=1)
    kf = keep.astype(f32)[:, None]
    st_sums = np.stack([st.sum_speed, st.sum_speed2, st.sum_lat,
                        st.sum_lon], axis=1)
    base = np.zeros((C, 4), f32)
    np.add.at(base, s_to, (st_sums * kf)[s_from])
    comp_r = np.zeros((C, 4), f32)
    np.add.at(comp_r, s_to, (st.comp * kf)[s_from])
    delta = np.zeros((C, 4), f32)
    np.add.at(delta, b_to, ev_vals[b_from])
    y = delta - comp_r
    t = base + y
    comp = (t - base) - y
    hist = np.zeros((C, B), np.int32)
    if B:
        np.add.at(hist, s_to, (st.hist * keep[:, None])[s_from])
        ev_bin = np.clip((ev_speed / f32(params.speed_hist_max / B))
                         .astype(np.int32), 0, B - 1)
        np.add.at(hist, (b_to, ev_bin[b_from]), 1)
    anc = [np.where(np.isfinite(a), a, f32(0)) for a in anc]
    return dict(key_hi=key_hi, key_lo=key_lo, key_ws=key_ws, count=count,
                sum_speed=t[:, 0], sum_speed2=t[:, 1], sum_lat=t[:, 2],
                sum_lon=t[:, 3], hist=hist, anchor_speed=anc[0],
                anchor_lat=anc[1], anchor_lon=anc[2], comp=comp)


def _negative_zeros(st, every=3):
    """``st`` with -0.0 in every ``every``-th row's sum and comp lanes
    (a restored slab may carry them; no fold makes one)."""
    rows = np.arange(st.capacity) % every == 0
    neg = lambda a: np.where(rows.reshape((-1,) + (1,) * (a.ndim - 1)),
                             np.float32(-0.0), np.asarray(a))
    return st._replace(sum_speed=neg(st.sum_speed),
                       sum_speed2=neg(st.sum_speed2),
                       sum_lat=neg(st.sum_lat), comp=neg(st.comp))


@pytest.mark.parametrize("impl", ["sort", "rank", "probe", "insert"])
def test_gather_rebuild_matches_scatter_reference(rng, impl):
    """``_apply_routing`` moves kept rows by a gather through the inverse
    routing map: its slab must equal the scatter formulation's bit for
    bit — every lane, ``hist`` and ``comp`` too, zero signs included —
    on routing tables from each slow impl (evictions, overflow past the
    capacity, an all-invalid batch) and from the insert tier, over a
    slab holding -0.0 sums."""
    import jax

    from heatmap_tpu.engine import step as step_mod

    apply = jax.jit(step_mod._apply_routing, static_argnames=("params",))
    # a full slab: a row that takes no state row must not read the last
    cap, bins = 64, 8
    t0 = 1_700_000_000 - 1_700_000_000 % 300
    if impl == "insert":
        fold = functools.partial(step_mod._merge_fastpath, slow_impl="rank")
    else:
        fold = {"sort": _merge_sort, "rank": _merge_rank,
                "probe": _merge_probe}[impl]

    def args_of(batch, cutoff=-2**31):
        lat, lng, speed, ts, valid = batch
        hi, lo, ws = snap_and_window(lat, lng, ts, valid, P)
        return (hi, lo, ws, speed,
                np.degrees(lat.astype(np.float64)).astype(np.float32),
                np.degrees(lng.astype(np.float64)).astype(np.float32),
                ts, valid, np.int32(cutoff), P)

    # more groups than the slab holds; for the slow impls a fifth in an
    # older window, which evicts below while -0.0 rows of the newer stay
    first = (make_batch(rng, 128, t0=t0, spread_s=200) if impl == "insert"
             else make_batch(rng, 128, t0=t0 + 280, spread_s=100))
    st, _e, _s = _merge_sort(init_state(cap, bins), *args_of(first))
    st = _negative_zeros(st)
    if impl == "insert":
        # a few new cells among repeats: the insert tier, then a batch
        # whose misses overflow the slab (still the insert tier)
        lat, lng, speed, ts, valid = (a.copy() for a in first)
        lat[:4] += np.float32(0.001)
        invalid = make_batch(rng, 128, t0=t0, spread_s=200)
        invalid[4][:] = False
        stream = [(lat, lng, speed, ts, valid), invalid,
                  make_batch(rng, 512, t0=t0, spread_s=200)]
        cutoffs = [-2**31] * 3
    else:
        # the next window evicting the first, an all-invalid batch, and
        # more distinct groups than the slab holds
        invalid = make_batch(rng, 128, t0=t0 + 600)
        invalid[4][:] = False
        stream = [make_batch(rng, 128, t0=t0 + 600, spread_s=200,
                             nan_frac=0.1),
                  invalid, make_batch(rng, 512, t0=t0 + 600)]
        cutoffs = [t0 + 300] * 3

    seen = dict(evict=False, overflow=False, all_invalid=False,
                neg_zero=False)
    for k, (batch, cutoff) in enumerate(zip(stream, cutoffs)):
        (st, _e, stats), calls = _routing_calls(
            fold, st, *args_of(batch, cutoff))
        if impl == "insert" and not batch[4].any():
            assert not calls  # nothing missed: the fast tier
            continue
        assert len(calls) == 1, (impl, k)
        args, (op_by_op, _emit, _stats), params = calls[0]
        compiled = jax.device_get(apply(*args, params=params)[0])
        ref = _scatter_epilogue(args, params)
        for new in (op_by_op, compiled):
            for name in new._fields:
                got, want = getattr(new, name), ref[name]
                msg = f"{impl} batch {k}: {name}"
                np.testing.assert_array_equal(got, want, err_msg=msg)
                if got.dtype == np.float32:
                    np.testing.assert_array_equal(
                        np.signbit(got), np.signbit(want), err_msg=msg)
        old, state_seg = args[0], args[11]
        neg = np.signbit(old.sum_speed) & (old.sum_speed == 0)
        seen["neg_zero"] |= bool((neg & (state_seg < cap)).any())
        seen["evict"] |= bool(args[10].any())
        seen["overflow"] |= int(stats.state_overflow) > 0
        seen["all_invalid"] |= not args[8].any()
    assert seen["overflow"] and seen["neg_zero"]
    if impl != "insert":
        assert seen["evict"] and seen["all_invalid"]


@pytest.mark.parametrize("cap,n,picks_rank", [(2048, 128, True),
                                              (256, 128, False)])
def test_env_auto_dispatch(rng, cap, n, picks_rank):
    """auto picks rank only when the slab dwarfs the batch (>= 4x)."""
    with mock.patch("heatmap_tpu.engine.step.MERGE_IMPL", "auto"):
        st = init_state(cap, 0)
        lat, lng, speed, ts, valid = make_batch(rng, n)
        hi, lo, ws = snap_and_window(lat, lng, ts, valid, P)
        with mock.patch("heatmap_tpu.engine.step._merge_rank",
                        wraps=_merge_rank) as mr, \
             mock.patch("heatmap_tpu.engine.step._merge_sort",
                        wraps=_merge_sort) as ms:
            merge_batch(st, hi, lo, ws, speed, np.degrees(lat),
                        np.degrees(lng), ts, valid, np.int32(-2**31), P)
            assert mr.called == picks_rank
            assert ms.called == (not picks_rank)
