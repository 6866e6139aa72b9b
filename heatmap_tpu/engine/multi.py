"""MultiAggregator — every (resolution, window) pair fused into ONE program.

The hex-pyramid and multi-window configs (BASELINE configs #4/#5) need
3+ concurrent aggregations of the *same* micro-batch.  Driving one
SingleAggregator per pair costs, per batch, P separate dispatches and P
separate device->host emit pulls — and re-snaps the batch once per window
length even though the snap only depends on the resolution.

This class fuses all pairs into a single jitted step:

  * the H3 snap runs once per **unique resolution** (a 3-window config
    snaps once, not three times);
  * each pair's ``merge_batch`` fold runs inside the same XLA program, so
    the per-step dispatch overhead is paid once;
  * the per-pair packed emits are stacked into one (P, E+1, 13) matrix —
    the whole batch's output crosses the device->host link in ONE pull.

Host API mirrors SingleAggregator per pair via :class:`PairView` (the
stream runtime checkpoints each (res, window) state independently;
reference parity: heatmap_stream.py:112-133 run once per configuration).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from heatmap_tpu.engine.state import (TileState, donate_state_argnums,
                                      init_state)
from heatmap_tpu.engine.step import (
    AggParams,
    merge_batch,
    pack_emit,
    read_stats_rider,
    ride_stats,
    snap_and_window,
    window_start,
)


def fused_fold(params_list, states, lat_rad, lng_rad, speed, ts, valid,
               cutoff, prekeys=None):
    """THE per-batch multi-pair fold (trace-time): one H3 snap per unique
    resolution shared across its windows, then each pair's merge_batch on
    its own state slab.  Shared by MultiAggregator's jitted step and by
    bench.py's scanned chunks, so the benchmark always measures exactly
    the production fusion.  Returns (new_states, [(emit, stats)] in pair
    order).

    Each stage runs under a ``jax.named_scope`` (``snap_r<res>`` per
    unique resolution, ``merge_r<res>_w<minutes>`` per pair), so a
    device trace attributes every op of the step to one of them.
    Scopes are trace-time metadata: the computation is unchanged.

    ``prekeys``: optional dict res -> (hi, lo) of PRE-COMPUTED cell keys
    (the host C++ snap, hexgrid.native_snap) — the fold then runs no
    in-program snap for those resolutions, only the valid-mask.  This is
    how HEATMAP_H3_IMPL=native integrates: snapping stays host-side
    (callbacks inside jit proved deadlock-prone on the CPU runtime), and
    the masking below keeps the invalid-row contract identical to
    snap_and_window's."""
    from heatmap_tpu.engine.state import EMPTY_KEY_HI, EMPTY_KEY_LO

    lat_deg = lat_rad * jnp.float32(180.0 / np.pi)
    lon_deg = lng_rad * jnp.float32(180.0 / np.pi)
    by_res: dict[int, tuple] = {}
    for p in params_list:
        if p.res not in by_res:
            with jax.named_scope(f"snap_r{p.res}"):
                if prekeys is not None and p.res in prekeys:
                    hi, lo = prekeys[p.res]
                    hi = jnp.where(valid, hi, jnp.uint32(EMPTY_KEY_HI))
                    lo = jnp.where(valid, lo, jnp.uint32(EMPTY_KEY_LO))
                else:
                    hi, lo, _ = snap_and_window(lat_rad, lng_rad, ts, valid,
                                                p)
            by_res[p.res] = (hi, lo)
    new_states, folded = [], []
    for p, st in zip(params_list, states):
        hi, lo = by_res[p.res]
        with jax.named_scope(f"merge_r{p.res}_w{p.window_s // 60}"):
            ws = window_start(ts, valid, p.window_s)
            st2, emit, stats = merge_batch(
                st, hi, lo, ws, speed, lat_deg, lon_deg, ts, valid, cutoff,
                p)
        new_states.append(st2)
        folded.append((emit, stats))
    return tuple(new_states), folded


class MultiAggregator:
    """Fused aggregation over P (resolution, window_s) pairs, one device.

    All pairs share capacity / hist_bins / emit capacity so states and
    emits stack along a leading pair axis.

    ``device``: optional explicit jax device this aggregator's state and
    feeds are committed to.  The partitioned mesh fast path
    (parallel.PartitionedAggregator) runs one MultiAggregator
    per mesh device this way — jit follows the committed inputs, so each
    shard's program executes on its own chip with no collectives and no
    shared dispatch stream.  ``None`` (the default) keeps the historical
    default-device behavior.
    """

    n_shards = 1

    def __init__(
        self,
        pairs: Sequence[tuple[int, int]],   # (res, window_s), unique
        capacity: int,
        batch_size: int,
        emit_capacity: int,
        hist_bins: int = 0,
        speed_hist_max: float = 256.0,
        device=None,
    ):
        if len(set(pairs)) != len(pairs):
            raise ValueError(f"duplicate (res, window) pairs: {pairs}")
        self.pairs = list(pairs)
        self.capacity_per_shard = capacity
        self.batch_size = batch_size
        self.device = device
        self.params = [
            AggParams(res=r, window_s=w, emit_capacity=emit_capacity,
                      speed_hist_max=speed_hist_max)
            for r, w in self.pairs
        ]
        self.states: list[TileState] = [
            TileState(*[self._put(leaf)
                        for leaf in init_state(capacity, hist_bins)])
            for _ in self.pairs
        ]
        self.n_steps = 0

        param_list = self.params

        def _pack(folded):
            # ride the step stats in the packed head row, so the host
            # needs NO second transfer for them (see stats_from_packed)
            with jax.named_scope("pack"):
                return jnp.stack([
                    ride_stats(pack_emit(emit, p.speed_hist_max), stats)
                    for p, (emit, stats) in zip(param_list, folded)])

        def _step(states, lat, lng, speed, ts, valid, cutoff):
            new_states, folded = fused_fold(param_list, states, lat, lng,
                                            speed, ts, valid, cutoff)
            return new_states, _pack(folded)

        self._step = jax.jit(_step,
                     donate_argnums=donate_state_argnums())

        uniq_res = list(dict.fromkeys(p.res for p in param_list))
        self._uniq_res = uniq_res

        def _step_pre(states, keys, lat, lng, speed, ts, valid, cutoff):
            prekeys = {r: keys[i] for i, r in enumerate(uniq_res)}
            new_states, folded = fused_fold(param_list, states, lat, lng,
                                            speed, ts, valid, cutoff,
                                            prekeys=prekeys)
            return new_states, _pack(folded)

        self._step_pre = jax.jit(
            _step_pre, donate_argnums=donate_state_argnums())

    def _put(self, x):
        """Commit ``x`` to this aggregator's device (a no-op asarray on
        the default-device path, and a no-op device_put for arrays
        already committed there)."""
        if self.device is not None:
            return jax.device_put(x, self.device)
        return jnp.asarray(x)

    def instrument(self, wrap) -> None:
        """Wrap the jitted entry points with a compile tracker
        (obs.runtimeinfo.CompileTracker.wrap): per-function compile
        counts / compile seconds / retrace-after-warmup detection.
        Idempotent enough for one runtime: call once, right after
        construction and before the first step."""
        self._step = wrap("multi_step", self._step)
        self._step_pre = wrap("multi_step_pre", self._step_pre)

    def step_packed_all(self, lat_rad, lng_rad, speed, ts, valid,
                        watermark_cutoff, prekeys=None):
        """Fold one batch into every pair's state.

        Returns the packed emits on device: (P, E+1, 13) uint32 — one
        ``unpack_emit`` row block per pair in ``self.pairs`` order, with
        that pair's step stats ridden in head-row slots 2..7
        (``stats_from_packed``).

        ``prekeys``: optional dict res -> (hi, lo) numpy arrays of
        host-computed cell keys.  Unlike fused_fold's per-res optional
        contract, THIS method requires keys for EVERY unique resolution
        when prekeys is given (a partial dict raises) — the pre-jitted
        _step_pre signature takes the full key tuple.
        """
        fn, args = self._step_call(lat_rad, lng_rad, speed, ts, valid,
                                   watermark_cutoff, prekeys)
        states, packed = fn(*args)
        self.states = list(states)
        self.n_steps += 1
        return packed

    def compile_step(self, lat_rad, lng_rad, speed, ts, valid,
                     watermark_cutoff, prekeys=None) -> None:
        """Compile, without running, the program ``step_packed_all``
        runs on these arguments: the states are left as they are, and
        the next step of that shape finds the program compiled."""
        fn, args = self._step_call(lat_rad, lng_rad, speed, ts, valid,
                                   watermark_cutoff, prekeys)
        getattr(fn, "_inner", fn).lower(*args).compile()

    def _step_call(self, lat_rad, lng_rad, speed, ts, valid,
                   watermark_cutoff, prekeys):
        """(jitted step, its arguments) of one batch."""
        # committed, or each shard's step waits in the default device's queue
        lanes = (self._put(lat_rad), self._put(lng_rad), self._put(speed),
                 self._put(ts), self._put(valid),
                 self._put(np.int32(watermark_cutoff)))
        if prekeys is None:
            return self._step, (tuple(self.states),) + lanes
        missing = [r for r in self._uniq_res if r not in prekeys]
        if missing:
            raise ValueError(f"prekeys missing resolutions {missing}")
        keys = tuple((self._put(prekeys[r][0]), self._put(prekeys[r][1]))
                     for r in self._uniq_res)
        return self._step_pre, (tuple(self.states), keys) + lanes

    def view(self, res: int, window_s: int) -> "PairView":
        return PairView(self, self.pairs.index((res, window_s)))

    def grow(self, new_capacity: int) -> None:
        """Resize EVERY pair's slab (pairs share one capacity so the fused
        step keeps uniform shapes).  The next step retraces on the new
        shape; sortedness is preserved (EMPTY pads the tail).  Emit
        capacity grows with the slab (a larger slab means a batch can
        touch more groups than the old min(batch, cap) bound) — the
        in-place params update is read at that retrace."""
        from heatmap_tpu.engine.state import resize_state

        self.states = [
            TileState(*[self._put(leaf)
                        for leaf in resize_state(st, new_capacity)])
            for st in self.states
        ]
        self.capacity_per_shard = new_capacity
        new_emit = min(self.batch_size, new_capacity)
        self.params[:] = [
            p._replace(emit_capacity=max(p.emit_capacity, new_emit))
            for p in self.params
        ]


class PairView:
    """Checkpoint adapter for one pair of a MultiAggregator (SingleAggregator
    snapshot/restore API)."""

    n_shards = 1

    def __init__(self, multi: MultiAggregator, idx: int):
        self._multi = multi
        self._idx = idx

    @property
    def capacity_per_shard(self) -> int:  # tracks growth
        return self._multi.capacity_per_shard

    @property
    def state(self) -> TileState:
        return self._multi.states[self._idx]

    def snapshot(self) -> TileState:
        from heatmap_tpu.engine.state import to_host

        return to_host(self._multi.states[self._idx])

    def device_snapshot(self) -> TileState:
        """Fresh-buffer on-device copy (see SingleAggregator)."""
        from heatmap_tpu.engine.state import device_copy

        return device_copy(self._multi.states[self._idx])

    @staticmethod
    def to_host(snap: TileState) -> TileState:
        from heatmap_tpu.engine.state import to_host

        return to_host(snap)

    def restore(self, st: TileState) -> None:
        cur = self._multi.states[self._idx]
        want = (cur.key_hi.shape, cur.hist.shape)
        got = (st.key_hi.shape, st.hist.shape)
        if want != got:
            raise ValueError(f"state shape {got} != configured {want}")
        self._multi.states[self._idx] = TileState(
            *[self._multi._put(leaf) for leaf in st])


class MultiStats(NamedTuple):
    """Host-side StepStats (field order MUST match engine.step.StepStats —
    the rider is decoded positionally, see step.ride_stats)."""

    n_valid: int
    n_late: int
    n_evicted: int
    n_active: int
    state_overflow: int
    batch_max_ts: int


def stats_from_packed(packed_pair: np.ndarray) -> MultiStats:
    """Decode the StepStats ridden in a pair's packed head row (written by
    MultiAggregator's step; avoids a separate stats transfer)."""
    return read_stats_rider(packed_pair, MultiStats)
