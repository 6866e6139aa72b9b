"""Single-device, single-pair aggregator.

Used when one chip is enough (the bench's single-chip runs); the state
slab lives on the default device.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp


from heatmap_tpu.engine.state import (TileState, donate_state_argnums,
                                      init_state)
from heatmap_tpu.engine.step import (AggParams, aggregate_batch, pack_emit,
                                     ride_stats)


class SingleAggregator:
    n_shards = 1

    def __init__(self, params: AggParams, capacity: int, batch_size: int,
                 hist_bins: int = 0):
        self.params = params
        self.capacity_per_shard = capacity
        self.batch_size = batch_size
        self.state: TileState = init_state(capacity, hist_bins)

        def _step(state, lat, lng, speed, ts, valid, cutoff):
            return aggregate_batch(state, lat, lng, speed, ts, valid, cutoff,
                                   self.params)

        self._step = jax.jit(_step,
                     donate_argnums=donate_state_argnums())

        def _step_packed(state, lat, lng, speed, ts, valid, cutoff):
            state, emit, stats = aggregate_batch(
                state, lat, lng, speed, ts, valid, cutoff, self.params
            )
            return state, pack_emit(emit, self.params.speed_hist_max), stats

        self._step_packed = jax.jit(
            _step_packed, donate_argnums=donate_state_argnums())

        def _step_ride(state, lat, lng, speed, ts, valid, cutoff):
            state, emit, stats = aggregate_batch(
                state, lat, lng, speed, ts, valid, cutoff, self.params
            )
            return state, ride_stats(
                pack_emit(emit, self.params.speed_hist_max), stats)

        self._step_ride = jax.jit(
            _step_ride, donate_argnums=donate_state_argnums())

    def step(self, lat_rad, lng_rad, speed, ts, valid, watermark_cutoff):
        self.state, emit, stats = self._step(
            self.state,
            jnp.asarray(lat_rad), jnp.asarray(lng_rad), jnp.asarray(speed),
            jnp.asarray(ts), jnp.asarray(valid),
            jnp.int32(watermark_cutoff),
        )
        # align emit scalar shapes with the sharded aggregator's (D,) form
        emit = emit._replace(n_emitted=emit.n_emitted[None],
                             overflowed=emit.overflowed[None])
        return emit, stats

    def step_packed(self, lat_rad, lng_rad, speed, ts, valid, watermark_cutoff):
        """Single-transfer variant: returns (packed_emit_device, stats_device).

        The caller pulls the packed matrix with one device_get (see
        engine.step.pack_emit) — one transfer per batch; the bench hot
        loop uses it."""
        self.state, packed, stats = self._step_packed(
            self.state,
            jnp.asarray(lat_rad), jnp.asarray(lng_rad), jnp.asarray(speed),
            jnp.asarray(ts), jnp.asarray(valid),
            jnp.int32(watermark_cutoff),
        )
        return packed, stats

    def step_packed_ride(self, lat_rad, lng_rad, speed, ts, valid,
                         watermark_cutoff):
        """Like step_packed, but the StepStats ride the packed head row
        (engine.step.ride_stats) so the WHOLE batch output is one device
        array — the shape engine.step.EmitRing accumulates and
        ``stats_from_packed`` decodes (parity with MultiAggregator).
        Returns the (E+1, 13) packed matrix on device."""
        self.state, packed = self._step_ride(
            self.state,
            jnp.asarray(lat_rad), jnp.asarray(lng_rad), jnp.asarray(speed),
            jnp.asarray(ts), jnp.asarray(valid),
            jnp.int32(watermark_cutoff),
        )
        return packed

    def emit_to_host(self, emit) -> dict:
        """Emit leaves as host numpy."""
        import numpy as np

        e = jax.device_get(emit)
        return {
            "key_hi": e.key_hi, "key_lo": e.key_lo, "key_ws": e.key_ws,
            "count": e.count, "sum_speed": e.sum_speed,
            "sum_speed2": e.sum_speed2, "sum_lat": e.sum_lat,
            "sum_lon": e.sum_lon, "anchor_speed": e.anchor_speed,
            "anchor_lat": e.anchor_lat, "anchor_lon": e.anchor_lon,
            "valid": e.valid,
            "hist": np.asarray(e.hist) if e.hist.shape[1] else None,
        }

    # --- checkpoint interface (runtime._checkpoint / _maybe_resume) --------

    def snapshot(self) -> TileState:
        """Host-side copy of the state slab (synchronous; no device copy)."""
        from heatmap_tpu.engine.state import to_host

        return to_host(self.state)

    def device_snapshot(self) -> TileState:
        """On-device copy with fresh buffers (async dispatch) — safe to
        hold across later (buffer-donating) steps and pull off-thread."""
        from heatmap_tpu.engine.state import device_copy

        return device_copy(self.state)

    @staticmethod
    def to_host(snap: TileState) -> TileState:
        from heatmap_tpu.engine.state import to_host

        return to_host(snap)

    def restore(self, st: TileState) -> None:
        """Install a snapshot (shape-checked; raises on config mismatch)."""
        self._check_restore_shapes(st)
        self.state = TileState(*st)

    def _check_restore_shapes(self, st: TileState) -> None:
        want = (self.state.key_hi.shape, self.state.hist.shape)
        got = (st.key_hi.shape, st.hist.shape)
        if want != got:
            raise ValueError(f"state shape {got} != configured {want}")
