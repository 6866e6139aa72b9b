"""shard_map sharded aggregation step (the ICI shuffle).

Dataflow per device (= one shard of the mesh axis "shards"):

    local batch shard (N/D events)
      → H3 snap once per unique resolution (hexgrid.device)
      → per (res, window) pair: owner = mix32(key) % D   # key partitioning
      → bucket into (D, cap) padded lanes  # stable-sort by owner + rank
      → ONE lax.all_to_all over "shards" carrying EVERY pair's lanes
        (the ICI exchange ≈ Spark shuffle; fewer, larger messages)
      → engine.merge_batch per pair into its local state slab
        (keys owned exclusively)

All configured (resolution, window) pairs run inside one jitted program —
one dispatch per batch — and the per-pair packed emits come back stacked,
so a host reads its entire step output (emits + psum'd stats ridden in
head rows) in ONE addressable transfer.

Bucket lanes are fixed-capacity (static shapes); events beyond a lane's
capacity are dropped and counted in ``ShardStats.bucket_dropped`` — size
``bucket_factor`` for the expected worst-case skew.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from heatmap_tpu.parallel import multihost
from heatmap_tpu.engine.state import (
    EMPTY_KEY_HI,
    EMPTY_KEY_LO,
    EMPTY_WS,
    TileState,
    donate_state_argnums,
    init_state,
)
from heatmap_tpu.engine.step import (
    AggParams,
    BatchEmit,
    FUTURE_WINDOWS,
    merge_batch,
    pack_emit,
    read_stats_rider,
    ride_stats,
    snap_and_window,
    unpack_emit,
    window_start,
)

AXIS = "shards"


class ShardStats(NamedTuple):
    n_valid: jnp.ndarray
    n_late: jnp.ndarray
    n_evicted: jnp.ndarray
    n_active: jnp.ndarray
    state_overflow: jnp.ndarray
    batch_max_ts: jnp.ndarray
    bucket_dropped: jnp.ndarray


class ShardStatsHost(NamedTuple):
    """ShardStats decoded from a packed head row (host ints; field order
    MUST match ShardStats — the rider is decoded positionally, see
    engine.step.ride_stats)."""

    n_valid: int
    n_late: int
    n_evicted: int
    n_active: int
    state_overflow: int
    batch_max_ts: int
    bucket_dropped: int


def unpack_emit_shards(rows: np.ndarray, emit_capacity: int,
                       n_pairs: int | None = None):
    """Decode one host's packed emit rows from ShardedAggregator.step_packed.

    ``rows`` is (S * n_pairs * (E+1), 13) — per local shard, the P pairs'
    blocks in pair order.  With ``n_pairs`` given (any value, even 1),
    returns a list of (emit dict, ShardStatsHost), one per pair; with it
    omitted, the historical single-pair signature: one bare
    (emit dict, ShardStatsHost) tuple.

    Keys are owned exclusively per shard, so concatenating blocks' rows
    never duplicates a group; the stats head fields are psum'd (identical
    in every shard's block for a given pair), so the first shard's copy is
    authoritative.
    """
    single = n_pairs is None
    if single:
        n_pairs = 1
    blk = emit_capacity + 1
    n_shards = rows.shape[0] // (blk * n_pairs)
    blocks = rows.reshape(n_shards, n_pairs, blk, rows.shape[1])
    out = []
    for p in range(n_pairs):
        es = [unpack_emit(blocks[s, p]) for s in range(n_shards)]
        e = {k: np.concatenate([x[k] for x in es]) for k in
             ("key_hi", "key_lo", "key_ws", "count", "sum_speed",
              "sum_speed2", "sum_lat", "sum_lon", "valid", "p95",
              "anchor_speed", "anchor_lat", "anchor_lon")}
        e["n_emitted"] = sum(x["n_emitted"] for x in es)
        e["overflowed"] = any(x["overflowed"] for x in es)
        out.append((e, read_stats_rider(blocks[0, p], ShardStatsHost)))
    return out[0] if single else out


def packed_pair_bodies(rows: np.ndarray, emit_capacity: int, n_pairs: int):
    """Split one host's packed emit rows into per-pair BODY matrices for
    the packed sink fast path (sink.Store.upsert_tiles_packed): returns
    [(body (S*E, 13) uint32, ShardStatsHost)] in pair order.  The head
    rows are dropped after their stats are read; keys are shard-disjoint
    so concatenating shard blocks never duplicates a group."""
    blk = emit_capacity + 1
    n_shards = rows.shape[0] // (blk * n_pairs)
    blocks = rows.reshape(n_shards, n_pairs, blk, rows.shape[1])
    out = []
    for p in range(n_pairs):
        body = np.ascontiguousarray(
            blocks[:, p, 1:, :].reshape(-1, rows.shape[1]))
        out.append((body, read_stats_rider(blocks[0, p], ShardStatsHost)))
    return out


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D shards mesh.  Devices are ordered **process-major** (a no-op on
    one host): consecutive shard indices stay on the same host first, so
    the packed all_to_all's heaviest lanes ride intra-host ICI before
    crossing DCN (multi-host deployment: parallel.multihost)."""
    if devices is None:
        devices = jax.devices()
    devices = sorted(devices, key=lambda d: (d.process_index, d.id))
    if n_devices:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (AXIS,))


def _mix32(hi, lo, ws):
    """Cheap avalanche mix of the composite key into uint32 (owner hash)."""
    h = hi ^ (lo * jnp.uint32(2654435761))
    h = h ^ (ws.astype(jnp.uint32) * jnp.uint32(0x9E3779B1))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    return h


_LANE_NAMES = ("hi", "lat_deg", "lo", "lon_deg", "speed", "ts", "ws",
               "valid")


def _lane_init(name: str, n: int):
    if name in ("hi", "lo"):
        return jnp.full((n,), EMPTY_KEY_HI, jnp.uint32)
    if name == "ws":
        return jnp.full((n,), EMPTY_WS, jnp.int32)
    if name == "valid":
        return jnp.zeros((n,), bool)
    if name == "ts":
        return jnp.zeros((n,), jnp.int32)
    return jnp.zeros((n,), jnp.float32)


def _bucket_lanes(fields, dest, valid, n_shards: int, cap: int):
    """Route per-event field arrays into (n_shards*cap,) owner-ordered
    lanes (stable-sort by owner, rank within owner).  Returns the lanes
    stacked as one (n_shards, cap, L) uint32 block ready for the exchange,
    plus the dropped-events count.  Lane order is ``_LANE_NAMES``."""
    n = dest.shape[0]
    # invalid events must not consume lane capacity: sink them to a
    # nonexistent destination group before ranking
    dest = jnp.where(valid, dest, jnp.int32(n_shards))
    order = jnp.argsort(dest, stable=True)
    dest_s = dest[order]
    # rank of each event within its destination group
    pos = jnp.arange(n, dtype=jnp.int32)
    is_first = jnp.concatenate(
        [jnp.ones((1,), bool), dest_s[1:] != dest_s[:-1]]
    )
    group_start = jax.lax.cummax(jnp.where(is_first, pos, 0))
    rank = pos - group_start
    slot = dest_s * cap + rank
    ok = valid[order] & (rank < cap) & (dest_s < n_shards)
    slot = jnp.where(ok, slot, n_shards * cap)  # OOB → dropped

    out = []
    for name in _LANE_NAMES:
        if name == "valid":
            out.append(jnp.zeros((n_shards * cap,), bool)
                       .at[slot].set(ok, mode="drop"))
        else:
            out.append(_lane_init(name, n_shards * cap)
                       .at[slot].set(fields[name][order], mode="drop"))
    n_dropped = jnp.sum((valid[order] & (rank >= cap)).astype(jnp.int32))

    packed = jnp.stack(
        [a.astype(jnp.uint32) if a.dtype == jnp.bool_
         else jax.lax.bitcast_convert_type(a, jnp.uint32)
         for a in out],
        axis=-1,
    ).reshape(n_shards, cap, len(out))
    return packed, n_dropped


def _decode_lanes(packed):
    """(n_shards*cap, L) uint32 → dict of typed lanes (_LANE_NAMES)."""
    n = packed.shape[0]
    recv = {}
    for i, name in enumerate(_LANE_NAMES):
        lane = packed[:, i]
        want = _lane_init(name, n).dtype
        if want == jnp.bool_:
            recv[name] = lane != 0
        else:
            recv[name] = jax.lax.bitcast_convert_type(lane, want)
    return recv


def _sharded_step_body(params_list: tuple[AggParams, ...], n_shards: int,
                       cap: int, states, lat, lng, speed, ts, valid, cutoff,
                       prekeys=None):
    """Per-device body run under shard_map: every pair in one program,
    every pair's exchange in ONE all_to_all.

    ``prekeys``: optional dict res -> (hi, lo) of host-precomputed cell
    keys for this shard's rows (HEATMAP_H3_IMPL=native — see
    engine.multi.fused_fold); masking keeps the invalid-row contract
    identical to snap_and_window's."""
    lat_deg = lat * jnp.float32(180.0 / np.pi)
    lon_deg = lng * jnp.float32(180.0 / np.pi)
    # one snap per unique resolution, shared across its windows
    snapped = {}
    for p in params_list:
        if p.res not in snapped:
            if prekeys is not None and p.res in prekeys:
                hi = jnp.where(valid, prekeys[p.res][0],
                               jnp.uint32(EMPTY_KEY_HI))
                lo = jnp.where(valid, prekeys[p.res][1],
                               jnp.uint32(EMPTY_KEY_LO))
            else:
                hi, lo, _ = snap_and_window(lat, lng, ts, valid, p)
            snapped[p.res] = (hi, lo)

    blocks, n_lates, n_drops = [], [], []
    for p in params_list:
        hi, lo = snapped[p.res]
        ws = window_start(ts, valid, p.window_s)
        # drop late/future events BEFORE the exchange so a replay backlog
        # neither wastes ICI bandwidth nor steals bucket-lane capacity
        # (future drop mirrors engine.step — see FUTURE_WINDOWS there)
        late = valid & (ws != EMPTY_WS) & (ws + p.window_s <= cutoff)
        has_wm = cutoff > jnp.int32(-(2**31))
        late = late | (
            valid & has_wm & (ws != EMPTY_WS)
            & ((ws - cutoff) >= FUTURE_WINDOWS * p.window_s)
        )
        valid_p = valid & ~late
        n_lates.append(jnp.sum(late.astype(jnp.int32)))
        dest = (_mix32(hi, lo, ws) % jnp.uint32(n_shards)).astype(jnp.int32)
        fields = {
            "hi": hi, "lo": lo, "ws": ws, "speed": speed,
            "lat_deg": lat_deg, "lon_deg": lon_deg, "ts": ts,
        }
        block, n_dropped = _bucket_lanes(fields, dest, valid_p, n_shards, cap)
        blocks.append(block)
        n_drops.append(n_dropped)

    # ONE ICI collective for all pairs: (P, D, cap, L), peer dim = axis 1
    packed = jnp.stack(blocks)
    packed = jax.lax.all_to_all(packed, AXIS, split_axis=1, concat_axis=1)

    new_states, emits, packs, stats_list = [], [], [], []
    for i, (p, st) in enumerate(zip(params_list, states)):
        recv = _decode_lanes(packed[i].reshape(n_shards * cap, -1))
        new_state, emit, s = merge_batch(
            st, recv["hi"], recv["lo"], recv["ws"], recv["speed"],
            recv["lat_deg"], recv["lon_deg"], recv["ts"], recv["valid"],
            cutoff, p,
        )
        stats = ShardStats(
            n_valid=jax.lax.psum(s.n_valid, AXIS),
            n_late=jax.lax.psum(n_lates[i] + s.n_late, AXIS),
            n_evicted=jax.lax.psum(s.n_evicted, AXIS),
            n_active=jax.lax.psum(s.n_active, AXIS),
            state_overflow=jax.lax.psum(s.state_overflow, AXIS),
            batch_max_ts=jax.lax.pmax(s.batch_max_ts, AXIS),
            bucket_dropped=jax.lax.psum(n_drops[i], AXIS),
        )
        # this pair's packed (E+1, 13) emit block with the (replicated,
        # psum'd) stats ridden in its head row — the host reads the WHOLE
        # step's output in one addressable pull (engine.step.ride_stats)
        packs.append(ride_stats(pack_emit(emit, p.speed_hist_max), stats))
        # per-shard scalars need a rank-1 axis to ride a sharded out_spec
        emits.append(emit._replace(
            n_emitted=emit.n_emitted[None], overflowed=emit.overflowed[None]
        ))
        new_states.append(new_state)
        stats_list.append(stats)
    packed_out = jnp.concatenate(packs, axis=0)  # (P*(E+1), 13) per shard
    return tuple(new_states), tuple(emits), packed_out, tuple(stats_list)


def exchange_lane_capacity(n_local: int, n_shards: int,
                           bucket_factor: float = 2.0,
                           z: float = 4.0) -> int:
    """Rows per (src, dst) exchange lane — the ONE sizing rule shared by
    production (`ShardedAggregator.__init__`) and the driver's
    `dryrun_multichip`, so the dryrun proves conservation under exactly
    the headroom production ships with.

    Per-lane load is ~Binomial(n_local, 1/n_shards): mean
    m = n_local/n_shards, std < sqrt(m).  ``bucket_factor`` scales the
    mean for systematic key skew (2.0 = one owner draws 2x the uniform
    share); the ``z*sqrt(bucket_factor*m) + z^2`` term absorbs
    multinomial sampling variance, which dominates at small per-shard
    batches (the regime where a bare 2x cap was observed to drop a
    handful of events at 256 ev/shard x 16 shards) and vanishes
    relative to the mean at production batches.
    """
    m = bucket_factor * n_local / n_shards
    return max(1, int(math.ceil(m + z * math.sqrt(m) + z * z)))


def _step_specs(n_pairs: int):
    """shard_map partition specs of the sharded step: (per-pair state
    specs, step input specs, one pair's emit specs, one pair's stats
    specs)."""
    spec1 = P(AXIS)
    spec2 = P(AXIS, None)
    state_specs = TileState(
        key_hi=spec1, key_lo=spec1, key_ws=spec1, count=spec1,
        sum_speed=spec1, sum_speed2=spec1, sum_lat=spec1, sum_lon=spec1,
        hist=spec2, anchor_speed=spec1, anchor_lat=spec1,
        anchor_lon=spec1, comp=spec2,
    )
    emit_specs = BatchEmit(
        key_hi=spec1, key_lo=spec1, key_ws=spec1, count=spec1,
        sum_speed=spec1, sum_speed2=spec1, sum_lat=spec1, sum_lon=spec1,
        anchor_speed=spec1, anchor_lat=spec1, anchor_lon=spec1,
        hist=spec2, valid=spec1, n_emitted=P(AXIS), overflowed=P(AXIS),
    )
    stats_specs = ShardStats(*([P()] * 7))
    states_specs = tuple([state_specs] * n_pairs)
    in_specs = (states_specs, spec1, spec1, spec1, spec1, spec1, P())
    return states_specs, in_specs, emit_specs, stats_specs


def packed_step(mesh: Mesh, params_list, bucket_cap: int):
    """The streaming hot path's sharded program: fold one global batch
    into every pair's slab and return the stacked packed emits.
    ``params_list`` is read when the program traces (a list that
    ``ShardedAggregator.grow`` mutates in place)."""
    n_pairs = len(params_list)
    states_specs, in_specs, _, _ = _step_specs(n_pairs)
    body = functools.partial(_sharded_step_body, params_list,
                             mesh.devices.size, bucket_cap)

    def body_packed(*a):
        states, emits, packed, stats = body(*a)
        return states, packed

    return jax.jit(
        jax.shard_map(body_packed, mesh=mesh, in_specs=in_specs,
                      out_specs=(states_specs, P(AXIS, None))),
        donate_argnums=donate_state_argnums(),
    )


class ShardedAggregator:
    """Host-facing wrapper owning the sharded device state.

    ``params`` is one AggParams or a sequence of them — every configured
    (resolution, window) pair folds inside the same program.  Batches are
    fed as global (batch_size,) arrays, sharded over the mesh's
    ``shards`` axis.  ``bucket_factor`` oversizes the exchange lanes
    relative to the uniform share (2.0 = tolerate 2x skew toward one
    shard).
    """

    def __init__(
        self,
        mesh: Mesh,
        params: AggParams | Sequence[AggParams],
        capacity_per_shard: int,
        batch_size: int,
        hist_bins: int = 0,
        bucket_factor: float = 2.0,
    ):
        self.mesh = mesh
        plist = ([params] if isinstance(params, AggParams) else list(params))
        if len({(p.res, p.window_s) for p in plist}) != len(plist):
            raise ValueError(f"duplicate (res, window) pairs: "
                             f"{[(p.res, p.window_s) for p in plist]}")
        if len({p.emit_capacity for p in plist}) != 1:
            raise ValueError("all pairs must share emit_capacity "
                             "(packed blocks stack uniformly)")
        # a LIST on purpose: grow() mutates it in place and the jitted
        # bodies re-read it when the new state shapes force a retrace
        self.params_list = list(plist)
        self.params = self.params_list[0]
        self.pairs = [(p.res, p.window_s) for p in self.params_list]
        self.n_shards = mesh.devices.size
        if batch_size % self.n_shards:
            raise ValueError(
                f"batch_size {batch_size} not divisible by {self.n_shards} shards"
            )
        self.batch_size = batch_size
        n_local = batch_size // self.n_shards
        self.bucket_cap = exchange_lane_capacity(
            n_local, self.n_shards, bucket_factor)
        self.capacity_per_shard = capacity_per_shard

        shard1 = NamedSharding(mesh, P(AXIS))
        shard2 = NamedSharding(mesh, P(AXIS, None))
        self._state_shardings = (shard1, shard2)
        self.states: list[TileState] = [
            TileState(*[
                jax.device_put(leaf, shard2 if leaf.ndim == 2 else shard1)
                for leaf in init_state(self.n_shards * capacity_per_shard,
                                       hist_bins)
            ])
            for _ in self.params_list
        ]

        body = functools.partial(
            _sharded_step_body, self.params_list, self.n_shards,
            self.bucket_cap,
        )
        n_pairs = len(self.params_list)
        states_specs, in_specs, emit_specs, stats_specs = _step_specs(
            n_pairs)
        spec1, spec2 = P(AXIS), P(AXIS, None)
        # two lazily-compiled variants of the SAME body, each returning
        # only what its caller consumes (jit cannot DCE returned outputs;
        # the streaming hot path must not materialize the emit pytrees)

        def body_full(*a):
            states, emits, packed, stats = body(*a)
            return states, emits, stats

        self._step = jax.jit(
            jax.shard_map(
                body_full, mesh=mesh, in_specs=in_specs,
                out_specs=(states_specs, tuple([emit_specs] * n_pairs),
                           tuple([stats_specs] * n_pairs)),
            ),
            donate_argnums=donate_state_argnums(),  # fold slabs in place
        )
        self._step_packed = packed_step(mesh, self.params_list,
                                        self.bucket_cap)

        # prekeys variant: host-precomputed (hi, lo) planes per unique
        # resolution ride as extra sharded args (HEATMAP_H3_IMPL=native)
        uniq_res = list(dict.fromkeys(p.res for p in self.params_list))
        self._uniq_res = uniq_res

        def body_packed_pre(states, lat, lng, speed, ts, valid, cutoff,
                            *keys):
            prekeys = {r: (keys[2 * i], keys[2 * i + 1])
                       for i, r in enumerate(uniq_res)}
            states, emits, packed, stats = body(
                states, lat, lng, speed, ts, valid, cutoff,
                prekeys=prekeys)
            return states, packed

        in_specs_pre = in_specs + tuple([spec1] * (2 * len(uniq_res)))
        self._step_packed_pre = jax.jit(
            jax.shard_map(body_packed_pre, mesh=mesh, in_specs=in_specs_pre,
                          out_specs=(states_specs, spec2)),
            donate_argnums=donate_state_argnums(),
        )
        self._in_sharding = shard1
        self.n_steps = 0

    def instrument(self, wrap) -> None:
        """Wrap the jitted entry points with a compile tracker
        (obs.runtimeinfo.CompileTracker.wrap) — same contract as
        MultiAggregator.instrument; the sharded program's retraces
        (slab growth, policy flips) are the expensive ones, so they
        must be the visible ones."""
        self._step = wrap("sharded_step", self._step)
        self._step_packed = wrap("sharded_step_packed", self._step_packed)
        self._step_packed_pre = wrap("sharded_step_packed_pre",
                                     self._step_packed_pre)

    # --- compat aliases (single-pair callers: tests, dryrun) ---------------

    @property
    def state(self) -> TileState:
        return self.states[0]

    def step(self, lat_rad, lng_rad, speed, ts, valid, watermark_cutoff):
        """Fold one global batch; returns (BatchEmit, ShardStats) on device
        — pair 0's view (use step_packed for multi-pair configurations).

        Per-shard scalar emit fields (n_emitted/overflowed) come back with a
        leading (n_shards,) axis.  Multi-host: each process passes its LOCAL
        slice (batch_size / process_count events, see parallel.multihost)
        and reads back only its addressable emit shards (emit_to_host).
        """
        states, emits, stats = self._step(
            tuple(self.states), *self._puts(lat_rad, lng_rad, speed, ts,
                                            valid),
            jnp.int32(watermark_cutoff),
        )
        self.states = list(states)
        return emits[0], stats[0]

    def step_packed(self, lat_rad, lng_rad, speed, ts, valid,
                    watermark_cutoff, prekeys=None):
        """Single-transfer variant: folds the batch into every pair's
        state and returns the global packed emit array,
        (n_shards * n_pairs * (E+1), 13) uint32 sharded over the mesh —
        per shard, one (E+1, 13) block per pair with the replicated stats
        in its head row.  Pull this host's rows with
        ``multihost.addressable_rows`` and decode with
        ``unpack_emit_shards(rows, E, n_pairs)`` (the streaming runtime's
        hot path).

        ``prekeys``: optional dict res -> (hi, lo) numpy arrays of
        host-precomputed cell keys for THIS host's local rows (same
        local-slice convention as lat_rad); required for EVERY unique
        resolution when given (a partial dict raises)."""
        if prekeys is not None:
            missing = [r for r in self._uniq_res if r not in prekeys]
            if missing:
                raise ValueError(f"prekeys missing resolutions {missing}")
            key_arrays = [a for r in self._uniq_res for a in prekeys[r]]
            states, packed = self._step_packed_pre(
                tuple(self.states), *self._puts(lat_rad, lng_rad, speed,
                                                ts, valid),
                jnp.int32(watermark_cutoff),
                *self._puts(*key_arrays),
            )
        else:
            states, packed = self._step_packed(
                tuple(self.states), *self._puts(lat_rad, lng_rad, speed,
                                                ts, valid),
                jnp.int32(watermark_cutoff),
            )
        self.states = list(states)
        self.n_steps += 1
        return packed

    def _puts(self, *arrays):
        return tuple(multihost.put_global(self._in_sharding, np.asarray(a))
                     for a in arrays)

    @property
    def local_batch_size(self) -> int:
        """Events THIS process feeds per step (= batch_size on one host)."""
        return multihost.global_batch_to_local(self.batch_size)

    def emit_to_host(self, emit: BatchEmit) -> dict:
        """Emit leaves as host numpy, restricted to this process's shards
        (each host sinks only the keys it owns; cross-host device_get on a
        sharded global array is an error)."""
        rows = {name: multihost.addressable_rows(getattr(emit, name))
                for name in ("key_hi", "key_lo", "key_ws", "count",
                             "sum_speed", "sum_speed2", "sum_lat", "sum_lon",
                             "anchor_speed", "anchor_lat", "anchor_lon",
                             "valid")}
        hist = multihost.addressable_rows(emit.hist)
        rows["hist"] = hist if hist.shape[1] else None
        return rows

    # --- checkpoint interface (runtime._checkpoint / _maybe_resume) --------

    def view(self, res: int, window_s: int) -> "ShardedPairView":
        return ShardedPairView(self, self.pairs.index((res, window_s)))

    @property
    def local_shards(self) -> int:
        """Shard blocks held by THIS process (== addressable devices in a
        multi-host mesh; all shards on a single host)."""
        n_local = len(self.states[0].key_hi.sharding.addressable_devices)
        return n_local if jax.process_count() > 1 else self.n_shards

    def grow(self, new_capacity: int) -> None:
        """Resize every pair's sharded slab to ``new_capacity`` rows per
        shard (host roundtrip + retrace on the next step; growth is rare
        and geometric).  EMPTY pads each shard block's tail, preserving
        per-shard sortedness.  In a multi-host mesh every process must
        call this at the same step (the runtime's growth decision is
        derived from replicated stats, so it is)."""
        from heatmap_tpu.engine.state import resize_state

        shards = self.local_shards
        snaps = [self.snapshot(i) for i in range(len(self.states))]
        self.capacity_per_shard = new_capacity
        for i, snap in enumerate(snaps):
            self.restore(resize_state(snap, new_capacity, shards), i)
        # emit capacity grows with the slab: a batch can now touch more
        # groups per shard than the old min(batch, cap) bound.  In-place
        # so the partial-bound list the jitted bodies read stays the same
        # object; the changed state shapes force the retrace that reads it.
        new_emit = min(self.batch_size, new_capacity)
        self.params_list[:] = [
            p._replace(emit_capacity=max(p.emit_capacity, new_emit))
            for p in self.params_list
        ]
        self.params = self.params_list[0]

    def snapshot(self, idx: int = 0) -> TileState:
        """THIS process's rows of one pair's sharded state (per-host
        checkpoint — hosts restore their own shards; see stream.checkpoint
        docstring).  Synchronous: pulls the live slabs, no device copy."""
        return self.snapshot_to_host(self.states[idx])

    def device_snapshot(self, idx: int = 0) -> TileState:
        """Fresh-buffer on-device copy, sharding preserved (the step
        programs donate the state slabs, so references don't survive)."""
        from heatmap_tpu.engine.state import device_copy

        return device_copy(self.states[idx])

    @staticmethod
    def snapshot_to_host(snap: TileState) -> TileState:
        return TileState(*[multihost.addressable_rows(leaf)
                           for leaf in snap])

    def restore(self, st: TileState, idx: int = 0) -> None:
        shard1, shard2 = self._state_shardings
        cur = self.states[idx]
        n_local = cur.key_hi.sharding.addressable_devices
        want_rows = (self.capacity_per_shard * len(n_local)
                     if jax.process_count() > 1
                     else self.n_shards * self.capacity_per_shard)
        got = (st.key_hi.shape, st.hist.shape)
        want = ((want_rows,), (want_rows, cur.hist.shape[1]))
        if got != want:
            raise ValueError(f"state shape {got} != configured {want}")
        self.states[idx] = TileState(*[
            multihost.put_global(shard2 if leaf.ndim == 2 else shard1,
                                 np.asarray(leaf))
            for leaf in st
        ])


class PartitionedAggregator:
    """Shard-per-device mesh aggregation, ``partitioned`` mode — the
    collective-free sibling of :class:`ShardedAggregator`.

    The ICI-shuffle path above exists because a position-sharded feed
    scatters every key across devices, so the device program must route
    events to their owners (``_bucket_lanes`` + one ``all_to_all``).
    When the FEED pre-partitions each batch by H3 parent cell
    (stream/shardmap.MeshPartition — the same stable cell→owner
    assignment the PR 7 process fleet ships on), that shuffle is dead
    weight: every device already holds exactly its own cell space.  This
    class therefore runs one fused single-device program
    (engine.multi.MultiAggregator) per mesh device, inputs committed to
    that device (the watermark cutoff too) — no collectives, no
    lockstep, no shared dispatch stream, so the async per-device folds
    overlap; each device's packed emits stay resident on ITS chip, which
    lets the runtime keep one independently-flushed EmitRing and one
    independently-governed BatchGovernor per shard (the mesh-resident
    fast path).

    Cell spaces are disjoint by the partitioner, so per-device emits
    merge upsert-only at the view, exactly like the process fleet.
    Single-process meshes only: multi-host runs keep the lockstep
    shuffle path (their accounting must advance identically on every
    host)."""

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
                "partitioned mesh mode is single-process only; "
                "multi-host meshes keep the ICI-shuffle path")
        plist = ([params] if isinstance(params, AggParams) else list(params))
        if len({(p.res, p.window_s) for p in plist}) != len(plist):
            raise ValueError(f"duplicate (res, window) pairs: "
                             f"{[(p.res, p.window_s) for p in plist]}")
        if len({p.emit_capacity for p in plist}) != 1:
            raise ValueError("all pairs must share emit_capacity "
                             "(packed blocks stack uniformly)")
        from heatmap_tpu.engine.multi import MultiAggregator

        self.mesh = mesh
        self.devices = sorted(mesh.devices.ravel().tolist(),
                              key=lambda d: (d.process_index, d.id))
        self.n_shards = len(self.devices)
        # a LIST on purpose, like ShardedAggregator: grow() mutates it
        # in place so callers holding a reference read updated
        # emit capacities
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

    @property
    def local_shards(self) -> int:
        return self.n_shards

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
        # n_steps counts BATCHES like the sibling aggregators, not
        # chunks — the runtime bumps it once per dispatched batch
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

    # --- checkpoint interface (same shard-block layout as the shuffle
    # path: one concatenated (n_shards * cap, …) slab per pair, split
    # back per device on restore; stream.checkpoint meta records
    # mesh_mode so the two layouts can never restore into each other —
    # the key OWNERSHIP differs, and a cross-mode restore would
    # silently duplicate groups across devices) -------------------------

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
    """Checkpoint adapter for one pair of a PartitionedAggregator (same
    surface as ShardedPairView — the runtime treats both mesh modes
    identically at checkpoint time)."""

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


class ShardedPairView:
    """Checkpoint adapter for one pair of a multi-pair ShardedAggregator
    (same snapshot/restore surface as engine.multi.PairView)."""

    def __init__(self, agg: ShardedAggregator, idx: int):
        self._agg = agg
        self._idx = idx

    @property
    def capacity_per_shard(self) -> int:  # tracks growth
        return self._agg.capacity_per_shard

    @property
    def state(self) -> TileState:
        return self._agg.states[self._idx]

    def snapshot(self) -> TileState:
        return self._agg.snapshot(self._idx)

    def device_snapshot(self) -> TileState:
        return self._agg.device_snapshot(self._idx)

    @staticmethod
    def to_host(snap: TileState) -> TileState:
        return ShardedAggregator.snapshot_to_host(snap)

    @property
    def n_shards(self) -> int:
        return self._agg.local_shards

    def restore(self, st: TileState) -> None:
        self._agg.restore(st, self._idx)
