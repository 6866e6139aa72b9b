"""The per-micro-batch aggregation fold (device hot path).

Replaces one Spark micro-batch's parse → H3-UDF → shuffle → stateful-agg
chain (reference: heatmap_stream.py:88-133 and call stack SURVEY.md §3.3)
with a single jitted XLA program:

  1. ``snap_and_window`` — vectorized H3 snap (hexgrid.device) + tumbling
     window-start computation; invalid/late rows get the EMPTY key (the
     moral equivalent of the reference's null/bounds filters,
     heatmap_stream.py:96-108, and its 10-minute watermark drop, :107).
  2. ``merge_batch`` — merge-sort the batch into the compact sorted state
     slab: one ``lax.sort`` over (state ∥ batch) keys, segment-id
     derivation, then masked scatters to rebuild the slab.  Watermark
     eviction of closed windows is folded into the same sort (evicted rows
     are relabeled EMPTY so they sink to the tail and their slots recycle).

Everything is static-shape; the only dynamic quantities (number of distinct
keys, number of touched groups) are carried as masks and counters.

Degradation semantics: if the number of distinct live groups ever exceeds the
slab capacity, the groups with the highest composite keys are dropped —
including, possibly, pre-existing rows whose aggregates are then lost (their
next re-emit restarts the count).  ``StepStats.state_overflow`` counts the
dropped segments; the stream runtime surfaces any nonzero value as
per-batch ``state_overflow_groups`` / ``state_overflow_last_epoch``
counters at /metrics plus a rate-limited ERROR log, and with
``HEATMAP_ON_OVERFLOW=fail`` stops the run (capacity must be sized for
the active-cell cardinality, SURVEY.md §5.7).
"""

from __future__ import annotations

import functools
import os
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp

from heatmap_tpu.engine.state import (
    EMPTY_KEY_HI,
    EMPTY_KEY_LO,
    EMPTY_WS,
    TileState,
)
from heatmap_tpu.hexgrid import device as hexdev

I32_MIN = jnp.int32(-(2**31))

# Events this many windows ahead of an active watermark are dropped as
# clock-skew poison (and keep the live span well inside the 4096-window
# sort-key compression, see merge_batch).
FUTURE_WINDOWS = 2048

# Merge-fold routing (sort|rank|probe|auto).  ``MERGE_IMPL`` is the
# process-wide OVERRIDE slot (bench sweeps and tests assign it); when it
# is None — the normal state — HEATMAP_MERGE_IMPL is read at TRACE time
# by _resolve_merge_impl(), so a library user who sets the env var after
# importing this module is honored rather than silently served the
# import-time snapshot (round-3 advisor footgun).  All impls are
# bit-identical by construction and differential test, so programs
# traced before and after an env change still agree on results.
MERGE_IMPL: "str | None" = None


def _resolve_merge_impl() -> str:
    return (MERGE_IMPL if MERGE_IMPL is not None
            else os.environ.get("HEATMAP_MERGE_IMPL", "auto"))


# In-program snap routing (xla|pallas|auto) — same override-slot pattern
# as MERGE_IMPL: ``SNAP_IMPL`` wins when set (the stream runtime assigns
# it to pin the checkpointed impl across a resume — unlike the merge
# impls, the two snaps are NOT bit-identical on f32 cell-edge points, so
# a mid-stream flip would re-key a handful of groups); otherwise
# HEATMAP_H3_IMPL is read at trace time.
SNAP_IMPL: "str | None" = None

# Frozen bank verdict for the merge-impl ``auto`` path.  Sentinel
# ``_BANK_LIVE`` (the import-time default) means "consult
# hwbank.merge_winner() at trace time" — right for standalone
# merge_batch users (bench, tests, notebooks).  The stream runtime
# REPLACES it at init with a one-shot snapshot (a winner name or None),
# because (a) re-reading the bank at every trace would let a bank file
# rewritten MID-RUN flip the impl after the multihost startup collective
# validated a snapshot, compiling divergent lockstep programs across
# hosts, and
# (b) the getmtime stat has no place on the per-batch hot path.  The
# collective demotes the snapshot to None when hosts' banks disagree
# (every host then shares the static capacity-ratio rule; the merge
# impls are bit-identical, so results never depend on the choice).
_BANK_LIVE = object()
MERGE_BANK_PIN: "str | None | object" = _BANK_LIVE


def _resolve_snap_impl() -> str:
    return (SNAP_IMPL if SNAP_IMPL is not None
            else os.environ.get("HEATMAP_H3_IMPL", "auto"))


def resolve_snap_policy(ignore_pin: bool = False) -> str:
    """The in-program snap POLICY ("pallas" | "xla"): explicit
    env/override wins; "auto" consults the hardware bank.  Per-res
    eligibility (res <= 10, kernel lowers) still applies at trace time,
    so a policy of "pallas" deterministically degrades to the XLA snap
    for ineligible resolutions — recording the policy is enough to
    reproduce the exact per-res kernel choice across a resume.
    The stream runtime FREEZES this in ``SNAP_IMPL`` at init so a bank
    file appearing/changing mid-run cannot flip the kernel at a
    growth retrace or float the checkpointed name.  ``ignore_pin``
    resolves from env+bank even when the slot is set (the runtime uses
    it to detect a conflicting pin left by another runtime in the
    process — comparing against the slot-reading resolution would
    always agree with itself)."""
    impl = (os.environ.get("HEATMAP_H3_IMPL", "auto") if ignore_pin
            else _resolve_snap_impl())
    if impl == "auto":
        from heatmap_tpu import hwbank

        impl = hwbank.snap_winner() or "xla"
    # "native" is handled upstream via host prekeys; any other value
    # (incl. typos) keeps the safe default
    return impl if impl == "pallas" else "xla"


def inprogram_snap_name(res: int = 8) -> str:
    """The in-program snap ``_snap_impl`` would hand back right now,
    as a checkpointable name ("pallas" | "xla").  A Pallas policy on a
    backend where the kernel cannot run raises: it never quietly
    becomes the XLA snap."""
    if resolve_snap_policy() == "pallas" and res <= 10:
        from heatmap_tpu.hexgrid import pallas_kernel

        if not pallas_kernel.pallas_available():
            raise RuntimeError(
                "the Pallas H3 snap was requested but runs only on a TPU "
                f"(backend: {jax.default_backend()})")
        return "pallas"
    return "xla"

# _merge_probe tunables (resolved once at import — they only shape the
# probe impl's internal loop, not results, and tests patch the module
# constants directly): probe rounds before the per-batch sort fallback,
# and the unique-key budget divisor (budget = batch/PROBE_UNIQ_DIV,
# floor 256).
PROBE_ROUNDS = int(os.environ.get("HEATMAP_PROBE_ROUNDS", "16"))
PROBE_UNIQ_DIV = int(os.environ.get("HEATMAP_PROBE_UNIQ_DIV", "8"))

# Steady-state fast path (HEATMAP_FASTPATH=0 disables; module override
# slot for tests).  Read at trace time like the merge impl.
FASTPATH: "bool | None" = None


def _resolve_fastpath() -> bool:
    if FASTPATH is not None:
        return FASTPATH
    return os.environ.get("HEATMAP_FASTPATH", "1") != "0"


class AggParams(NamedTuple):
    """Static parameters of one (resolution, window) aggregation."""

    res: int                 # H3 resolution (heatmap_stream.py:26)
    window_s: int            # tumbling window seconds (heatmap_stream.py:29)
    emit_capacity: int       # max groups emitted per batch (update mode)
    speed_hist_max: float = 256.0   # km/h mapped onto the last hist bin


class BatchEmit(NamedTuple):
    """Update-mode output: current aggregates of every group touched by this
    batch (the reference's outputMode("update") contract,
    heatmap_stream.py:241-247).  Fixed capacity; ``valid`` marks live rows."""

    key_hi: jnp.ndarray
    key_lo: jnp.ndarray
    key_ws: jnp.ndarray
    count: jnp.ndarray
    sum_speed: jnp.ndarray   # residual sums about the anchor_* lanes
    sum_speed2: jnp.ndarray  # (engine.state.TileState docstring)
    sum_lat: jnp.ndarray
    sum_lon: jnp.ndarray
    anchor_speed: jnp.ndarray  # per-group anchors: consumers recombine
    anchor_lat: jnp.ndarray    # anchor + resid/count in f64 host-side
    anchor_lon: jnp.ndarray
    hist: jnp.ndarray
    valid: jnp.ndarray       # bool
    n_emitted: jnp.ndarray   # int32 scalar — true touched-group count
    overflowed: jnp.ndarray  # bool scalar — touched groups > emit capacity


class StepStats(NamedTuple):
    n_valid: jnp.ndarray       # events aggregated
    n_late: jnp.ndarray        # events dropped by the watermark
    n_evicted: jnp.ndarray     # state rows recycled (closed windows)
    n_active: jnp.ndarray      # live groups after the merge
    state_overflow: jnp.ndarray  # distinct keys beyond capacity (dropped)
    batch_max_ts: jnp.ndarray  # int32 — max valid event ts (watermark input)


def _snap_impl(res: int):
    """IN-PROGRAM H3 snap implementation: pure-XLA by default; the fused
    Pallas geometry kernel (hexgrid.pallas_kernel) via
    HEATMAP_H3_IMPL=pallas.  Falls back to XLA when the kernel doesn't
    apply (res > 10) or doesn't lower on the current backend.

    HEATMAP_H3_IMPL=native is NOT dispatched here: the C++ host snap
    (hexgrid.native_snap, ~11x faster per CPU core and f64-exact)
    integrates as host-computed ``prekeys`` fed into the fold
    (engine.multi.fused_fold; the stream runtime and bench do this) —
    a pure_callback inside the jitted program deadlocked intermittently
    on the CPU runtime, see hexgrid/native_snap.py."""
    # "auto" takes a banked winner for the live platform (hwbank) and
    # otherwise the XLA snap (CPU's `auto` winner — the native host
    # pre-snap — never reaches here: it rides the prekeys path upstream)
    if inprogram_snap_name(res) == "pallas":
        from heatmap_tpu.hexgrid import pallas_kernel

        return pallas_kernel.latlng_to_cell_pallas
    return hexdev.latlng_to_cell_vec


def window_start(ts_s, valid, window_s: int):
    """Tumbling window start per event; invalid → EMPTY_WS.  The single
    definition of window assignment (engine.multi shares it)."""
    ws = (ts_s // window_s) * window_s
    return jnp.where(valid, ws, EMPTY_WS)


def snap_and_window(lat_rad, lng_rad, ts_s, valid, params: AggParams):
    """Compute (key_hi, key_lo, window_start) per event; invalid → EMPTY."""
    hi, lo = _snap_impl(params.res)(lat_rad, lng_rad, params.res)
    hi = jnp.where(valid, hi, EMPTY_KEY_HI)
    lo = jnp.where(valid, lo, EMPTY_KEY_LO)
    return hi, lo, window_start(ts_s, valid, params.window_s)


def _drop_and_evict(state, ev_hi, ev_lo, ev_ws, ev_valid, watermark_cutoff,
                    params: AggParams):
    """Shared prologue: late/future-event drop + window eviction masks.

    late: the window already closed (ws + window <= cutoff).  future:
    more than FUTURE_WINDOWS ahead of the watermark — a clock-skewed
    producer poison pill; dropping it also guarantees the live window
    span stays < 4096 windows, which the 12-bit window-index key
    compression relies on.  (With the watermark disabled the span bound
    is the caller's responsibility — bounded replays only.)
    """
    late = ev_valid & (ev_ws + params.window_s <= watermark_cutoff)
    if FUTURE_WINDOWS:
        has_wm = watermark_cutoff > jnp.int32(-(2**31))
        future = ev_valid & has_wm & (
            (ev_ws - watermark_cutoff) >= FUTURE_WINDOWS * params.window_s
        )
        late = late | future
    ev_valid = ev_valid & ~late
    ev_hi = jnp.where(ev_valid, ev_hi, EMPTY_KEY_HI)
    ev_lo = jnp.where(ev_valid, ev_lo, EMPTY_KEY_LO)
    ev_ws = jnp.where(ev_valid, ev_ws, EMPTY_WS)

    live = state.key_hi != EMPTY_KEY_HI
    evict = live & (state.key_ws + params.window_s <= watermark_cutoff)
    keep = live & ~evict
    st_hi = jnp.where(keep, state.key_hi, EMPTY_KEY_HI)
    st_lo = jnp.where(keep, state.key_lo, EMPTY_KEY_LO)
    st_ws = jnp.where(keep, state.key_ws, EMPTY_WS)
    return (late, ev_valid, ev_hi, ev_lo, ev_ws,
            evict, keep, st_hi, st_lo, st_ws)


def _compress_key(hi, ws, empty, params: AggParams):
    """96-bit composite key → u32 upper sort key (the low word is `lo`).

    With `res` static, hi's upper bits (mode/res) are constant and its
    variable part (base cell + coarse digits) fits 20 bits; the window
    start folds to a 12-bit window index (mod 4096).  Distinct live keys
    stay distinct while the active window span is < 4096 windows —
    guaranteed by any sane watermark (4096 x 5 min ≈ 14 days);
    k1 = 0xFFFFFFFF is unreachable for live rows (base cell <= 121) and
    marks empties."""
    wix = (ws // params.window_s).astype(jnp.uint32) & jnp.uint32(0xFFF)
    return jnp.where(
        empty,
        jnp.uint32(0xFFFFFFFF),
        (wix << 20) | (hi & jnp.uint32(0xFFFFF)),
    )


def merge_batch(
    state: TileState,
    ev_hi,
    ev_lo,
    ev_ws,
    ev_speed,
    ev_lat_deg,
    ev_lon_deg,
    ev_ts,
    ev_valid,
    watermark_cutoff,          # int32 scalar: evict windows ending before this
    params: AggParams,
    impl: str | None = None,
):
    """Fold one batch into the state. Returns (state, BatchEmit, StepStats).

    Two equivalent routing implementations (differential-tested against
    each other): the default full merge-sort over (state ∥ batch), or —
    with ``HEATMAP_MERGE_IMPL=rank`` — a batch-only sort merged into the
    already-sorted slab by rank (searchsorted), which does ~sort(N)
    instead of ~sort(C+N) work and wins when the slab dwarfs the batch
    (latency-oriented streaming configs).  ``auto`` (the default) picks
    by the measured crossover: rank when capacity >= 4x batch.  The
    round-5 warm-slab arg-passing A/B (the only valid methodology —
    closed-over batch arrays get constant-folded by XLA and an empty
    slab drops every state-side scatter, both of which silently flatter
    rank) confirms it on CPU: sort wins 2^18-batch shapes, rank wins
    2^14-batch streaming shapes by ~1.5x; the on-chip crossover is not
    measured.  The env var is
    read at trace time (module override slot ``MERGE_IMPL`` wins when
    set — bench sweeps and tests use it); pass ``impl`` explicitly to
    override per call."""
    if impl is None:
        impl = _resolve_merge_impl()
    if impl == "auto":
        # a banked on-chip crossover (hwbank merge units) outranks the
        # static capacity-ratio rule
        if MERGE_BANK_PIN is _BANK_LIVE:
            from heatmap_tpu import hwbank

            banked = hwbank.merge_winner()
        else:
            banked = MERGE_BANK_PIN
        impl = (banked
                or ("rank" if state.capacity >= 4 * ev_hi.shape[0]
                    else "sort"))
    slow = {"rank": _merge_rank, "probe": _merge_probe,
            "sort": _merge_sort}[impl]
    if _resolve_fastpath():
        return _merge_fastpath(state, ev_hi, ev_lo, ev_ws, ev_speed,
                               ev_lat_deg, ev_lon_deg, ev_ts, ev_valid,
                               watermark_cutoff, params, impl)
    return slow(state, ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg,
                ev_lon_deg, ev_ts, ev_valid, watermark_cutoff, params)


@functools.partial(jax.jit, static_argnames=("params",))
def _merge_sort(
    state: TileState,
    ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg, ev_lon_deg, ev_ts, ev_valid,
    watermark_cutoff,
    params: AggParams,
):
    """Routing via one merge-sort of (state ∥ batch) compressed keys."""
    C = state.capacity
    N = ev_hi.shape[0]

    (late, ev_valid, ev_hi, ev_lo, ev_ws, evict, keep, st_hi, st_lo,
     st_ws) = _drop_and_evict(state, ev_hi, ev_lo, ev_ws, ev_valid,
                              watermark_cutoff, params)

    # --- merge-sort state ∥ batch; carry origin row -----------------------
    # Halving the sort operands (2 u32 keys instead of the 96-bit
    # composite) nearly halves the cost of the dominant op in this fold.
    all_hi = jnp.concatenate([st_hi, ev_hi])
    all_lo = jnp.concatenate([st_lo, ev_lo])
    all_ws = jnp.concatenate([st_ws, ev_ws])
    k1 = _compress_key(all_hi, all_ws, all_hi == EMPTY_KEY_HI, params)
    orig = jnp.arange(C + N, dtype=jnp.int32)  # <C: state row, >=C: batch row
    s_k1, s_k2, s_orig = jax.lax.sort((k1, all_lo, orig), num_keys=2)

    nonempty = s_k1 != jnp.uint32(0xFFFFFFFF)
    changed = (s_k1 != jnp.roll(s_k1, 1)) | (s_k2 != jnp.roll(s_k2, 1))
    is_start = changed.at[0].set(True)
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1  # sorted-order segment id

    # --- per-origin-row new segment (the scatter routing tables) ---------
    # state row r (kept) lands in segment state_seg[r]; batch row i in batch_seg[i]
    st_idx = jnp.where(s_orig < C, s_orig, C)
    state_seg = jnp.full((C,), C, jnp.int32).at[st_idx].set(seg, mode="drop")
    bt_idx = jnp.where(s_orig >= C, s_orig - C, N)
    batch_seg = jnp.full((N,), C, jnp.int32).at[bt_idx].set(seg, mode="drop")
    # route empties/evictions/lates to the drop bin
    state_seg = jnp.where(keep, state_seg, C)
    batch_seg = jnp.where(ev_valid, batch_seg, C)

    n_seg_total = seg[-1] + 1  # includes the single EMPTY segment if present
    has_empty = ~nonempty[-1]  # empties (if any) sort last
    n_distinct = n_seg_total - has_empty.astype(jnp.int32)
    return _apply_routing(state, ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg,
                          ev_lon_deg, ev_ts, ev_valid, late, evict, keep,
                          state_seg, batch_seg, n_distinct, params)


def _searchsorted_pair(a1, a2, q1, q2):
    """Leftmost insertion index of each (q1, q2) query into the array
    sorted lexicographically by (a1, a2) — u32 pairs, since the default
    no-x64 JAX config has no u64 (a static unrolled binary search; each
    step is two gathers over the query vector)."""
    n = a1.shape[0]
    lo = jnp.zeros(q1.shape, jnp.int32)
    hi = jnp.full(q1.shape, n, jnp.int32)
    for _ in range(max(n, 1).bit_length()):
        mid = (lo + hi) >> 1
        i = jnp.clip(mid, 0, n - 1)
        m1 = a1[i]
        m2 = a2[i]
        a_lt_q = (m1 < q1) | ((m1 == q1) & (m2 < q2))
        lo = jnp.where(a_lt_q, mid + 1, lo)
        hi = jnp.where(a_lt_q, hi, mid)
    return lo


@functools.partial(jax.jit, static_argnames=("params",))
def _merge_rank(
    state: TileState,
    ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg, ev_lon_deg, ev_ts, ev_valid,
    watermark_cutoff,
    params: AggParams,
):
    """Routing via a batch-only sort merged into the sorted slab by rank.

    The slab's sortedness invariant means the state side never needs
    re-sorting: evicted rows compact out with a cumsum, the batch's
    unique keys binary-search their insertion points, and every row's
    final position is (state rank) + (count of smaller new keys).  Work
    is ~sort(N) + O((C+N) log) instead of ~sort(C+N)."""
    C = state.capacity
    N = ev_hi.shape[0]
    U32MAX = jnp.uint32(0xFFFFFFFF)

    (late, ev_valid, ev_hi, ev_lo, ev_ws, evict, keep, st_hi, st_lo,
     st_ws) = _drop_and_evict(state, ev_hi, ev_lo, ev_ws, ev_valid,
                              watermark_cutoff, params)

    # compressed key pair: (k1, lo); k1 == U32MAX marks empty/invalid and
    # is unreachable for live rows (see _compress_key)
    st_k1 = _compress_key(st_hi, st_ws, ~keep, params)
    ev_k1 = _compress_key(ev_hi, ev_ws, ~ev_valid, params)

    # --- compact the kept state rows (stays sorted: subsequence) ---------
    c1, c2, pos_k, n_keep = _compact_state(keep, st_k1, st_lo, C)

    # --- sort the batch only ---------------------------------------------
    u1, u2, uid_of_event = _sorted_batch_uniques(ev_k1, ev_lo, N)

    state_seg, batch_seg, n_distinct = _route_via_uniques(
        c1, c2, pos_k, keep, n_keep, u1, u2, uid_of_event, ev_valid, C)
    return _apply_routing(state, ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg,
                          ev_lon_deg, ev_ts, ev_valid, late, evict, keep,
                          state_seg, batch_seg, n_distinct, params)


def _compact_state(keep, st_k1, st_lo, C: int):
    """Compact the kept state rows to the slab prefix (stays sorted: a
    subsequence of a sorted sequence).  THE definition of the compacted
    (c1, c2) slab both rank and probe routing search against."""
    U32MAX = jnp.uint32(0xFFFFFFFF)
    keep_i = keep.astype(jnp.int32)
    pos_k = jnp.cumsum(keep_i) - 1                # target rank per kept row
    n_keep = jnp.sum(keep_i)
    st_dst = jnp.where(keep, pos_k, C)
    c1 = jnp.full((C,), U32MAX, jnp.uint32).at[st_dst].set(st_k1, mode="drop")
    c2 = jnp.full((C,), U32MAX, jnp.uint32).at[st_dst].set(st_lo, mode="drop")
    return c1, c2, pos_k, n_keep


def _sorted_batch_uniques(ev_k1, ev_lo, N: int):
    """Batch sort + dedup: ascending unique (k1, lo) keys padded with
    (MAX, MAX), and each event's index into them.  THE definition of the
    sort route — _merge_rank always takes it, _merge_probe falls back to
    it, and bit-identity between those paths depends on both calling
    this one function."""
    U32MAX = jnp.uint32(0xFFFFFFFF)
    orig = jnp.arange(N, dtype=jnp.int32)
    s_k1, s_k2, s_orig = jax.lax.sort((ev_k1, ev_lo, orig), num_keys=2)
    is_start = ((s_k1 != jnp.roll(s_k1, 1))
                | (s_k2 != jnp.roll(s_k2, 1))).at[0].set(True)
    seg_b = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    u1 = jnp.full((N,), U32MAX, jnp.uint32).at[seg_b].set(s_k1)
    u2 = jnp.full((N,), U32MAX, jnp.uint32).at[seg_b].set(s_k2)
    uid_of_event = jnp.zeros((N,), jnp.int32).at[s_orig].set(seg_b)
    return u1, u2, uid_of_event


def _route_via_uniques(c1, c2, pos_k, keep, n_keep, u1, u2, uid_of_event,
                       ev_valid, C: int):
    """Shared rank-merge tail: given the compacted sorted slab (c1, c2),
    the ascending unique batch keys (u1, u2 — any length, (MAX, MAX)
    padded) and each event's index into them, produce the scatter
    routing tables (state_seg, batch_seg, n_distinct)."""
    U32MAX = jnp.uint32(0xFFFFFFFF)
    u_valid = u1 != U32MAX

    # --- rank the uniques against the compacted slab ---------------------
    p_state = _searchsorted_pair(c1, c2, u1, u2)
    i = jnp.clip(p_state, 0, C - 1)
    matched = u_valid & (p_state < C) & (c1[i] == u1) & (c2[i] == u2)
    is_new = u_valid & ~matched
    new_i = is_new.astype(jnp.int32)
    before = jnp.cumsum(new_i) - new_i        # new keys strictly smaller
    out_u = jnp.where(u_valid, p_state + before, C)

    # state-side shift without a second search: slab row j moves right by
    # #{new keys < c[j]} = #{new: p_state <= j} (a new key inserting at j
    # is strictly smaller than c[j] — never equal, else it would have
    # matched), i.e. an inclusive cumsum of insertion-point counts
    cnt_new = (jnp.zeros((C,), jnp.int32)
               .at[jnp.where(is_new, p_state, C)].add(1, mode="drop"))
    out_state_pos = jnp.arange(C, dtype=jnp.int32) + jnp.cumsum(cnt_new)

    # --- routing tables ---------------------------------------------------
    state_seg = jnp.where(
        keep, out_state_pos[jnp.clip(pos_k, 0, C - 1)], C)
    batch_seg = jnp.where(ev_valid, out_u[uid_of_event], C)
    n_distinct = n_keep + jnp.sum(new_i)
    return state_seg, batch_seg, n_distinct


@functools.partial(jax.jit, static_argnames=("params",))
def _merge_probe(
    state: TileState,
    ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg, ev_lon_deg, ev_ts, ev_valid,
    watermark_cutoff,
    params: AggParams,
):
    """Routing via hash-probe dedup instead of a batch sort.

    The batch sort is the dominant cost of ``rank`` at streaming shapes,
    yet a batch of N events typically holds ~N/10 distinct (cell,
    window) keys.  This impl dedups the batch into a 2N-slot linear-
    probing table with R rounds of gather/scatter (O(R·N) memory traffic
    — no log²N sorting network), then sorts only a fixed N/PROBE_UNIQ_DIV
    unique budget and reuses the rank machinery.  On a sort-hostile
    backend (TPU: lax.sort is ~log²N serial stages) the probe rounds
    replace ~98 stages with ~PROBE_ROUNDS passes.

    Correctness never depends on the probe converging: if any event is
    still unplaced after R rounds, or the distinct count exceeds the
    unique budget, a ``lax.cond`` falls back to the full batch-sort
    route for THIS batch (same routing-table contract, bit-identical
    ``_apply_routing`` epilogue).  Tunables: HEATMAP_PROBE_ROUNDS
    (default 16), HEATMAP_PROBE_UNIQ_DIV (default 8 → budget N/8,
    floor 256)."""
    C = state.capacity
    N = ev_hi.shape[0]
    U32MAX = jnp.uint32(0xFFFFFFFF)
    M = 1 << (2 * N - 1).bit_length()        # pow2 table, load <= 0.5
    U = min(N, max(256, N // PROBE_UNIQ_DIV))

    (late, ev_valid, ev_hi, ev_lo, ev_ws, evict, keep, st_hi, st_lo,
     st_ws) = _drop_and_evict(state, ev_hi, ev_lo, ev_ws, ev_valid,
                              watermark_cutoff, params)

    st_k1 = _compress_key(st_hi, st_ws, ~keep, params)
    ev_k1 = _compress_key(ev_hi, ev_ws, ~ev_valid, params)

    c1, c2, pos_k, n_keep = _compact_state(keep, st_k1, st_lo, C)

    # --- probe-dedup the batch -------------------------------------------
    h = ((ev_k1 * jnp.uint32(0x9E3779B9))
         ^ (ev_lo * jnp.uint32(0x85EBCA6B)))
    eidx = jnp.arange(N, dtype=jnp.int32)

    def probe_round(_, carry):
        tk1, tk2, placed, slot, off = carry
        idx = ((h + off.astype(jnp.uint32))
               & jnp.uint32(M - 1)).astype(jnp.int32)
        want = ~placed
        cur1 = tk1[idx]
        cur2 = tk2[idx]
        empty = cur1 == U32MAX
        mine = want & ~empty & (cur1 == ev_k1) & (cur2 == ev_lo)
        claim = want & empty
        # lowest event index wins a contested empty slot.  ALL losers of
        # an empty-slot contest re-check the SAME slot next round (off
        # unchanged): same-key losers then match the installed key;
        # different-key losers see a foreign key and advance — i.e. an
        # empty-slot loss costs one stalled round before advancing, so
        # worst-case placement needs (probe-chain length + contested
        # rounds), not just the chain length; size PROBE_ROUNDS (and
        # trust the fallback) accordingly
        claim_arr = (jnp.full((M,), N, jnp.int32)
                     .at[jnp.where(claim, idx, M)].min(eidx, mode="drop"))
        winner = claim & (claim_arr[idx] == eidx)
        widx = jnp.where(winner, idx, M)
        tk1 = tk1.at[widx].set(ev_k1, mode="drop")
        tk2 = tk2.at[widx].set(ev_lo, mode="drop")
        advance = want & ~empty & ~mine
        return (tk1, tk2, placed | mine | winner,
                jnp.where(mine | winner, idx, slot),
                off + advance.astype(jnp.int32))

    init = (jnp.full((M,), U32MAX, jnp.uint32),
            jnp.full((M,), U32MAX, jnp.uint32),
            ~ev_valid,                            # invalid rows never probe
            jnp.zeros_like(eidx),
            jnp.zeros_like(eidx))
    if PROBE_ROUNDS > 0:
        # round 0 unrolled: under shard_map the fori_loop carry must have
        # uniform "varying over shards" types, but the fresh tables above
        # are replicated constants while the loop's outputs depend on the
        # (sharded) batch.  One unrolled round makes every carry
        # component batch-derived before the loop sees it.
        init = probe_round(0, init)
    tk1, tk2, placed, slot, _ = jax.lax.fori_loop(
        1, PROBE_ROUNDS, probe_round, init)

    # --- compact + sort only the unique budget ---------------------------
    occupied = tk1 != U32MAX
    comp_pos = jnp.cumsum(occupied.astype(jnp.int32)) - 1     # over M slots
    n_uniq = jnp.sum(occupied.astype(jnp.int32))
    dst = jnp.where(occupied & (comp_pos < U), comp_pos, U)
    cu1 = jnp.full((U,), U32MAX, jnp.uint32).at[dst].set(tk1, mode="drop")
    cu2 = jnp.full((U,), U32MAX, jnp.uint32).at[dst].set(tk2, mode="drop")
    cid = jnp.arange(U, dtype=jnp.int32)
    s_u1, s_u2, s_cid = jax.lax.sort((cu1, cu2, cid), num_keys=2)
    rank_of_compact = jnp.zeros((U,), jnp.int32).at[s_cid].set(cid)
    compact_of_slot = jnp.clip(comp_pos, 0, U - 1)
    uid_of_event = rank_of_compact[compact_of_slot[jnp.clip(slot, 0, M - 1)]]

    fallback = jnp.any(ev_valid & ~placed) | (n_uniq > U)

    def probe_route(_):
        return _route_via_uniques(c1, c2, pos_k, keep, n_keep, s_u1, s_u2,
                                  uid_of_event, ev_valid & placed, C)

    def sort_route(_):
        u1, u2, uid = _sorted_batch_uniques(ev_k1, ev_lo, N)
        return _route_via_uniques(c1, c2, pos_k, keep, n_keep, u1, u2,
                                  uid, ev_valid, C)

    state_seg, batch_seg, n_distinct = jax.lax.cond(
        fallback, sort_route, probe_route, None)
    return _apply_routing(state, ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg,
                          ev_lon_deg, ev_ts, ev_valid, late, evict, keep,
                          state_seg, batch_seg, n_distinct, params)


def _fastpath_probe_full(state, ev_hi, ev_lo, ev_ws, ev_valid,
                         watermark_cutoff, params: AggParams):
    """The fast-path predicate: per-event binary search against the
    sorted slab.  Returns the masked prologue outputs, compressed keys,
    per-event row position, hit mask, and the tier-1 fast_ok scalar.

    The prologue runs on masked COPIES of the event arrays; the slow
    branch gets the ORIGINALS (its own prologue must see late rows to
    count them in its stats)."""
    C = state.capacity
    (late, ev_valid_m, ev_hi_m, ev_lo_m, ev_ws_m, evict, keep, st_hi,
     st_lo, st_ws) = _drop_and_evict(state, ev_hi, ev_lo, ev_ws, ev_valid,
                                     watermark_cutoff, params)
    st_k1 = _compress_key(st_hi, st_ws, ~keep, params)
    ev_k1 = _compress_key(ev_hi_m, ev_ws_m, ~ev_valid_m, params)
    pos = _searchsorted_pair(st_k1, st_lo, ev_k1, ev_lo_m)
    i = jnp.clip(pos, 0, C - 1)
    hit = (ev_valid_m & (pos < C) & (st_k1[i] == ev_k1)
           & (st_lo[i] == ev_lo_m))
    # with evictions the slab has EMPTY holes mid-array and the search
    # above ran against an unsorted sequence — `hit` is then garbage,
    # but the evict term already forces the slow branch
    fast_ok = jnp.all(hit == ev_valid_m) & ~jnp.any(evict)
    return (late, ev_valid_m, ev_hi_m, ev_lo_m, ev_ws_m, evict, keep,
            ev_k1, st_k1, st_lo, pos, hit, fast_ok)


def _fastpath_probe(state, ev_hi, ev_lo, ev_ws, ev_valid,
                    watermark_cutoff, params: AggParams):
    """Compact view of `_fastpath_probe_full` for the predicate tests:
    (late, masked ev_valid, positions, hit mask, tier-1 fast_ok)."""
    (late, ev_valid_m, _hi, _lo, _ws, _evict, _keep, _k1, _sk1, _slo,
     pos, hit, fast_ok) = _fastpath_probe_full(
        state, ev_hi, ev_lo, ev_ws, ev_valid, watermark_cutoff, params)
    return late, ev_valid_m, pos, hit, fast_ok


@functools.partial(jax.jit, static_argnames=("params", "slow_impl"))
def _merge_fastpath(
    state: TileState,
    ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg, ev_lon_deg, ev_ts, ev_valid,
    watermark_cutoff,
    params: AggParams,
    slow_impl: str,
):
    """Steady-state fast path wrapped around any routing impl.

    In a warmed stream most batches touch ONLY existing (cell, window)
    groups and evict nothing — yet every impl above rebuilds the entire
    slab (sort/scatter every lane of every row) per batch, which is the
    dominant cost at production shapes (~4/5 of the fold wall on CPU,
    round-5 attribution).  This wrapper binary-searches each event
    against the sorted slab directly (no batch sort, no dedup —
    duplicate hits are scatter-adds) and, when every valid event hits an
    existing row and no window evicts, applies the batch with in-place
    scatter-adds on the touched rows only; otherwise it falls through to
    the configured slow impl for THIS batch via ``lax.cond``.

    Three tiers, cheapest condition first (``lax.cond`` nest):

    1. **all-hit**: every valid event matched an existing row and no
       window evicts — in-place scatter-adds only, the slab untouched.
    2. **few misses** (≤ max(1024, N/16) events): hit events take their
       searched positions directly; only the miss events compact into a
       small buffer, sort, and ride the rank impl's insertion rails
       (`_route_via_uniques` + `_apply_routing`).  This replaces rank's
       full-batch sort — the dominant term at production batches — with
       a sort of just the misses, and produces the exact routing tables
       rank would (proof sketch: a matched unique's shift `before(u)`
       equals `cumsum(cnt_new)[p_state(u)]` because a new key inserting
       at or before a matched row is strictly smaller than it).
    3. otherwise (evictions, miss burst, window turnover): the
       configured slow impl, unchanged.

    Bit-identity with the slow path on tier-1/2 batches is by
    construction: tier 1 replicates `_apply_routing`'s arithmetic under
    its no-new-keys/no-evict conditions — including the slow path's
    Kahan rewrite of untouched rows (sum' = sum - comp, comp' absorbs
    it) — and tier 2 feeds `_apply_routing` itself with rank-identical
    routing tables.  Differential-tested per batch
    (tests/test_merge_fastpath.py).  The slab's sorted invariant is
    preserved by every tier."""
    C = state.capacity
    N = ev_hi.shape[0]
    M = max(1024, N // 16)  # miss-event budget for the insert tier
    (late, ev_valid_m, ev_hi_m, ev_lo_m, ev_ws_m, evict, keep,
     ev_k1, st_k1, st_lo_m, pos, hit, fast_ok) = _fastpath_probe_full(
        state, ev_hi, ev_lo, ev_ws, ev_valid, watermark_cutoff, params)

    def fast(_):
        B = state.hist_bins
        E = params.emit_capacity
        gi = jnp.where(hit, pos, C)          # drop bin for misses
        gic = jnp.clip(gi, 0, C - 1)
        one = hit.astype(jnp.int32)
        count = state.count.at[gi].add(one, mode="drop")

        resid = lambda ev, anc: jnp.where(hit, ev - anc[gic], 0.0)
        r_speed = resid(ev_speed, state.anchor_speed)
        r_lat = resid(ev_lat_deg, state.anchor_lat)
        r_lon = resid(ev_lon_deg, state.anchor_lon)
        ev_vals = jnp.stack([
            r_speed, r_speed * r_speed, r_lat, r_lon,
        ], axis=1)
        # the slow path's epilogue Kahan-rewrites EVERY row (untouched
        # rows become sum-comp with comp absorbing the shift); replicate
        # it exactly so fast and slow batches interleave bit-identically
        base = jnp.stack([
            state.sum_speed, state.sum_speed2, state.sum_lat,
            state.sum_lon,
        ], axis=1)
        delta = jnp.zeros((C, 4), jnp.float32).at[gi].add(
            ev_vals, mode="drop")
        y = delta - state.comp
        t = base + y
        comp = (t - base) - y
        sum_speed, sum_speed2, sum_lat, sum_lon = (
            t[:, 0], t[:, 1], t[:, 2], t[:, 3]
        )
        if B > 0:
            bin_w = params.speed_hist_max / B
            ev_bin = jnp.clip((ev_speed / bin_w).astype(jnp.int32), 0,
                              B - 1)
            hist = state.hist.at[gi, ev_bin].add(one, mode="drop")
        else:
            hist = state.hist
        new_state = TileState(
            key_hi=state.key_hi, key_lo=state.key_lo, key_ws=state.key_ws,
            count=count, sum_speed=sum_speed, sum_speed2=sum_speed2,
            sum_lat=sum_lat, sum_lon=sum_lon, hist=hist,
            anchor_speed=state.anchor_speed, anchor_lat=state.anchor_lat,
            anchor_lon=state.anchor_lon, comp=comp,
        )

        touched = jnp.zeros((C,), bool).at[gi].set(True, mode="drop")
        n_emitted = jnp.sum(touched.astype(jnp.int32))
        emit_idx = jnp.nonzero(touched, size=E, fill_value=C)[0]
        emit_ok = emit_idx < C
        g = jnp.where(emit_ok, emit_idx, 0)
        emit = BatchEmit(
            key_hi=jnp.where(emit_ok, state.key_hi[g], EMPTY_KEY_HI),
            key_lo=jnp.where(emit_ok, state.key_lo[g], EMPTY_KEY_LO),
            key_ws=jnp.where(emit_ok, state.key_ws[g], EMPTY_WS),
            count=jnp.where(emit_ok, count[g], 0),
            sum_speed=jnp.where(emit_ok, sum_speed[g], 0.0),
            sum_speed2=jnp.where(emit_ok, sum_speed2[g], 0.0),
            sum_lat=jnp.where(emit_ok, sum_lat[g], 0.0),
            sum_lon=jnp.where(emit_ok, sum_lon[g], 0.0),
            anchor_speed=jnp.where(emit_ok, state.anchor_speed[g], 0.0),
            anchor_lat=jnp.where(emit_ok, state.anchor_lat[g], 0.0),
            anchor_lon=jnp.where(emit_ok, state.anchor_lon[g], 0.0),
            hist=hist[g] * emit_ok[:, None].astype(jnp.int32) if B > 0
            else jnp.zeros((E, 0), jnp.int32),
            valid=emit_ok,
            n_emitted=n_emitted,
            overflowed=n_emitted > E,
        )
        n_valid = jnp.sum(one)
        stats = StepStats(
            n_valid=n_valid,
            n_late=jnp.sum(late.astype(jnp.int32)),
            # zero by the tier-1 predicate, but derived from varying data
            # (a literal 0 would give this branch an unvarying aval and
            # break lax.cond type agreement under shard_map)
            n_evicted=jnp.sum(evict.astype(jnp.int32)),
            n_active=jnp.sum((state.key_hi != EMPTY_KEY_HI)
                             .astype(jnp.int32)),
            state_overflow=0 * n_valid,
            batch_max_ts=jnp.max(jnp.where(ev_valid_m, ev_ts, I32_MIN)),
        )
        return new_state, emit, stats

    miss = ev_valid_m & ~hit
    n_miss = jnp.sum(miss.astype(jnp.int32))
    insert_ok = (~jnp.any(evict)) & (n_miss <= M) & (n_miss > 0)

    def insert(_):
        """Tier 2: hits keep their searched rows; only the miss events
        sort (M rows, not N) and ride the rank insertion rails."""
        U32MAX = jnp.uint32(0xFFFFFFFF)
        midx = jnp.nonzero(miss, size=M, fill_value=N)[0]
        mvalid = midx < N
        mi = jnp.clip(midx, 0, N - 1)
        mk1 = jnp.where(mvalid, ev_k1[mi], U32MAX)
        mk2 = jnp.where(mvalid, ev_lo_m[mi], U32MAX)
        mu1, mu2, uid_m = _sorted_batch_uniques(mk1, mk2, M)
        # event -> its M-slot -> unique id (only meaningful for misses)
        slot_of_event = (jnp.zeros((N,), jnp.int32)
                         .at[jnp.where(mvalid, midx, N)]
                         .set(jnp.arange(M, dtype=jnp.int32), mode="drop"))
        c1, c2, pos_k, n_keep = _compact_state(keep, st_k1, st_lo_m, C)
        state_seg, batch_seg_u, n_distinct = _route_via_uniques(
            c1, c2, pos_k, keep, n_keep, mu1, mu2,
            uid_m[jnp.clip(slot_of_event, 0, M - 1)],
            miss, C)
        # hits: final position = searched row + #new keys inserted at or
        # before it (== rank's `before` for a matched unique; see proof
        # sketch in the docstring).  Recover the shift from state_seg:
        # row r moved to state_seg[r], so shift lives in the same table.
        hit_rows = jnp.clip(pos, 0, C - 1)
        batch_seg = jnp.where(
            hit, state_seg[hit_rows],
            jnp.where(miss, batch_seg_u, C))
        return _apply_routing(state, ev_hi_m, ev_lo_m, ev_ws_m, ev_speed,
                              ev_lat_deg, ev_lon_deg, ev_ts, ev_valid_m,
                              late, evict, keep, state_seg, batch_seg,
                              n_distinct, params)

    def slow(_):
        fn = {"rank": _merge_rank, "probe": _merge_probe,
              "sort": _merge_sort}[slow_impl]
        return fn(state, ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg,
                  ev_lon_deg, ev_ts, ev_valid, watermark_cutoff, params)

    def not_fast(_):
        return jax.lax.cond(insert_ok, insert, slow, None)

    return jax.lax.cond(fast_ok, fast, not_fast, None)


def _apply_routing(
    state: TileState,
    ev_hi, ev_lo, ev_ws, ev_speed, ev_lat_deg, ev_lon_deg, ev_ts, ev_valid,
    late, evict, keep,
    state_seg, batch_seg, n_distinct,
    params: AggParams,
):
    """Shared epilogue: rebuild the slab from the routing tables, build the
    update-mode emit, and assemble StepStats."""
    C = state.capacity
    B = state.hist_bins

    # --- rebuild the slab ------------------------------------------------
    # keys scatter from the ORIGINAL arrays via the routing maps (the sort
    # only carried the compressed keys); rows of one segment all write the
    # same value, the EMPTY segment keeps its init sentinel.
    key_hi = (
        jnp.full((C,), EMPTY_KEY_HI, jnp.uint32)
        .at[state_seg].set(state.key_hi, mode="drop")
        .at[batch_seg].set(ev_hi, mode="drop")
    )
    key_lo = (
        jnp.full((C,), EMPTY_KEY_LO, jnp.uint32)
        .at[state_seg].set(state.key_lo, mode="drop")
        .at[batch_seg].set(ev_lo, mode="drop")
    )
    key_ws = (
        jnp.full((C,), EMPTY_WS, jnp.int32)
        .at[state_seg].set(state.key_ws, mode="drop")
        .at[batch_seg].set(ev_ws, mode="drop")
    )

    zc = jnp.zeros((C,), jnp.int32)
    one = ev_valid.astype(jnp.int32)
    count = (
        zc.at[state_seg].add(jnp.where(keep, state.count, 0), mode="drop")
        .at[batch_seg].add(one, mode="drop")
    )

    # --- residual-anchor accumulation (the f64-free precision story) ----
    # TPUs have no f64, and absolute f32 sums cannot hold the needed
    # precision: Σlat over a 1M-event hot cell reaches ~4e7 where the f32
    # ulp is 4, so even a correctly-rounded absolute sum puts the centroid
    # ~2e-6 deg off.  Each group instead carries FIXED anchors (min over
    # the events of the batch that created it — a segment-min, so both
    # merge impls derive the identical value) and accumulates residuals
    # about them.  Values within one hex cell lie within a fraction of
    # each other, so `ev - anchor` is exact (Sterbenz) and the residual
    # sums stay small enough for f32 to hold to ~1e-8 deg.  Consumers
    # recombine anchor + resid/count in f64 host-side (sink/base.py,
    # native/tile_ops.cpp); speed variance is anchor-invariant:
    # Var(v) = E[r²] − E[r]².
    inf = jnp.float32(jnp.inf)

    def group_anchor(ev, stored):
        a = (jnp.full((C,), inf, jnp.float32)
             .at[batch_seg].min(jnp.where(ev_valid, ev, inf), mode="drop"))
        # existing groups keep their stored anchor: accumulated residuals
        # are relative to it, so it must never move while the group lives
        return a.at[state_seg].set(jnp.where(keep, stored, inf), mode="drop")

    anc_speed = group_anchor(ev_speed, state.anchor_speed)
    anc_lat = group_anchor(ev_lat_deg, state.anchor_lat)
    anc_lon = group_anchor(ev_lon_deg, state.anchor_lon)

    gi_ev = jnp.clip(batch_seg, 0, C - 1)
    resid = lambda ev, anc: jnp.where(ev_valid, ev - anc[gi_ev], 0.0)
    r_speed = resid(ev_speed, anc_speed)
    r_lat = resid(ev_lat_deg, anc_lat)
    r_lon = resid(ev_lon_deg, anc_lon)
    # overflow-dropped events may read an empty row's inf anchor → non-
    # finite residuals; their scatter writes are dropped (mode="drop"),
    # so the values never land — only anchors stored/emitted must be
    # sanitized (below).

    # the four float accumulators ride one (C, 4) scatter instead of four
    kf = keep.astype(jnp.float32)
    st_vals = jnp.stack([
        state.sum_speed * kf, state.sum_speed2 * kf,
        state.sum_lat * kf, state.sum_lon * kf,
    ], axis=1)
    ev_vals = jnp.stack([
        r_speed, r_speed * r_speed, r_lat, r_lon,
    ], axis=1)
    base = jnp.zeros((C, 4), jnp.float32).at[state_seg].add(
        st_vals, mode="drop")
    delta = jnp.zeros((C, 4), jnp.float32).at[batch_seg].add(
        ev_vals, mode="drop")
    comp_r = jnp.zeros((C, 4), jnp.float32).at[state_seg].add(
        state.comp * kf[:, None], mode="drop")
    # Kahan fold of the batch delta into the carried sums: the error of
    # each fold is captured in `comp` and fed back, so the accumulated
    # error stays at per-batch scatter rounding instead of growing with
    # the group's total count.  (XLA does not reassociate float adds by
    # default, so the compensation term survives compilation.)
    y = delta - comp_r
    t = base + y
    comp = (t - base) - y
    sums = t
    sum_speed, sum_speed2, sum_lat, sum_lon = (
        sums[:, 0], sums[:, 1], sums[:, 2], sums[:, 3]
    )
    # empty/recycled rows: finite zeros (inf anchors would poison a later
    # emit pack; empties have no batch events and no kept state row)
    anc_speed = jnp.where(jnp.isfinite(anc_speed), anc_speed, 0.0)
    anc_lat = jnp.where(jnp.isfinite(anc_lat), anc_lat, 0.0)
    anc_lon = jnp.where(jnp.isfinite(anc_lon), anc_lon, 0.0)

    if B > 0:
        bin_w = params.speed_hist_max / B
        ev_bin = jnp.clip((ev_speed / bin_w).astype(jnp.int32), 0, B - 1)
        hist = jnp.zeros((C, B), jnp.int32)
        hist = hist.at[state_seg].add(
            state.hist * keep[:, None].astype(jnp.int32), mode="drop"
        )
        hist = hist.at[batch_seg, ev_bin].add(one, mode="drop")
    else:
        hist = state.hist

    new_state = TileState(
        key_hi=key_hi, key_lo=key_lo, key_ws=key_ws, count=count,
        sum_speed=sum_speed, sum_speed2=sum_speed2,
        sum_lat=sum_lat, sum_lon=sum_lon, hist=hist,
        anchor_speed=anc_speed, anchor_lat=anc_lat, anchor_lon=anc_lon,
        comp=comp,
    )

    # --- update-mode emit: groups touched by this batch -------------------
    E = params.emit_capacity
    touched = jnp.zeros((C,), bool).at[batch_seg].set(True, mode="drop")
    n_emitted = jnp.sum(touched.astype(jnp.int32))
    emit_idx = jnp.nonzero(touched, size=E, fill_value=C)[0]
    emit_ok = emit_idx < C
    gi = jnp.where(emit_ok, emit_idx, 0)
    emit = BatchEmit(
        key_hi=jnp.where(emit_ok, key_hi[gi], EMPTY_KEY_HI),
        key_lo=jnp.where(emit_ok, key_lo[gi], EMPTY_KEY_LO),
        key_ws=jnp.where(emit_ok, key_ws[gi], EMPTY_WS),
        count=jnp.where(emit_ok, count[gi], 0),
        sum_speed=jnp.where(emit_ok, sum_speed[gi], 0.0),
        sum_speed2=jnp.where(emit_ok, sum_speed2[gi], 0.0),
        sum_lat=jnp.where(emit_ok, sum_lat[gi], 0.0),
        sum_lon=jnp.where(emit_ok, sum_lon[gi], 0.0),
        anchor_speed=jnp.where(emit_ok, anc_speed[gi], 0.0),
        anchor_lat=jnp.where(emit_ok, anc_lat[gi], 0.0),
        anchor_lon=jnp.where(emit_ok, anc_lon[gi], 0.0),
        hist=hist[gi] * emit_ok[:, None].astype(jnp.int32) if B > 0
        else jnp.zeros((E, 0), jnp.int32),
        valid=emit_ok,
        n_emitted=n_emitted,
        overflowed=n_emitted > E,
    )

    # --- stats ------------------------------------------------------------
    stats = StepStats(
        n_valid=jnp.sum(one),
        n_late=jnp.sum(late.astype(jnp.int32)),
        n_evicted=jnp.sum(evict.astype(jnp.int32)),
        n_active=jnp.sum((key_hi != EMPTY_KEY_HI).astype(jnp.int32)),
        state_overflow=jnp.maximum(n_distinct - C, 0),
        batch_max_ts=jnp.max(jnp.where(ev_valid, ev_ts, I32_MIN)),
    )
    return new_state, emit, stats


def p95_from_hist_device(hist, count, hist_max: float):
    """Vectorized 95th percentile from per-row speed histograms (device).

    Same interpolation as the host oracle (tests/test_emit_pack.py);
    computing it on device means the (E, B) histogram never has to cross
    the device->host link."""
    E, B = hist.shape
    bin_w = hist_max / B
    target = 0.95 * count.astype(jnp.float32)
    cum = jnp.cumsum(hist, axis=1).astype(jnp.float32)
    i = jnp.sum((cum < target[:, None]).astype(jnp.int32), axis=1)
    ic = jnp.clip(i, 0, B - 1)
    prev = jnp.where(
        ic > 0,
        jnp.take_along_axis(cum, jnp.maximum(ic - 1, 0)[:, None], axis=1)[:, 0],
        0.0,
    )
    in_bin = jnp.take_along_axis(hist, ic[:, None], axis=1)[:, 0].astype(jnp.float32)
    frac = jnp.where(in_bin > 0, (target - prev) / in_bin, 0.0)
    p95 = jnp.where(i >= B, hist_max, (ic.astype(jnp.float32) + frac) * bin_w)
    return jnp.where(count > 0, p95, 0.0)


def pack_emit(emit: BatchEmit, speed_hist_max: float = 256.0) -> jnp.ndarray:
    """Pack a BatchEmit into one (E+1, 13) uint32 matrix.

    Every transferred leaf costs a device->host round trip; one packed
    matrix makes the per-batch pull a single transfer.
    Row 0 carries [n_emitted, overflowed] in slots 0..1; slots 2.. are
    reserved for a stats rider (``ride_stats`` — engine.multi and
    parallel.sharded embed their step stats there so the host needs no
    second transfer).  Rows 1.. are [key_hi, key_lo, ws, count, sum_speed,
    sum_speed2, sum_lat, sum_lon, valid, p95, anchor_speed, anchor_lat,
    anchor_lon] with float lanes bitcast — the sum lanes are per-group
    RESIDUAL sums about the anchor lanes (engine.state.TileState); the
    consumer recombines anchor + resid/count in f64.  The histogram
    itself stays on device — its p95 summary is computed here.
    ``unpack_emit`` reverses it host-side.
    """
    bc = lambda a: jax.lax.bitcast_convert_type(a, jnp.uint32)
    E = emit.key_hi.shape[0]
    if emit.hist.shape[1] > 0:
        p95 = p95_from_hist_device(emit.hist, emit.count, speed_hist_max)
    else:
        p95 = jnp.zeros((E,), jnp.float32)
    body = jnp.stack([
        emit.key_hi,
        emit.key_lo,
        bc(emit.key_ws),
        bc(emit.count),
        bc(emit.sum_speed),
        bc(emit.sum_speed2),
        bc(emit.sum_lat),
        bc(emit.sum_lon),
        emit.valid.astype(jnp.uint32),
        bc(p95),
        bc(emit.anchor_speed),
        bc(emit.anchor_lat),
        bc(emit.anchor_lon),
    ], axis=1)
    head = jnp.zeros((1, body.shape[1]), jnp.uint32)
    head = head.at[0, 0].set(emit.n_emitted.reshape(()).astype(jnp.uint32))
    head = head.at[0, 1].set(emit.overflowed.reshape(()).astype(jnp.uint32))
    return jnp.concatenate([head, body], axis=0)


_STATS_RIDER_SLOT0 = 2  # first head-row slot available to ride_stats


def ride_stats(packed: jnp.ndarray, stats) -> jnp.ndarray:
    """Embed a NamedTuple of int32 scalars into the packed head row.

    The single definition of the stats-rider layout: fields land in head
    slots 2..2+len(stats), in field order, bitcast to uint32.  Decode with
    ``read_stats_rider`` using a host NamedTuple with the SAME fields in
    the same order.
    """
    n = len(stats)
    if _STATS_RIDER_SLOT0 + n > packed.shape[1]:
        raise ValueError(f"stats rider of {n} fields does not fit the "
                         f"{packed.shape[1]}-slot head row")
    svec = jax.lax.bitcast_convert_type(
        jnp.stack(list(stats)).astype(jnp.int32), jnp.uint32)
    return packed.at[0, _STATS_RIDER_SLOT0:_STATS_RIDER_SLOT0 + n].set(svec)


def read_stats_rider(packed_np, cls):
    """Host-side inverse of ``ride_stats``: decode ``cls`` (a NamedTuple
    type of ints, fields ordered as the device-side stats tuple) from a
    packed matrix's head row."""
    import numpy as np

    n = len(cls._fields)
    raw = np.asarray(packed_np)[0, _STATS_RIDER_SLOT0:_STATS_RIDER_SLOT0 + n]
    return cls(*[int(v) for v in raw.view(np.int32)])


def unpack_emit(packed) -> dict:
    """Host-side inverse of pack_emit: dict of numpy arrays + scalars."""
    import numpy as np

    p = np.asarray(packed)
    body = p[1:]
    f32 = lambda col: body[:, col].view(np.float32)
    return {
        "key_hi": body[:, 0],
        "key_lo": body[:, 1],
        "key_ws": body[:, 2].view(np.int32),
        "count": body[:, 3].view(np.int32),
        "sum_speed": f32(4),
        "sum_speed2": f32(5),
        "sum_lat": f32(6),
        "sum_lon": f32(7),
        "valid": body[:, 8] != 0,
        "p95": f32(9),
        "anchor_speed": f32(10),
        "anchor_lat": f32(11),
        "anchor_lon": f32(12),
        "n_emitted": int(p[0, 0]),
        "overflowed": bool(p[0, 1]),
    }


def aggregate_batch(
    state: TileState,
    lat_rad,
    lng_rad,
    speed_kmh,
    ts_s,
    valid,
    watermark_cutoff,
    params: AggParams,
):
    """Convenience: snap + window + merge in one call (used by stream/)."""
    hi, lo, ws = snap_and_window(lat_rad, lng_rad, ts_s, valid, params)
    lat_deg = lat_rad * (180.0 / jnp.pi)
    lon_deg = lng_rad * (180.0 / jnp.pi)
    return merge_batch(
        state, hi, lo, ws, speed_kmh, lat_deg, lon_deg, ts_s, valid,
        watermark_cutoff, params,
    )

def pull_packed_stack(packed, prefix: bool) -> list:
    """Device->host pull of a stacked packed-emit array ((P, E+1, L)
    uint32 — one (E+1, L) block per pair/batch) as a list of P host
    matrices.  THE single implementation of the transfer discipline
    (stream.runtime and bench.py both route here).

    ``prefix=False``: one full transfer.  ``prefix=True``: the P head
    rows first (they carry n_emitted + the stats rider), then one shared
    live-prefix bucket — max n_emitted across blocks rounded up to a
    power of two, so at most log2(E) slice shapes ever compile.  Live
    emit rows are a prefix by construction (pack_emit's nonzero() yields
    ascending indices with the fill at the tail) and rows inside the
    bucket past a block's own n_emitted carry valid=0, so every consumer
    (unpack_emit, packed_tile_docs, the C++ encoder) works unchanged.

    Off the CPU the prefix pull moves far fewer bytes once emit capacity
    dwarfs the touched-group count — the streaming steady state — at the
    price of one more round trip.  On CPU the full pull is cheaper (an
    extra round trip with nothing to save).
    """
    import numpy as np

    if not prefix:
        b = np.asarray(packed)
        return [b[i] for i in range(b.shape[0])]
    heads = np.asarray(packed[:, 0, :])             # (P, L) tiny
    E = packed.shape[1] - 1
    n_max = int(heads[:, 0].astype(np.int64).max())
    bucket = 1
    while bucket < n_max and bucket < E:
        bucket <<= 1
    bucket = min(bucket, E)                          # overflow: n > E
    body = np.asarray(packed[:, 1:1 + bucket, :])
    return [np.concatenate([heads[i:i + 1], body[i]])
            for i in range(body.shape[0])]


def pull_emit_prefix(packed):
    """Live-prefix pull of ONE packed emit matrix ((E+1, L) uint32) —
    the single-block view of ``pull_packed_stack``."""
    return pull_packed_stack(packed[None], prefix=True)[0]


class EmitRing:
    """Fixed-capacity accumulator of DEVICE-RESIDENT packed emits.

    Each ``append`` parks one batch's stacked packed-emit matrix
    ((P, E+1, L) uint32, stats ridden in the head rows) on device; a
    ``flush_stacked`` concatenates every parked batch in ONE eager device
    op and crosses the device->host link with a single
    ``pull_packed_stack`` call — so K batches pay one pull's round trips
    instead of K.  While
    entries sit in the ring the device runs ahead unforced: nothing
    synchronizes on batch k's fold until the flush that covers it.

    Entries must share one shape — the owner flushes before any slab /
    emit-capacity resize (``append`` refuses a mismatched shape loudly
    rather than corrupting the stack).  ``take`` hands the raw entries
    back un-pulled for callers with their own transfer discipline (the
    sharded path pulls addressable shards per entry).

    Per-mesh-shard rings (the partitioned mesh fast path keeps ONE ring
    per device) additionally distinguish LIVE entries (batches that fed
    the shard rows) from idle ones (empty dispatches parked only so
    their eviction emits and stats are never dropped): ``full`` triggers
    on the live count, so a hot shard's flush cadence is its own and an
    idle shard holds its (empty) entries until a forced flush — its
    device→host pull count stays at the idle-flush floor.  Idle entries
    still bound memory: past ``8 * capacity`` total parked entries the
    ring reads full regardless of liveness.
    """

    def __init__(self, capacity: int):
        self.capacity = max(1, int(capacity))
        self._entries: list = []      # (packed_device, tag) append order
        self._enter: list = []        # (monotonic enter, append seq, live)
        self._appends = 0             # lifetime appends (residency base)
        self.live_pending = 0         # parked entries appended live=True
        self.n_flushes = 0            # pulls issued (telemetry)
        # residency of the entries the LAST take()/flush_stacked()
        # drained, aligned with its return order: (seconds parked,
        # batches resident — appends from the entry's own, inclusive, to
        # the flush; the oldest entry of a K-deep flush reads K).  The
        # stream runtime feeds these into the
        # heatmap_emit_ring_residency_* histograms and the freshness
        # lineage (obs.lineage) right after each flush.
        # ``last_flush_live`` is the aligned per-entry live flag.
        self.last_flush_residency: list = []
        self.last_flush_live: list = []

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def full(self) -> bool:
        return (self.live_pending >= self.capacity
                or len(self._entries) >= 8 * self.capacity)

    @property
    def oldest_tag(self):
        """Tag of the oldest parked entry (None when empty)."""
        return self._entries[0][1] if self._entries else None

    @property
    def nbytes(self) -> int:
        """Bytes of packed emits currently parked on device — the ring
        slab's share of HBM (obs.runtimeinfo memory telemetry).  All
        entries share one shape, so this is len * entry-bytes.  Reads a
        local snapshot: the scrape thread races the step thread's
        take(), and a swap between the check and the index must not
        turn the gauge sample into an error."""
        entries = self._entries
        if not entries:
            return 0
        return len(entries) * int(entries[0][0].nbytes)

    def append(self, packed, tag=None, live: bool = True) -> bool:
        """Park one batch's packed emits; True when the ring is full
        (flush before the next append).  ``live=False`` marks an empty
        dispatch (no input rows for this shard): it parks — eviction
        emits and stats riding it must still be pulled eventually — but
        does not advance the flush trigger (per-mesh-shard flush
        independence)."""
        if self._entries and tuple(packed.shape) != tuple(
                self._entries[0][0].shape):
            raise ValueError(
                f"emit ring entries must share one shape "
                f"(got {tuple(packed.shape)} vs "
                f"{tuple(self._entries[0][0].shape)}); flush before a "
                f"slab/emit-capacity resize")
        self._appends += 1
        self._entries.append((packed, tag))
        self._enter.append((time.monotonic(), self._appends, live))
        if live:
            self.live_pending += 1
        return self.full

    def take(self) -> list:
        """Drain the raw (packed, tag) entries without pulling."""
        entries, self._entries = self._entries, []
        enters, self._enter = self._enter, []
        self.live_pending = 0
        if entries:
            self.n_flushes += 1
            now = time.monotonic()
            self.last_flush_residency = [
                (now - t, self._appends - seq + 1)
                for t, seq, _live in enters]
            # aligned liveness flags: residency TELEMETRY should only
            # describe real data batches — an idle mesh shard's empty
            # entries park ~8x longer than any live batch and would
            # dominate the histograms (the caller filters on this)
            self.last_flush_live = [live for _t, _s, live in enters]
        else:
            self.last_flush_residency = []
            self.last_flush_live = []
        return entries

    def flush_stacked(self, prefix: bool) -> list:
        """Pull every parked batch in one transfer.

        Returns [(bufs, tag)] in append order, where ``bufs`` is the
        per-pair list of host matrices ``pull_packed_stack`` would have
        produced for that batch alone — consumers (unpack_emit,
        stats_from_packed, packed_tile_docs) are unchanged.
        """
        entries = self.take()
        if not entries:
            return []
        if len(entries) == 1:
            packed, tag = entries[0]
            return [(pull_packed_stack(packed, prefix), tag)]
        import jax.numpy as jnp

        n_pairs = entries[0][0].shape[0]
        blocks = jnp.concatenate([p for p, _ in entries], axis=0)
        bufs = pull_packed_stack(blocks, prefix)
        return [(bufs[i * n_pairs:(i + 1) * n_pairs], tag)
                for i, (_, tag) in enumerate(entries)]
