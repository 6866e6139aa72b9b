"""Offsets + device-state checkpointing (replaces Spark's checkpointLocation).

The reference delegates offsets and windowed-aggregation state to Spark's
checkpoint directory (reference: heatmap_stream.py:37,244; resume semantics
SURVEY.md §5.4).  Here the framework owns both.

Atomicity: every commit writes a fresh ``commit-<epoch>/`` directory holding
``meta.json`` (source offset, watermark high-ts, epoch) plus one
``state-<res>-<win>.npz`` per configured (resolution, window) pair, then
atomically renames the single ``LATEST`` pointer file at it.  A crash at any
point leaves LATEST referencing a complete older commit — offsets and state
can never be torn against each other (a torn pair would double-count
replayed events into restored state).  Older commit dirs are pruned after
the pointer moves.

Commit ordering (SURVEY.md §7 hard part #5): the runtime drains the sink
writer *before* committing, so a crash replays only events whose upserts
are idempotent by deterministic _id — same correctness backstop the
reference relies on (heatmap_stream.py:173,188).
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Any

import numpy as np

from heatmap_tpu.engine.state import TileState

KEEP_COMMITS = 2  # current + previous, for post-mortem debugging


class CheckpointManager:
    def __init__(self, directory: str):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)
        self.latest_path = os.path.join(directory, "LATEST")

    def _commit_dir(self) -> str | None:
        if not os.path.exists(self.latest_path):
            return None
        with open(self.latest_path, encoding="utf-8") as fh:
            name = fh.read().strip()
        path = os.path.join(self.dir, name)
        return path if os.path.isdir(path) else None

    # --- read -----------------------------------------------------------
    def load_meta(self) -> dict | None:
        """Latest commit's meta."""
        d = self._commit_dir()
        if d is None:
            return None
        with open(os.path.join(d, "meta.json"), encoding="utf-8") as fh:
            return json.load(fh)

    def load_state(self, res: int, win: int) -> TileState | None:
        d = self._commit_dir()
        if d is None:
            return None
        path = os.path.join(d, f"state-{res}-{win}.npz")
        if not os.path.exists(path):
            return None
        with np.load(path) as z:
            missing = [k for k in TileState._fields if k not in z.files]
            if missing:
                # pre-anchor checkpoints hold ABSOLUTE sums; the current
                # state holds residual sums about per-group anchors that
                # an old snapshot simply doesn't have — synthesizing them
                # would corrupt every resumed average, so refuse loudly
                raise ValueError(
                    f"checkpoint {path} was written by an older state "
                    f"layout (missing {missing}); it cannot be resumed by "
                    f"this version — restart from empty state (the sink "
                    f"is idempotent) or replay with the writing version")
            return TileState(**{k: z[k] for k in TileState._fields})

    def load_extra(self, name: str) -> dict | None:
        """A named extras payload committed alongside the window state
        (``extra-<name>.npz``), or None when the commit predates it —
        e.g. the inference engine's entity table (infer.engine).  Extras
        are auxiliary: absence never blocks a resume."""
        d = self._commit_dir()
        if d is None:
            return None
        path = os.path.join(d, f"extra-{name}.npz")
        if not os.path.exists(path):
            return None
        with np.load(path) as z:
            return {k: z[k] for k in z.files}

    # --- write ----------------------------------------------------------
    def commit(self, offset: Any, max_event_ts: int, epoch: int,
               states: dict[tuple[int, int], TileState] | None = None,
               shards: int | None = None,
               snap_impl: str | None = None,
               mesh_mode: str | None = None,
               extras: dict[str, dict] | None = None) -> None:
        """``shards``: the writer's local shard-block count.  Recorded so
        a restart can tell a capacity change (absorbable: pad/grow) from a
        shard-count change (NOT absorbable: rows would be reinterpreted as
        the wrong shard blocks and keys would land off their owner).

        ``snap_impl``: the H3 snap implementation ("native" host C++ vs
        "xla" in-program) that keyed the checkpointed state.  The two
        agree everywhere except f32-rounded points lying exactly on a
        cell edge, so a resume pins the same impl (runtime._maybe_resume)
        rather than letting a backend failover re-key edge events
        mid-stream (ADVICE r4 #1).

        ``mesh_mode``: how the shard blocks were KEYED on a mesh run —
        "partitioned" (H3 parent cell, parallel.PartitionedAggregator).
        Older checkpoints may say "shuffle" (a key hash, from a mesh
        path since removed): same shape, different key ownership, so
        the resume refuses them (stream.runtime._maybe_resume).

        ``extras``: named auxiliary payloads ({name: {key: array}}) —
        reducer state riding the same atomic commit as the window state
        it must stay consistent with (torn against each other, a resume
        would re-fold replayed batches into already-folded filter
        state)."""
        name = f"commit-{epoch:012d}"
        cdir = os.path.join(self.dir, name)
        tmp = cdir + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        for (res, win), st in (states or {}).items():
            np.savez(os.path.join(tmp, f"state-{res}-{win}.npz"),
                     **{k: np.asarray(v) for k, v in st._asdict().items()})
        for ename, payload in (extras or {}).items():
            np.savez(os.path.join(tmp, f"extra-{ename}.npz"),
                     **{k: np.asarray(v) for k, v in payload.items()})
        meta = {"offset": offset, "max_event_ts": int(max_event_ts),
                "epoch": int(epoch)}
        if shards is not None:
            meta["shards"] = int(shards)
        if snap_impl is not None:
            meta["snap_impl"] = snap_impl
        if mesh_mode is not None:
            meta["mesh_mode"] = mesh_mode
        with open(os.path.join(tmp, "meta.json"), "w", encoding="utf-8") as fh:
            json.dump(meta, fh)
        shutil.rmtree(cdir, ignore_errors=True)
        os.replace(tmp, cdir)

        # the atomic pointer flip
        ptmp = self.latest_path + ".tmp"
        with open(ptmp, "w", encoding="utf-8") as fh:
            fh.write(name)
        os.replace(ptmp, self.latest_path)
        self._prune(keep=name)

    def _prune(self, keep: str) -> None:
        commits = sorted(
            n for n in os.listdir(self.dir)
            if n.startswith("commit-") and not n.endswith(".tmp")
        )
        for n in commits[:-KEEP_COMMITS]:
            if n != keep:
                shutil.rmtree(os.path.join(self.dir, n), ignore_errors=True)
