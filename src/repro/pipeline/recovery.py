"""Snapshot + WAL-tail recovery: the durability tier's coordinator.

``Durability`` bundles the admission-point WAL (``pipeline/wal.py``) with
periodic index snapshots (``checkpoint.py``) behind two hooks the live
pipeline already exposes:

* ``on_seal(window)``   — the collector's seal hook: one WAL append per
  sealed window, *before* the window is dispatched (write-ahead).
* ``maybe_snapshot(index, seq)`` — called by the dispatcher after each
  submit; every ``snapshot_every`` windows it materializes the index
  pytree via ``CheckpointManager``, stamped with the WAL sequence number
  of the last submitted window, then garbage-collects WAL segments behind
  the oldest *kept* snapshot.

``recover(dir)`` inverts it: load the latest complete snapshot, replay
the WAL tail (``seq > snapshot seq``) through the same ``Dispatcher``
execute path the live system uses — so the recovered state is
bit-identical to never having crashed — and return the index plus the
replayed records.

Contract (DESIGN.md §7): recovery always lands on a window boundary; it
includes every acknowledged window (fsync policy defines acknowledged),
may include a fully-written-but-unacknowledged suffix, and never replays
a torn tail record.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Optional, Tuple

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.core import distributed as dist
from repro.core import index as pi
from repro.pipeline.dispatcher import Dispatcher
from repro.pipeline.wal import (WalRecord, WalWriter, read_wal,
                                record_window)

META_NAME = "durability.json"


class RecoveryError(RuntimeError):
    """The durability directory cannot seed an index: missing metadata or
    no complete snapshot (``Durability`` writes both before acknowledging
    anything, so this means the directory never finished initializing)."""


def _snapshot_tree(index):
    if isinstance(index, dist.ShardedPIIndex):
        return (index.shards, index.fences)
    return index


def _empty_sharded(cfg: pi.PIConfig, n_shards: int, mesh):
    kdt = np.dtype(cfg.key_dtype)
    return dist.build_sharded(cfg, n_shards, np.zeros((0,), kdt),
                              np.zeros((0,), np.int32), mesh=mesh)


class Durability:
    """WAL-on-admission + periodic snapshots for one pipeline's index.

    Creating a ``Durability`` over a fresh directory writes the geometry
    metadata and a blocking step-0 snapshot of ``index`` (the initial
    build — without it a crash before the first periodic snapshot would
    be unrecoverable); over an existing directory it validates the log,
    repairs a torn tail, and resumes sequence numbering — pass the index
    returned by ``recover`` to continue where the crash left off.
    """

    def __init__(self, directory: str, index, *,
                 fsync: str = "per_window", fsync_interval: float = 0.05,
                 snapshot_every: int = 0, keep: int = 3,
                 segment_bytes: int = 1 << 22, metrics=None,
                 async_snapshots: bool = False,
                 group_commit: "int | None" = None):
        self.dir = directory
        self.snapshot_every = snapshot_every
        self.metrics = metrics
        # serving-path mode: periodic maybe_snapshot saves go through the
        # CheckpointManager's background thread instead of blocking the
        # tick.  WAL truncation is deferred until the next save (or close)
        # confirms the previous one landed — truncating behind a snapshot
        # that later fails would lose the only way to rebuild.  Write
        # errors surface via the manager's latched-exception contract at
        # the next save/wait.  The initial step-0 snapshot and explicit
        # snapshot() calls stay blocking regardless.
        self.async_snapshots = async_snapshots
        self._truncate_pending = False
        if isinstance(index, dist.ShardedPIIndex):
            self.kind = "sharded"
            self.n_shards = index.n_shards
            cfg = index.shards.config
        else:
            self.kind = "single"
            self.n_shards = 1
            cfg = index.config
        self.config = cfg
        os.makedirs(directory, exist_ok=True)
        meta_path = os.path.join(directory, META_NAME)
        if not os.path.exists(meta_path):
            tmp = meta_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"kind": self.kind, "n_shards": self.n_shards,
                           "config": dataclasses.asdict(cfg)}, f)
            os.rename(tmp, meta_path)
        self.ckpt = CheckpointManager(os.path.join(directory, "ckpt"),
                                      keep=keep)
        self.wal = WalWriter(os.path.join(directory, "wal"), fsync=fsync,
                             fsync_interval=fsync_interval,
                             segment_bytes=segment_bytes,
                             group_commit=group_commit)
        self._last_snap = self.ckpt.latest_step()
        if self._last_snap is None:
            # nothing acknowledged yet, so a crash inside this initial
            # snapshot is recoverable-by-vacuity; blocking so the first
            # acked window always has a base to replay onto
            self.snapshot(index, seq=self.wal.last_seq)

    @property
    def durable_seq(self) -> int:
        """Last window sequence the fsync policy guarantees on disk."""
        return self.wal.durable_seq

    @property
    def last_snapshot_seq(self) -> Optional[int]:
        return self._last_snap

    # -- live-path hooks ---------------------------------------------------

    def on_seal(self, window) -> int:
        """Collector seal hook: write-ahead append of the sealed window."""
        seq = self.wal.append(window)
        if self.metrics is not None:
            self.metrics.wal_appends += 1
            self.metrics.wal_fsyncs = self.wal.n_fsyncs
        return seq

    def maybe_snapshot(self, index, seq: Optional[int]):
        """Dispatcher post-submit hook: snapshot every N windows."""
        if (self.snapshot_every and seq is not None
                and seq - (self._last_snap or 0) >= self.snapshot_every):
            self.snapshot(index, seq=seq,
                          blocking=not self.async_snapshots)

    def snapshot(self, index, *, seq: Optional[int] = None,
                 blocking: bool = True):
        """Materialize the index pytree, stamped with its WAL position.

        ``seq`` must be the sequence number of the last window already
        applied to ``index`` — recovery replays strictly-greater records
        on top.  After a blocking save the WAL is truncated behind the
        oldest snapshot the checkpoint GC kept; a non-blocking save defers
        both the truncation and its own error surfacing to the next
        save/close (``CheckpointManager.save`` waits for — and re-raises
        from — the previous background save before starting a new one).
        """
        if seq is None:
            seq = self.wal.last_seq
        prev_pending = self._truncate_pending
        self.ckpt.save(seq, _snapshot_tree(index), blocking=blocking,
                       meta={"wal_seq": seq, "kind": self.kind})
        self._last_snap = seq
        if blocking:
            self._truncate()
        else:
            if prev_pending:
                # save() joined the previous background save (and would
                # have re-raised its failure), so the snapshot that
                # deferred this truncation is confirmed on disk; the save
                # now in flight is invisible to all_steps() until its
                # manifest lands, so it cannot be truncated against
                self._truncate()
            self._truncate_pending = True

    def _truncate(self):
        """GC WAL segments behind the oldest kept snapshot."""
        self._truncate_pending = False
        steps = self.ckpt.all_steps()
        if steps:
            self.wal.truncate_through(min(steps))

    def close(self):
        self.ckpt.wait()
        if self._truncate_pending:
            # the wait() above confirmed every async save landed, so the
            # deferred truncation is safe now
            self._truncate()
        self.wal.close()


def recover(directory: str, *, mesh=None, metrics=None, overload=None
            ) -> Tuple[object, List[WalRecord]]:
    """Rebuild the index from the latest snapshot + the WAL tail.

    Returns ``(index, replayed)`` where ``index`` is a ``PIIndex`` or
    ``ShardedPIIndex`` (per the directory's metadata) and ``replayed``
    lists the ``WalRecord``s applied on top of the snapshot, in order.
    The replay goes through ``Dispatcher.submit`` — the identical jitted
    execute+rebuild program the live pipeline ran — so the result is
    bit-identical to the pre-crash state at the last durable window.

    Raises ``RecoveryError`` when the directory has no metadata or no
    complete snapshot, and ``WalCorruptionError`` on interior log damage
    (a torn tail is repaired-by-exclusion, not an error).

    ``overload`` (an ``OverloadConfig``) arms the replay dispatcher's
    circuit breaker.  The default ``None`` keeps the bit-identical
    guarantee unconditionally; with a breaker, a replay that trips it is
    recovered the same way the live run would have been — logically
    identical (same results, same logical contents), byte-identical only
    when the live run tripped at the same windows.
    """
    meta_path = os.path.join(directory, META_NAME)
    if not os.path.exists(meta_path):
        raise RecoveryError(f"no {META_NAME} in {directory}")
    with open(meta_path) as f:
        meta = json.load(f)
    cfg = pi.PIConfig(**meta["config"])
    kind = meta["kind"]
    n_shards = int(meta.get("n_shards", 1))

    ckpt = CheckpointManager(os.path.join(directory, "ckpt"))
    step = ckpt.latest_step()
    if step is None:
        raise RecoveryError(
            f"no complete snapshot under {directory}/ckpt — the initial "
            f"blocking snapshot never finished, so nothing was ever "
            f"acknowledged")
    if kind == "sharded":
        if mesh is None:
            mesh = jax.make_mesh((n_shards,), ("data",))
        # each shard's block goes from disk straight to its own device
        target = _snapshot_tree(_empty_sharded(cfg, n_shards, mesh))
        shards, fences = ckpt.restore(
            step, target,
            shardings=jax.tree.map(lambda x: x.sharding, target))
        index = dist.ShardedPIIndex(shards=shards, fences=fences,
                                    n_shards=n_shards)
    else:
        index = ckpt.restore(step, pi.empty(cfg))

    tail = [r for r in read_wal(os.path.join(directory, "wal"))
            if r.seq > step]
    disp = Dispatcher(index, mesh=mesh, depth=0, overload=overload)
    for rec in tail:
        disp.submit(record_window(rec))
    if metrics is not None:
        metrics.recovery_replayed += len(tail)
    return disp.index, tail
