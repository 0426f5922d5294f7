"""Unified checkpoint plane: one ``Checkpointer`` protocol, one manager.

Before this module existed the repo had four checkpointer implementations
with incompatible interfaces (store/incremental/multilevel/async) and the
trainer, simulator and controller each only knew the plain full-snapshot
path.  ``CheckpointManager`` composes those pieces as *layers* behind a
single protocol, configured by ``config.CheckpointPlan``:

        trigger            CheckpointPolicy.due(t)   (the Khaos CI knob)
           |
        snapshot           chunked D2H transfer (pipeline.ChunkedHost-
           |               Snapshot): mutable host leaves copy eagerly,
           |               device chunks stream on the transfer pool —
           |               only the first chunk's device sync blocks
           |
        encode             full snapshot, or delta vs the last full
           |                 (lossless sub+XOR-residual or int8, both with
           |                  a kernels/ckpt_delta Pallas codec and its
           |                  ref.py host oracle), leaf-parallel on the
           |                  io pool, overlapped with the D2H stream;
           |                  unchanged leaves short-circuit to a "zero"
           |                  manifest marker.
           |               plan.encode_placement == "device" swaps the
           |                 order of the two stages above: ONE fused
           |                 Pallas kernel encodes the packed f32 subtree
           |                 against the device-resident flat base
           |                 (pipeline.DeltaLeafSource) and only the
           |                 encoded payload crosses the link — bytes_on_-
           |                 link drops to ~0.26x state bytes for int8
           |
        compress           zstd, or zlib when the plan names it; the codec
           |                 used is recorded in the delta manifest
           |
        level routing      memory  — in-RAM snapshot, every trigger
           |               local   — node-local store, every local_every-th
           |               remote  — durable store, every remote_every-th
           |                 (remote only ever receives FULL snapshots;
           |                  deltas stay with their base full's level)
           |
        commit             sync (blocks the step stream) or async via a
                           BackgroundCommitter (double-buffered, at most
                           one write in flight, skip/block busy policy);
                           shards write concurrently on the io pool either
                           way

    restore(treedef, failure_kind) walks the levels that survive the
    failure kind (multilevel.LEVEL_COVERAGE) newest-step-first, applies
    the newest matching delta on top of its base full, and reports which
    (level, kind) served the recovery — the controller prices exactly this
    path when it optimizes over plans.

Every save/restore returns a report carrying bytes + durations so the
trainer's metrics, the simulator's cost model and ``bench_ckpt`` all
account the same quantities.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import numpy as np

from repro.checkpoint.async_ckpt import BackgroundCommitter
from repro.checkpoint.incremental import (apply_delta, delta_dir,
                                          newest_delta_step,
                                          read_delta_manifest, write_delta)
from repro.checkpoint.multilevel import allowed_levels
from repro.checkpoint.pipeline import (ChunkedHostSnapshot, DeltaLeafSource,
                                       DeviceDeltaBase, PlainLeafSource,
                                       prepare_device_encode)
from repro.checkpoint.policy import CheckpointPolicy
from repro.checkpoint.replication import PeerReplicatedStore
from repro.checkpoint.store import CheckpointStore
from repro.config import CheckpointPlan
from repro.utils.timing import span
from repro.utils.trees import tree_bytes


@dataclass
class SaveReport:
    """What one save() actually did (all byte/duration accounting flows
    from here into metrics, the simulator calibration and benchmarks)."""
    step: int
    kind: str                       # full | delta | skipped
    levels: tuple = ()              # levels written this trigger
    bytes_written: int = 0          # post-compression bytes on disk
    bytes_on_link: int = 0          # pre-compression post-encode bytes the
                                    # trigger moved device->host — raw state
                                    # for host-encode paths, encoded payload
                                    # for device-encode deltas; the quantity
                                    # bench_ckpt/2 and the cost model price,
                                    # NOT the same thing as bytes_written
    duration_s: float = 0.0         # total write work (wall)
    blocking_s: float = 0.0         # portion that blocked the caller
    encode_s: float = 0.0           # delta encode+compress CPU seconds
    paths: tuple = ()
    synchronous: bool = True

    def __bool__(self) -> bool:     # truthy iff something was persisted
        return self.kind != "skipped"


@dataclass
class RestoreReport:
    state: Any
    step: int
    level: str                      # memory | local | remote
    kind: str                       # memory | full | full+delta
    duration_s: float
    extra: dict = field(default_factory=dict)
    degraded: bool = False          # a degraded partial restore: some shard
                                    # was rebuilt from peer replicas (or the
                                    # per-shard remote fallback)
    restored_bytes: int = 0         # bytes PULLED to rebuild dead shards —
                                    # the recovery-drill gate compares this
                                    # against the full checkpoint size
    fallbacks: int = 0              # fulls that failed their load (a
                                    # missing or corrupt shard) before the
                                    # restored one loaded whole


@runtime_checkable
class Checkpointer(Protocol):
    """The one interface all three planes (trainer, simulator cost
    accounting, controller) talk to."""

    def save(self, step: int, state: Any, timestamp: float = 0.0,
             extra: Optional[dict] = None) -> SaveReport: ...

    def restore(self, treedef_like: Any,
                failure_kind: str = "task") -> RestoreReport: ...

    def stats(self) -> dict: ...


class CheckpointManager:
    """Executes a ``CheckpointPlan``; the single checkpoint entry point."""

    def __init__(self, directory: str, plan: CheckpointPlan,
                 policy: Optional[CheckpointPolicy] = None):
        self.directory = directory
        self.plan = plan
        self.policy = policy or CheckpointPolicy(plan.interval_s)
        os.makedirs(directory, exist_ok=True)
        self.stores: dict[str, CheckpointStore] = {}
        for level in plan.disk_levels:
            if level == "local" and plan.effective_replication >= 1:
                # the replicated level-2 store: each host pushes its shard
                # to k ring peers, so a node loss is survivable HERE — the
                # survival rule the cost model derives from the same k
                self.stores[level] = PeerReplicatedStore(
                    os.path.join(directory, level),
                    num_shards=plan.num_shards, keep=plan.keep,
                    replication_factor=plan.effective_replication)
            else:
                self.stores[level] = CheckpointStore(
                    os.path.join(directory, level),
                    num_shards=plan.num_shards, keep=plan.keep)
        # first disk level is the primary: it anchors the delta chain
        self.primary_level: Optional[str] = (plan.disk_levels[0]
                                             if plan.disk_levels else None)
        self._memory: Optional[tuple[int, Any, dict]] = None   # newest only
        self._base: Optional[Any] = None       # last full snapshot (host)
        self._base_step: Optional[int] = None
        # device-resident twin of the host base (plan.encode_placement ==
        # "device"): immutable references to the last full's device leaves,
        # refreshed on every full trigger/savepoint so delta triggers can
        # encode on device without a host round trip
        self._device_base: Optional[DeviceDeltaBase] = None
        self._count = 0
        self._committer = (None if plan.sync
                           else BackgroundCommitter(plan.busy_policy))
        # accounting
        self.link_bytes = 0           # pre-compression post-encode (D2H)
        self.bytes_by_kind = {"full": 0, "delta": 0}
        self.saves_by_level = {l: 0 for l in ("memory", "local", "remote")}
        self.skips = 0
        self.savepoints = 0
        self.late_saves = 0           # triggers landing past their cadence
        self.late_by_s = 0.0          # slot, and by how much in total — a
                                      # backpressured trigger widens the
                                      # lost-work window the controller's
                                      # CI assumption prices, so the slip
                                      # is measured rather than silent
        self.restores: list[tuple[int, str, str]] = []

    def _mark_trigger(self, timestamp: float) -> None:
        """Advance the cadence clock, accounting how late the trigger ran
        relative to the slot that made it due (regular triggers only —
        ``savepoint`` is cadence-exempt and marks directly)."""
        slot = self.policy.next_due(timestamp)
        slip = timestamp - slot
        # polling quantization lands every trigger a little past its slot;
        # only a slip a controller could care about (5% of the interval)
        # counts as late — backpressure windows exceed this by design
        if slip > 0.05 * self.policy.interval_s:
            self.late_saves += 1
            self.late_by_s += slip
        self.policy.mark(timestamp)

    # -- save ---------------------------------------------------------------
    def prepare(self, state: Any) -> None:
        """Compile ahead of time the device programs this plan's triggers
        run on states shaped like ``state``, so no trigger's blocking time
        includes a compile.  A job calls it before its clock starts."""
        if self.plan.encode_placement == "device":
            prepare_device_encode(state, self.plan.delta_codec)

    def _kind(self) -> str:
        if self._base is None:     # no live base: the chain must restart
            return "full"
        return "full" if self.plan.is_full_trigger(self._count) else "delta"

    def save(self, step: int, state: Any, timestamp: float = 0.0,
             extra: Optional[dict] = None) -> SaveReport:
        with span("khaos.ckpt.save", step=step) as sp:
            return self._save(sp.id, step, state, timestamp, extra or {})

    def _save(self, save_id: int, step: int, state: Any, timestamp: float,
              extra: dict) -> SaveReport:
        if self._committer is not None:
            with span("khaos.ckpt.commit_wait"):
                if self._committer.busy:
                    if self.plan.busy_policy == "skip":
                        self.skips += 1
                        # the trigger happened; cadence moves on
                        self._count += 1
                        self._mark_trigger(timestamp)
                        return SaveReport(step, "skipped", synchronous=False)
                    self._committer.wait()

        t0 = time.monotonic()
        kind = self._kind()
        levels = [l for l, _ in self.plan.levels_due(self._count)
                  if l == "memory" or l in self.stores]
        # a real copy when the snapshot outlives this call (async write in
        # flight, or parked at the memory level / as the delta base) —
        # aliasing host arrays the caller may mutate would corrupt it.
        # ChunkedHostSnapshot copies only the mutable host leaves up front;
        # immutable device chunks stream to the io workers in background,
        # so blocking_s is the first chunk's device sync, not the full copy.
        # plan.eager_snapshot disables the deferral (donated-buffer states:
        # the "immutable" device arrays are re-used by the next step)
        need_copy = (self._committer is not None or "memory" in levels
                     or self.plan.mode == "incremental")
        device_delta = (kind == "delta"
                        and self.plan.encode_placement == "device"
                        and self._device_base is not None)
        with span("khaos.ckpt.snapshot"):
            if device_delta:
                # encode in front of D2H: only the encoded payload crosses
                # the link; raw leaves stay lazily reachable (memory-level
                # parking, delta-upgraded-to-full self-heal) through
                # immutable refs
                snap = DeltaLeafSource(state, self._device_base,
                                       codec=self.plan.delta_codec,
                                       chunk_bytes=self.plan.chunk_bytes)
            else:
                snap = (ChunkedHostSnapshot(
                            state, self.plan.chunk_bytes,
                            defer_device=not self.plan.eager_snapshot)
                        if need_copy else PlainLeafSource(state))
            if kind == "full" and self.plan.encode_placement == "device":
                self._device_base = DeviceDeltaBase(state)
        if "memory" in levels:
            # the memory level always holds the decoded newest state (as a
            # possibly-still-transferring snapshot source) — a task restart
            # restores from RAM without touching the codec path
            self._memory = (step, snap, dict(extra))
            self.saves_by_level["memory"] += 1
        if kind == "full":
            self._base, self._base_step = snap, step
        base, base_step = self._base, self._base_step
        self._count += 1

        disk = [l for l in levels if l in self.stores]
        report = SaveReport(step, kind, tuple(levels), synchronous=self._committer is None)

        def commit() -> None:
            with span("khaos.ckpt.commit", step=step,
                      parent=save_id) as csp:
                nbytes, paths, encode_s = 0, [], 0.0
                for level in disk:
                    store = self.stores[level]
                    # remote only ever receives fulls; a delta whose base full
                    # is missing at a level would be unrestorable there
                    write_full = (kind == "full" or level == "remote"
                                  or store.newest() != base_step)
                    if write_full:
                        paths.append(store.save(step, snap, timestamp,
                                                {**extra, "kind": "full"}))
                        n = store.total_bytes(step)
                        nbytes += n
                        self.bytes_by_kind["full"] += n
                    else:
                        p, n, enc = write_delta(store.directory, step, snap,
                                                base, base_step, timestamp,
                                                extra,
                                                self.plan.delta_codec,
                                                self.plan.codec)
                        paths.append(p)
                        nbytes += n
                        encode_s += enc
                        self.bytes_by_kind["delta"] += n
                        if isinstance(store, PeerReplicatedStore):
                            # deltas aren't physically replicated (the post-
                            # failure chain restarts from a full) but their
                            # mirror traffic is priced — keep the measured
                            # replica_bytes twin honest
                            store.account_delta_mirror(n)
                    self.saves_by_level[level] += 1
                csp.add(bytes_written=nbytes)
                report.bytes_written = nbytes
                report.bytes_on_link = snap.bytes_on_link()
                self.link_bytes += report.bytes_on_link
                report.encode_s = encode_s
                report.paths = tuple(paths)
                report.duration_s = time.monotonic() - t0

        if self._committer is None:
            commit()
            report.blocking_s = report.duration_s
        else:
            self._committer.submit(commit)
            report.blocking_s = time.monotonic() - t0   # snapshot only
        self._mark_trigger(timestamp)
        return report

    # -- savepoint (cadence-exempt checkpoint-now) ---------------------------
    def savepoint(self, step: int, state: Any, timestamp: float = 0.0,
                  extra: Optional[dict] = None) -> SaveReport:
        """Durable checkpoint-now: drain any in-flight commit, then write a
        FULL snapshot synchronously to EVERY configured level — ignoring
        the every-Nth level cadences, which gate regular triggers only.
        This is the drain barrier under a controlled reconfiguration:
        after it returns, nothing the job has processed can be lost, even
        if the next action discards this manager (a plan switch rebuild).
        Does not advance the trigger count (cadence patterns are
        unaffected); does anchor a fresh delta chain at ``step``."""
        with span("khaos.ckpt.save", step=step):
            return self._savepoint(step, state, timestamp, extra or {})

    def _savepoint(self, step: int, state: Any, timestamp: float,
                   extra: dict) -> SaveReport:
        with span("khaos.ckpt.commit_wait"):
            self.wait()
        t0 = time.monotonic()
        with span("khaos.ckpt.snapshot"):
            snap = ChunkedHostSnapshot(
                state, self.plan.chunk_bytes,
                defer_device=not self.plan.eager_snapshot)
            if self.plan.encode_placement == "device":
                # the savepoint anchors a fresh delta chain; refresh the
                # device-resident base so post-drain deltas encode
                # against it
                self._device_base = DeviceDeltaBase(state)
        levels = []
        if "memory" in self.plan.levels:
            self._memory = (step, snap, dict(extra))
            self.saves_by_level["memory"] += 1
            levels.append("memory")
        self._base, self._base_step = snap, step
        nbytes, paths = 0, []
        with span("khaos.ckpt.commit", step=step) as csp:
            for level, store in self.stores.items():
                paths.append(store.save(step, snap, timestamp,
                                        {**extra, "kind": "full"}))
                n = store.total_bytes(step)
                nbytes += n
                self.bytes_by_kind["full"] += n
                self.saves_by_level[level] += 1
                levels.append(level)
            csp.add(bytes_written=nbytes)
        self.savepoints += 1
        self.policy.mark(timestamp)
        dur = time.monotonic() - t0
        self.link_bytes += snap.bytes_on_link()
        return SaveReport(step, "full", tuple(levels), nbytes,
                          bytes_on_link=snap.bytes_on_link(),
                          duration_s=dur, blocking_s=dur,
                          paths=tuple(paths), synchronous=True)

    # -- restore ------------------------------------------------------------
    def _remote_steps(self) -> tuple[int, ...]:
        remote = self.stores.get("remote")
        return tuple(remote.list_steps()) if remote is not None else ()

    def _disk_candidate(self, level: str) -> Optional[tuple[int, int]]:
        """(restore_step, base_full_step) for a disk level, or None."""
        store = self.stores.get(level)
        if store is None:
            return None
        if isinstance(store, PeerReplicatedStore):
            # a degraded step (some shards only on replicas, or coverable
            # per-shard by the remote store AT THE SAME STEP) still counts
            full = store.newest_restorable(self._remote_steps())
        else:
            full = store.newest()
        if full is None:
            return None
        dstep = newest_delta_step(store.directory)
        if dstep is not None and dstep > full:
            meta = read_delta_manifest(store.directory, dstep)
            if meta is not None and meta["base_step"] == full:
                return dstep, full
        return full, full

    def restore(self, treedef_like: Any,
                failure_kind: str = "task") -> RestoreReport:
        with span("khaos.ckpt.restore") as sp:
            with span("khaos.ckpt.restore_wait"):
                self.wait()
            report = self._restore(treedef_like, failure_kind)
            sp.step = report.step
            sp.add(state_bytes=tree_bytes(report.state),
                   fallbacks=report.fallbacks)
        return report

    def _manifest_candidates(self, level: str, rejected: set
                             ) -> list[tuple[int, int]]:
        """(restore_step, base_full_step) for each full of a plain level
        that has a manifest and is not in ``rejected``, newest full first:
        matched with the level's newest delta as ``_disk_candidate`` does.
        No shard is read; the load checks them."""
        store = self.stores[level]
        dstep = newest_delta_step(store.directory)
        meta = (read_delta_manifest(store.directory, dstep)
                if dstep is not None else None)
        base = meta["base_step"] if meta is not None else None
        return [(dstep if full == base and dstep > full else full, full)
                for full in reversed(store.manifest_steps())
                if full not in rejected]

    def _restore(self, treedef_like: Any, failure_kind: str) -> RestoreReport:
        t0 = time.monotonic()
        allowed = allowed_levels(failure_kind,
                                 self.plan.effective_replication)
        speed = {"memory": 2, "local": 1, "remote": 0}
        levels = [l for l in ("local", "remote")
                  if l in allowed and l in self.stores]
        # each level's candidate is chosen once: a replicated level's from
        # its validated steps (a degraded step still counts); a plain
        # level's from its manifests, confirmed by loading its full, which
        # reads and checks each shard once.  A full that fails its load is
        # rejected and the choice made again, as if it were not on disk;
        # a level counts at the best its unrejected fulls could give until
        # its newest one has loaded
        chosen = {l: self._disk_candidate(l) for l in levels
                  if isinstance(self.stores[l], PeerReplicatedStore)}
        loaded: dict[str, tuple[Any, dict]] = {}
        rejected: dict[str, set] = {l: set() for l in levels}
        fallbacks = 0
        while True:
            pending = {l: self._manifest_candidates(l, rejected[l])
                       for l in levels if l not in chosen}
            candidates = [(c[0], speed[l], l) for l, c in chosen.items()
                          if c is not None]
            candidates += [(max(r for r, _ in c), speed[l], l)
                           for l, c in pending.items() if c]
            if "memory" in allowed and self._memory is not None:
                candidates.append((self._memory[0], speed["memory"],
                                   "memory"))
            if not candidates:
                raise FileNotFoundError(
                    f"no checkpoint survives a {failure_kind} failure")
            _, _, level = max(candidates)
            if level not in pending:
                break
            restore_step, full_step = pending[level][0]
            try:
                loaded[level] = self.stores[level].restore(treedef_like,
                                                           full_step)
            except FileNotFoundError:
                rejected[level].add(full_step)
                fallbacks += 1
                continue
            chosen[level] = (restore_step, full_step)
        if level == "memory":
            mstep, snap, extra = self._memory
            # deep copy so the caller can't corrupt the parked snapshot
            state = jax.tree_util.tree_map(lambda x: np.array(x, copy=True),
                                           snap.as_pytree())
            report = RestoreReport(state, mstep, "memory", "memory",
                                   time.monotonic() - t0, dict(extra))
        else:
            store = self.stores[level]
            restore_step, full_step = chosen[level]
            degraded, restored_bytes = False, 0
            if level in loaded:
                state, extra = loaded[level]
            else:
                # the replicated store's degraded partial restore: dead
                # shards come from peer replicas, and a shard with NO local
                # copy falls back per-shard to the remote store at the same
                # step
                remote = self.stores.get("remote")
                fallback = remote.read_leaves if remote is not None else None
                state, extra = store.restore(treedef_like, full_step,
                                             shard_fallback=fallback)
                degraded = store.last_restore.get("degraded", False)
                restored_bytes = store.last_restore.get("restored_bytes", 0)
            kind = "full"
            if restore_step > full_step:
                meta = read_delta_manifest(store.directory, restore_step)
                # decode where this plan encodes; blobs are byte-compatible
                # across placements, so a host-written delta restores here
                # and a device-written one restores under a host plan
                with span("khaos.ckpt.apply_delta",
                          step=restore_step) as asp:
                    state = apply_delta(
                        store.directory, restore_step, state,
                        placement=self.plan.encode_placement)
                    asp.add(bytes_read=sum(
                        e.stat().st_size for e in os.scandir(
                            delta_dir(store.directory, restore_step))))
                extra = meta.get("extra", extra)
                kind = "full+delta"
            report = RestoreReport(state, restore_step, level, kind,
                                   time.monotonic() - t0, extra,
                                   degraded=degraded,
                                   restored_bytes=restored_bytes)
        report.fallbacks = fallbacks
        self.restores.append((report.step, report.level, report.kind))
        return report

    # -- lifecycle / failure hooks -----------------------------------------
    def adopt_runtime_state(self, old: "CheckpointManager") -> None:
        """Carry the in-RAM snapshot and delta base over from a manager
        this one replaces (the plan-switch rebuild): the predecessor's
        drain savepoint is the newest state, so task restarts keep their
        RAM path and incremental plans delta against the drained full —
        the invariant lives here, next to the fields it protects.  The
        device-resident delta base rides along, so a plan switch onto (or
        between) device-encode plans deltas against the drained full
        without re-uploading it."""
        self._memory = old._memory
        self._base, self._base_step = old._base, old._base_step
        self._device_base = old._device_base

    def wait(self) -> None:
        """Drain any in-flight async commit."""
        if self._committer is not None:
            self._committer.wait()

    def on_failure(self, failure_kind: str,
                   host: Optional[int] = None) -> None:
        """Apply a failure's destruction to the levels it wipes out.
        A host-targeted node failure (``host`` given) additionally kills
        that host's node-local disk — its primary shards and the replicas
        it held for peers — which is what makes the subsequent restore a
        DEGRADED partial restore instead of a free local read.  With no
        ``host`` the node failure models a process loss whose disk
        survives (the pre-replication semantics, kept for back-compat)."""
        if failure_kind in ("node", "cluster"):
            self._memory = None
            self._base = None     # host RAM gone: next save must be a full
            self._base_step = None
            self._device_base = None   # the device died with the job too
        if failure_kind == "node" and host is not None \
                and "local" in self.stores:
            self.stores["local"].kill_host(host)
        if failure_kind == "cluster" and "local" in self.stores:
            # the sim's cluster failure loses node-local disks too; real
            # deployments re-point the store at an empty scratch dir
            import shutil
            shutil.rmtree(self.stores["local"].directory, ignore_errors=True)
            os.makedirs(self.stores["local"].directory, exist_ok=True)

    def newest_step(self) -> Optional[int]:
        try:
            return self.restore_candidates()[0][0]
        except IndexError:
            return None

    def restore_candidates(self) -> list[tuple[int, str]]:
        """(step, level) restore options, best first (newest, then fastest)."""
        out = []
        if self._memory is not None:
            out.append((self._memory[0], 2, "memory"))
        for level in self.stores:
            cand = self._disk_candidate(level)
            if cand is not None:
                out.append((cand[0], {"local": 1, "remote": 0}[level], level))
        return [(s, l) for s, _, l in sorted(out, reverse=True)]

    def stats(self) -> dict:
        errors = (list(self._committer.errors)
                  if self._committer is not None else [])
        return {
            "saves": self._count,
            "skips": self.skips,
            "savepoints": self.savepoints,
            "late_saves": self.late_saves,
            "late_by_s": self.late_by_s,
            "bytes_by_kind": dict(self.bytes_by_kind),
            "bytes_written": sum(self.bytes_by_kind.values()),
            "bytes_on_link": self.link_bytes,
            "saves_by_level": dict(self.saves_by_level),
            "restores": list(self.restores),
            "async_errors": errors,
            "plan": self.plan.name,
        }
