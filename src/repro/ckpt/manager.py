"""Transparent checkpoint/restart via storage windows.

Implements the paper's fault-tolerance recipe end to end:

* Training state lives in a :class:`WindowedPyTree` whose backing is a
  storage window (user-level page cache, selective sync).
* A checkpoint is paper Listing 4: exclusive lock + ``MPI_Win_sync``.
  ``compare_on_write`` keeps the sync *selective* -- only blocks whose bytes
  actually changed since the window last saw them get flushed.
* **Double buffering** (paper §4, "use two MPI storage windows and swap
  them on each checkpoint"): checkpoints alternate between window A and
  window B, so a crash mid-sync can never corrupt the last good version.
* A manifest (JSON, written atomically via rename) records step, target
  window and per-slot CRC32; restore validates CRCs and falls back to the
  previous manifest if the newest one is torn or mismatched.
* ``save_async`` overlaps the flush with compute: the puts land in the page
  cache synchronously (cheap memcpy), then the expensive storage flush rides
  the window's background :class:`~repro.core.storage.WritebackPool` as a
  ``sync_async`` request whose completion hook commits the manifest.
  ``wait()`` joins the request before the next checkpoint swaps buffers, so
  the flush runs concurrently with the training step in between.
* **Snapshot-diff staging** (``snapshot_diff=True``, the default): the
  manager keeps a host copy of each window's last-checkpointed bytes and
  page-diffs the new state against it.  Each slot is staged as a *shard*:
  its changed pages become byte spans and the per-slot page masks OR-merge
  into one window mask, shipped together through the transport's masked
  span-write primitive (``Window.sync(spans=...)``) -- apply + selective
  flush in a single operation, one control-channel round trip per rank
  under the multiprocess transport; the host-side twin of
  ``Window.sync_shards_from_device``.  If a flush fails, the snapshot for
  that window is invalidated and the backing re-marks the taken blocks, so
  the retry replays a full put + unmasked flush (replay, never skip); the
  manifest hook only ever runs after a *successful* flush, so a crash
  mid-save can never commit a manifest ahead of its data.
"""

from __future__ import annotations

import dataclasses
import json
import os
import zlib
from typing import Any, Mapping

import numpy as np

from repro.core.comm import Communicator
from repro.core.offload import WindowedPyTree
from repro.core.storage import dirty_runs, mark_span
from repro.core.window import Request
from repro.perf.trace import span

__all__ = ["CheckpointManager", "RestoreResult"]

_MANIFEST = "manifest.json"
_MANIFEST_PREV = "manifest.prev.json"


@dataclasses.dataclass
class RestoreResult:
    step: int
    tree: dict[str, np.ndarray]
    manifest: dict[str, Any]
    fell_back: bool = False


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).reshape(-1).view(np.uint8))


class CheckpointManager:
    """A/B double-buffered, selectively-synced checkpoints for a pytree."""

    def __init__(self, directory: str, comm: Communicator,
                 specs: Mapping[str, tuple[tuple[int, ...], Any]], *,
                 rank: int | None = None, double_buffer: bool = True,
                 mechanism: str = "cached", writeback_interval: float | None = None,
                 striping_factor: int = 1, striping_unit: int = 1 << 20,
                 page_size_hint: int | None = None, snapshot_diff: bool = True,
                 replication: int = 1, cache_bytes: int | None = None):
        """``replication=k`` passes the ``storage_alloc_replication`` hint
        to both checkpoint windows: every save's flush then mirrors the
        changed pages to k-1 replica ranks *before* the manifest commits
        (the window's sync/flush epoch means k durable copies), and a
        ``restore`` whose primary rank died reads transparently from a
        replica -- the checkpoint survives rank death without a restart.
        Requires ``comm.size >= k`` (clamped otherwise, like every hint).

        ``cache_bytes`` bounds each window's page cache (default: the
        whole window).  With a bound and ``snapshot_diff=False`` a save
        streams the tree through it to storage in full: no compare on
        write, which would read the old pages back from storage first.
        """
        self.directory = directory
        self.comm = comm
        # SPMD wiring: by default each process checkpoints its own rank's
        # segment (the communicator's env-bootstrapped identity)
        self.rank = comm.rank if rank is None else rank
        self.specs = {k: (tuple(v[0]), np.dtype(v[1])) for k, v in specs.items()}
        os.makedirs(directory, exist_ok=True)
        self.names = ["a", "b"] if double_buffer else ["a"]
        self.windows: dict[str, WindowedPyTree] = {}
        # snapshot_diff: page-diff each save against the window's last
        # checkpoint (host snapshot) and put/flush only changed blocks --
        # replaces the page cache's compare-on-write (which would compare
        # the same bytes a second time).
        self.snapshot_diff = snapshot_diff
        self._snapshots: dict[str, dict[str, np.ndarray]] = {}
        with span("ckpt.open"):
            for name in self.names:
                info = {
                    "alloc_type": "storage",
                    "storage_alloc_filename": os.path.join(
                        directory, f"ckpt_{name}.bin"),
                    "striping_factor": str(striping_factor),
                    "striping_unit": str(striping_unit),
                }
                if replication > 1:
                    info["storage_alloc_replication"] = str(replication)
                self.windows[name] = WindowedPyTree.allocate(
                    comm, self.specs, info, rank=self.rank,
                    mechanism=mechanism,
                    writeback_interval=writeback_interval,
                    cache_bytes=cache_bytes)
                if not snapshot_diff and cache_bytes is None:
                    # selective sync even under whole-tree puts:
                    for seg in self._segments(self.windows[name]):
                        if hasattr(seg, "backing") and hasattr(
                                seg.backing, "compare_on_write"):
                            seg.backing.compare_on_write = True
        self._turn = 0
        self.saves = 0
        self._pending: Request | None = None
        self._pending_target: str | None = None

    @staticmethod
    def _segments(wt: WindowedPyTree):
        return wt.win.segments

    # -- manifest -------------------------------------------------------------
    def _manifest_path(self, prev: bool = False) -> str:
        """Rank 0 keeps the historical names (``manifest.json``), so a
        driver-origin checkpoint restores unchanged; SPMD ranks > 0 each
        commit their own ``manifest.r<rank>.json`` beside it -- per-rank
        save cadences stay independent and the union of files is identical
        whether the same workload ran driver-origin or SPMD."""
        if self.rank == 0:
            name = _MANIFEST_PREV if prev else _MANIFEST
        else:
            name = (f"manifest.r{self.rank}.prev.json" if prev
                    else f"manifest.r{self.rank}.json")
        return os.path.join(self.directory, name)

    def _write_manifest(self, step: int, target: str,
                        crcs: dict[str, int]) -> None:
        m = {
            "step": step,
            "target": target,
            "layout": self.windows[target].manifest(),
            "crc": crcs,
            "nranks": self.comm.size,
        }
        path = self._manifest_path()
        if os.path.exists(path):
            os.replace(path, self._manifest_path(prev=True))
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(m, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)  # atomic commit

    # -- save -----------------------------------------------------------------
    def _page_size(self, wt: WindowedPyTree) -> int:
        seg = wt.win.segments[self.rank]
        tracker = getattr(seg, "tracker", None)
        if tracker is not None:
            return tracker.page_size
        # remote segments (mp transport) carry the owner's page size as an
        # attribute; last resort is the layout's page constant
        return getattr(seg, "page_size", None) or WindowedPyTree.PAGE

    @staticmethod
    def _page_diff(new: np.ndarray, old: np.ndarray, ps: int) -> np.ndarray:
        """Per-page changed flags between two equal-length uint8 buffers."""
        nb = -(-new.nbytes // ps) if new.nbytes else 0
        changed = np.zeros(nb, dtype=bool)
        whole = (new.nbytes // ps) * ps
        if whole:
            changed[: whole // ps] = np.any(
                new[:whole].reshape(-1, ps) != old[:whole].reshape(-1, ps),
                axis=1)
        if new.nbytes > whole:  # last partial page
            changed[-1] = not np.array_equal(new[whole:], old[whole:])
        return changed

    def _stage(self, target: str, wt: WindowedPyTree,
               tree: Mapping[str, Any]) -> tuple[dict[str, int],
                                                 np.ndarray | None,
                                                 list | None]:
        """Diff ``tree`` against the last checkpoint; returns
        (crcs, flush mask, changed spans).

        With a snapshot of the window's last checkpoint available, each
        slot is a *shard*: its changed pages become ``(offset, bytes)``
        spans and the per-slot page masks merge into one window mask --
        the sync/flush then ships spans + mask through the transport's
        masked span-write primitive (one round trip per rank on remote
        transports), applying them to the page cache and flushing in a
        single operation.  Without a snapshot every slot is put in full
        here and (None, None) means "flush everything dirty".
        """
        snap = self._snapshots.get(target) if self.snapshot_diff else None
        ps = self._page_size(wt)
        seg = wt.win.segments[self.rank]
        mask = (np.zeros(-(-seg.size // ps), dtype=bool)
                if snap is not None else None)
        spans: list | None = [] if snap is not None else None
        crcs: dict[str, int] = {}
        new_snap: dict[str, np.ndarray] = {}
        for k in sorted(self.specs):
            nbytes = wt.slots[k].nbytes
            with span("ckpt.fetch", nbytes=nbytes):
                # a tree of device arrays fetches each one as it is read
                arr = np.ascontiguousarray(tree[k], dtype=self.specs[k][1])
            with span("ckpt.crc", nbytes=nbytes):
                crcs[k] = _crc(arr)
            raw = arr.view(np.uint8).ravel()
            if self.snapshot_diff:
                with span("ckpt.snapshot", nbytes=nbytes):
                    new_snap[k] = raw.copy()
            if snap is not None:
                slot = wt.slots[k]
                # span payloads slice the manager-owned snapshot copy, so
                # a caller mutating its tree before the flush runs cannot
                # corrupt the staged bytes
                staged = new_snap[k]
                with span("ckpt.diff") as sp:
                    changed = self._page_diff(raw, snap[k], ps)
                    for b0, b1 in dirty_runs(changed):
                        lo, hi = b0 * ps, min(b1 * ps, raw.nbytes)
                        spans.append((slot.offset + lo, staged[lo:hi]))
                        mark_span(mask, slot.offset + lo, slot.offset + hi,
                                  ps)
                    sp.set(pages=int(changed.sum()))
            else:
                with span("ckpt.put", nbytes=nbytes):
                    wt.put(k, arr)
        if self.snapshot_diff:
            self._snapshots[target] = new_snap
        return crcs, mask, spans

    def _checked_stage(self, target: str, wt: WindowedPyTree,
                       tree: Mapping[str, Any]):
        """_stage, but a failure mid-staging (e.g. ENOSPC on a full put's
        cache-eviction write) invalidates the window's snapshot: the page
        cache may now hold a mix of old and new pages, so the next save
        must replay a full put + unmasked flush rather than diff against a
        snapshot that no longer describes the cache.  (Span-apply failures
        at flush time are handled the same way by save()/wait().)"""
        try:
            with span("ckpt.stage"):
                return self._stage(target, wt, tree)
        except BaseException:
            self._snapshots.pop(target, None)
            raise

    def save(self, step: int, tree: Mapping[str, Any]) -> int:
        """Synchronous checkpoint.  Returns bytes flushed (selective)."""
        self.wait()
        target = self.names[self._turn % len(self.names)]
        self._turn += 1
        wt = self.windows[target]
        crcs, mask, spans = self._checked_stage(target, wt, tree)
        # Paper Listing 4: exclusive lock prevents remote access during sync.
        wt.win.lock(self.rank, exclusive=True)
        try:
            flushed = wt.sync(mask=mask, spans=spans)
        except BaseException:
            # The snapshot now disagrees with the cache/disk: drop it so
            # the retry replays a full put + unmasked flush (never skips).
            self._snapshots.pop(target, None)
            raise
        finally:
            wt.win.unlock(self.rank)
        with span("ckpt.commit"):
            self._write_manifest(step, target, crcs)
        self.saves += 1
        return flushed

    def save_async(self, step: int, tree: Mapping[str, Any]) -> Request:
        """Stage the state, then flush + commit on the write-back pool.

        Staging computes the snapshot diff synchronously (cheap memory
        compares): the changed pages of every slot become spans merged
        under one window mask.  The flush request (exclusive lock, paper
        Listing 4) then ships spans + mask through the masked span-write
        primitive -- apply + selective flush in one operation, one
        control-channel round trip per rank on remote transports -- and
        its completion hook commits the manifest.  The hook runs only
        after a successful flush, so the manifest can never get ahead of
        its data.  Errors surface at ``wait()``.
        """
        self.wait()
        target = self.names[self._turn % len(self.names)]
        self._turn += 1
        wt = self.windows[target]
        crcs, mask, spans = self._checked_stage(target, wt, tree)

        def _commit(flushed: int) -> None:
            # Runs on the write-back thread after a successful flush; the
            # manifest only ever names fully-persisted data.
            with span("ckpt.commit"):
                self._write_manifest(step, target, crcs)
            self.saves += 1

        self._pending = wt.sync_async(exclusive=True, on_complete=_commit,
                                      mask=mask, spans=spans)
        self._pending_target = target
        return self._pending

    def wait(self) -> None:
        if self._pending is not None:
            req, self._pending = self._pending, None
            target, self._pending_target = self._pending_target, None
            try:
                with span("ckpt.wait"):
                    req.wait()
            except BaseException:
                # Failed flush: the window's snapshot no longer reflects
                # disk; invalidate so the next save to it replays in full.
                self._snapshots.pop(target, None)
                raise

    # -- restore ----------------------------------------------------------------
    def _try_restore(self, manifest_path: str) -> RestoreResult | None:
        if not os.path.exists(manifest_path):
            return None
        try:
            with open(manifest_path) as f:
                m = json.load(f)
        except (json.JSONDecodeError, OSError):
            return None
        target = m["target"]
        if target not in self.windows:
            return None
        wt = self.windows[target]
        tree: dict[str, np.ndarray] = {}
        for k in sorted(self.specs):
            nbytes = wt.slots[k].nbytes
            with span("ckpt.read", nbytes=nbytes):
                arr = wt.get(k)
            with span("ckpt.crc", nbytes=nbytes):
                ok = _crc(arr) == m["crc"].get(k)
            if not ok:
                return None  # torn/corrupt slot
            tree[k] = arr
        return RestoreResult(step=int(m["step"]), tree=tree, manifest=m)

    def restore(self) -> RestoreResult | None:
        """Latest valid checkpoint, falling back A->B via the prev manifest."""
        with span("ckpt.restore"):
            return self._restore()

    def _restore(self) -> RestoreResult | None:
        res = self._try_restore(self._manifest_path())
        if res is not None:
            return res
        res = self._try_restore(self._manifest_path(prev=True))
        if res is not None:
            res.fell_back = True
        return res

    # -- teardown -----------------------------------------------------------------
    def close(self, unlink: bool = False) -> None:
        """Join the pending save and free both windows.  A failed pending
        flush (e.g. a crashed owning rank) re-raises here, but only after
        every window has been freed -- teardown must not leak segments or
        worker-side state behind the error."""
        errors: list[BaseException] = []
        try:
            self.wait()
        except BaseException as e:
            errors.append(e)
        for wt in self.windows.values():
            wt.win.hints = dataclasses.replace(wt.win.hints, unlink=unlink) \
                if unlink else wt.win.hints
            try:
                wt.free()
            except BaseException as e:
                errors.append(e)
        if errors:
            raise errors[0]

    @classmethod
    def open_for_restore(cls, directory: str, comm: Communicator,
                         specs: Mapping[str, tuple[tuple[int, ...], Any]],
                         **kw) -> "CheckpointManager":
        """Re-open a checkpoint directory after a crash/restart.

        Window allocation maps the existing files; restore() then validates.
        """
        return cls(directory, comm, specs, **kw)
