"""Out-of-core AdamW: optimizer state + master weights in storage windows.

This is the paper's §3.4 applied to training state: the f32 master copy and
both Adam moments live in a *combined* window allocation (``factor='auto'``
pins what fits in host memory, spills the rest to storage through the
user-level page cache).  The device only ever holds bf16 parameters and
gradients; the update streams window blocks: fetch -> Adam math in numpy ->
put back.  Every ``sync()`` is a selective flush, so the same windows double
as the checkpoint (restart = reopen the files).

The streaming walk is pipelined through the window's nonblocking layer:
while the Adam math for block ``i`` runs, ``rget`` requests prefetch block
``i+1`` of all three state arrays and ``rput`` requests write block ``i-1``
behind -- per-rank FIFO ordering makes the write-behind safe, and the
storage latency hides under the compute (the paper's overlap argument
applied to the optimizer walk).  Pass ``prefetch=False`` to fall back to
the fully synchronous walk.

Selective write-behind: parameters missing from ``grads`` (MoE experts not
routed to this step) are skipped outright, and a block whose gradient and
both moments are all-zero with no weight decay is a provable no-op -- its
write-behind is skipped too, so the window's pages stay clean.  The walk
accumulates a window-block *touched mask*; ``sync(touched_only=True)``
narrows the flush to exactly the blocks some update wrote since the last
sync (``flush_async(mask=...)`` intersection), so checkpoint write traffic
scales with update sparsity, not state size.

When the authoritative master copy lives *on device* instead (donated
optimizer outputs on TPU), ``sync_masters_from_device`` persists it without
a host round trip of the full state: each parameter is a shard whose Pallas
``dirty_diff`` bitmap merges into one window mask, and only the changed
spans + that mask travel to the owning rank through the transport's masked
span-write primitive -- selective sync end to end, even with the page cache
in another process.

For the 236B/400B MoE configs this is the difference between fitting and
not fitting: 12 bytes/param of optimizer state move off-HBM, leaving 2
(bf16 weights) + 2 (grads) on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.comm import Communicator
from repro.core.offload import WindowedPyTree
from repro.core.storage import mark_span
from repro.core.window import Request
from repro.train.optimizer import AdamWConfig, cosine_schedule

__all__ = ["OutOfCoreAdamW", "to_host"]


#: walk blocks the page cache of a budgeted window holds: the walk keeps
#: three arrays' blocks in flight (prefetch, current, write-behind)
STREAM_CACHE_BLOCKS = 16


class OutOfCoreAdamW:
    """Blockwise AdamW over state kept in a storage window.

    ``memory_budget`` bounds the host memory the state takes: that many
    bytes stay pinned in memory (the combined window's ``factor='auto'``),
    and the rest streams from storage through a page cache of
    ``STREAM_CACHE_BLOCKS`` walk blocks.  Without it the whole state is
    cached in memory and storage holds the flushed copy.
    """

    def __init__(self, comm: Communicator, param_shapes: dict, directory: str,
                 cfg: AdamWConfig, *, memory_budget: int | None = None,
                 block_bytes: int = 1 << 22, writeback_interval: float | None = None):
        self.cfg = cfg
        self.step = 0
        specs = {}
        for k, (shape, _) in param_shapes.items():
            specs[f"master/{k}"] = (tuple(shape), np.float32)
            specs[f"m/{k}"] = (tuple(shape), np.float32)
            specs[f"v/{k}"] = (tuple(shape), np.float32)
        info = {
            "alloc_type": "storage",
            "storage_alloc_filename": f"{directory}/optstate.bin",
        }
        if memory_budget is not None:
            info["storage_alloc_factor"] = "auto"
            # split on a walk-block boundary: the spilled blocks then start
            # on storage pages of their own and stream past the cache
            memory_budget -= memory_budget % block_bytes
        # rank-local: each rank walks (and checkpoints) its own partition
        # of the optimizer window -- under SPMD every rank runs this same
        # code against its own segment, not rank 0's
        self.state = WindowedPyTree.allocate(
            comm, specs, info, rank=comm.rank, memory_budget=memory_budget,
            block_bytes=block_bytes, writeback_interval=writeback_interval,
            cache_bytes=(None if memory_budget is None
                         else STREAM_CACHE_BLOCKS * block_bytes))
        self.param_keys = sorted(param_shapes)
        self._initialized = False
        # window-block mask of pages some update wrote since the last sync
        seg = self.state.win.segments[self.state.rank]
        tracker = getattr(seg, "tracker", None)
        self._page_size = tracker.page_size if tracker is not None else 4096
        self._touched: np.ndarray | None = None
        self.blocks_skipped = 0  # provable no-op blocks (stats)

    def _mark_touched(self, lo: int, hi: int) -> None:
        if self._touched is None:
            seg = self.state.win.segments[self.state.rank]
            self._touched = np.zeros(-(-seg.size // self._page_size),
                                     dtype=bool)
        mark_span(self._touched, lo, hi, self._page_size)

    def initialize(self, params: dict) -> None:
        """Seed master weights from the (bf16) device params; zero moments."""
        for k in self.param_keys:
            p = to_host(params[k]).astype(np.float32)
            self.state.put(f"master/{k}", p)
            zeros = np.zeros(p.shape, np.float32)  # untouched pages: no RSS
            self.state.put(f"m/{k}", zeros)
            self.state.put(f"v/{k}", zeros)
        self._initialized = True

    def update(self, grads: dict, *, grad_scale: float = 1.0,
               prefetch: bool = True, skip_clean: bool = True) -> dict:
        """Streamed blockwise AdamW; see :meth:`iter_update`.  Returns the
        new f32 params (numpy) as one dict."""
        return dict(self.iter_update(grads, grad_scale=grad_scale,
                                     prefetch=prefetch,
                                     skip_clean=skip_clean))

    def iter_update(self, grads: dict, *, grad_scale: float = 1.0,
                    prefetch: bool = True, skip_clean: bool = True):
        """Streamed blockwise AdamW.  grads: host-fetchable arrays (bf16 ok,
        device arrays are fetched one at a time).  Yields ``(name, new f32
        param)`` (numpy) as each tensor is done, so a caller can push it
        to the device before the next one exists -- only for the keys
        present in ``grads`` (sparse/MoE updates skip the rest).  The step
        counts once the first tensor is requested; consume every item.

        With ``prefetch`` (default), block ``i+1`` of all three state arrays
        is fetched with ``rget`` while block ``i``'s math runs, and block
        writes go out as ``rput`` write-behind; the walk waits for the
        write-behind before returning, so callers observe fully-applied
        state.  Results are bit-identical to the synchronous walk.

        ``skip_clean`` elides the write-behind of provable no-op blocks
        (zero gradient, zero moments, no decay on the tensor), keeping
        their window pages clean for the selective sync.
        """
        cfg = self.cfg
        lr = float(cosine_schedule(cfg, self.step))
        self.step += 1
        t = self.step
        b1c = 1 - cfg.b1 ** t
        b2c = 1 - cfg.b2 ** t
        for k in self.param_keys:
            if k not in grads:  # sparse update: untouched expert/tensor
                continue
            g_full = to_host(grads[k]).astype(np.float32).ravel()
            if grad_scale != 1.0:
                g_full *= grad_scale
            wa_m = self.state.array(f"m/{k}")
            wa_v = self.state.array(f"v/{k}")
            wa_p = self.state.array(f"master/{k}")
            new_p = np.empty_like(g_full)
            off = 0
            decay = cfg.weight_decay if _decayable(k) else 0.0
            nblocks = wa_p.num_blocks

            def fetch(i):
                return (wa_m.read_block_async(i), wa_v.read_block_async(i),
                        wa_p.read_block_async(i))

            pending_writes: list[Request] = []
            nxt = fetch(0) if prefetch and nblocks else None
            for i in range(nblocks):
                if prefetch:
                    rm, rv, rp = nxt
                    nxt = fetch(i + 1) if i + 1 < nblocks else None
                    m, v, p = rm.wait(), rv.wait(), rp.wait()
                else:
                    m = wa_m.read_block(i)
                    v = wa_v.read_block(i)
                    p = wa_p.read_block(i)
                g = g_full[off: off + p.size]
                if (skip_clean and decay == 0.0 and not g.any()
                        and not m.any() and not v.any()):
                    # provable no-op: m,v stay zero and p is unchanged --
                    # skip the write-behind, leave the pages clean
                    self.blocks_skipped += 1
                    new_p[off: off + p.size] = p
                    off += p.size
                    continue
                m = cfg.b1 * m + (1 - cfg.b1) * g
                v = cfg.b2 * v + (1 - cfg.b2) * g * g
                upd = (m / b1c) / (np.sqrt(v / b2c) + cfg.eps) + decay * p
                p = p - lr * upd
                if prefetch:
                    pending_writes += [wa_m.write_block_async(i, m),
                                       wa_v.write_block_async(i, v),
                                       wa_p.write_block_async(i, p)]
                else:
                    wa_m.write_block(i, m)
                    wa_v.write_block(i, v)
                    wa_p.write_block(i, p)
                for wa in (wa_m, wa_v, wa_p):
                    self._mark_touched(*wa.block_byte_span(i))
                new_p[off: off + p.size] = p
                off += p.size
            Request.waitall(pending_writes)
            shape = self.state.slots[f"master/{k}"].shape
            yield k, new_p.reshape(shape)

    def sync_masters_from_device(self, masters: dict, snapshot: dict, *,
                                 blocking: bool = True,
                                 impl: str | None = None):
        """Persist device-resident master weights with one merged-mask flush.

        ``masters``/``snapshot`` map parameter names to same-shape float32
        arrays (jax or numpy): the new values and the last-persisted ones.
        Each named tensor is one *shard* at its ``master/<name>`` slot
        offset; the per-shard Pallas ``dirty_diff`` bitmaps are OR-merged
        into a single window mask and only the changed spans cross
        device->host -- then spans + mask ride the transport's masked
        span-write primitive to the owning rank (one control-channel round
        trip, wherever the page cache lives).  Names absent from
        ``masters`` are untouched (sparse/MoE updates).

        Returns bytes flushed (``blocking=True``, default) or the flush's
        :class:`Request`.
        """
        shards = []
        for k in self.param_keys:
            if k not in masters:
                continue
            slot = self.state.slots[f"master/{k}"]
            for name, arr in (("masters", masters[k]),
                              ("snapshot", snapshot[k])):
                if np.dtype(arr.dtype) != slot.dtype:
                    raise ValueError(
                        f"{name}[{k!r}] must be {slot.dtype} to match the "
                        f"window layout, got {np.dtype(arr.dtype)}")
            shards.append((masters[k], snapshot[k], slot.offset))
        if not shards:
            return 0 if blocking else None
        return self.state.win.sync_shards_from_device(
            self.state.rank, shards, blocking=blocking, impl=impl)

    def sync(self, *, touched_only: bool = False) -> int:
        """Selective flush of the optimizer window (checkpoint).

        ``touched_only`` narrows the flush to the window blocks updates have
        written since the last sync (the write-behind mask intersected with
        the host dirty bitmap); blocks dirtied by other writers stay dirty
        for a later full sync.
        """
        if touched_only:
            mask, self._touched = self._touched, None
            if mask is None:
                return 0  # nothing touched since the last sync
            try:
                return self.state.sync(mask=mask)
            except BaseException:
                # the backing re-marked the taken blocks; restore the mask
                # too so a touched_only retry replays them (never skips)
                if self._touched is None:
                    self._touched = mask
                else:
                    self._touched |= mask
                raise
        n = self.state.sync()
        self._touched = None  # only after a successful full flush
        return n

    def master(self, name: str) -> np.ndarray:
        return self.state.get(f"master/{name}")

    def masters(self) -> dict:
        return {k: self.master(k) for k in self.param_keys}

    def free(self) -> None:
        self.state.free()


def to_host(x) -> np.ndarray:
    """``x`` as a numpy array.  A ``jax.Array`` is copied on its device
    first: ``np.asarray`` caches the host copy on the array it fetches,
    which is then the short-lived copy and not ``x``, so no host copy
    outlives the caller's use of it."""
    if isinstance(x, jax.Array):
        x = jnp.array(x, copy=True)
    return np.asarray(x)


def _decayable(name: str) -> bool:
    leaf = name.split("/")[-1]
    return not ("norm" in leaf or leaf.startswith("b")
                or leaf in ("A_log", "D", "dt_bias", "lam"))
