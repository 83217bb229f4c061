"""Diff + pack on device: changed bitmap + compacted dirty blocks.

``dirty_diff`` alone leaves the expensive half of selective device sync on
the host: once the bitmap is known, each changed span still crosses PCIe as
its own device->host slice (`np.asarray` per span).  ``diff_pack_tpu``
keeps both steps on the device: the ``dirty_diff`` kernel emits the
per-block changed flags, then the ``pack_rows`` kernel copies every flagged
block, in block order, into the first ``count`` rows of a packed buffer in
HBM, so the changed bytes cross PCIe as ONE contiguous transfer regardless
of how fragmented the dirty set is.

The pack kernel never stages the packed buffer in VMEM, so any size packs:
the flags arrive in SMEM ``PACK_ROWS`` at a time, and each flagged block is
one HBM->HBM DMA (a 4 KiB page is one contiguous tile, whatever its
width) into packed row ``count``; the TPU grid is sequential, so ``count``
is a running prefix sum kept in SMEM.  At most ``MAX_INFLIGHT`` copies are
in flight at once, and each grid step waits for the DMAs it started.  Rows at index >= final count are uninitialized and must not be
read.

Bit-pattern semantics match ``dirty_diff``: the inputs are unsigned
integer views in the width the TPU stores them in (uint32 words, or
uint16/uint8 tiles; built by ``repro.kernels.ops``), so unchanged NaN
blocks stay clean and the packed rows hold the exact bytes of the current
tensor.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dirty_diff import changed_elem_spans, dirty_diff_tpu

__all__ = ["diff_pack_tpu", "pack_rows_tpu", "packed_run_layout"]

#: flags per pack grid step: a 1-D int32 SMEM block must match XLA's
#: T(1024) tiling of the flags vector
PACK_ROWS = 1024
#: block DMAs the pack kernel keeps in flight: past it, each new copy
#: first retires the oldest, so a fully dirty step never queues 1024
MAX_INFLIGHT = 64


def packed_run_layout(flags, block_elems: int,
                      nelems: int) -> list[tuple[int, int, int]]:
    """Bitmap -> ``[(lo_elem, hi_elem, packed_elem_off)]`` for span rebuild.

    Packing preserves block order, so a coalesced dirty run ``[b0, b1)``
    occupies packed rows ``[pos(b0), pos(b0) + (b1 - b0))`` contiguously,
    where ``pos`` is the exclusive prefix count of dirty blocks.  The
    ``(lo_elem, hi_elem)`` geometry is exactly
    :func:`~repro.kernels.dirty_diff.changed_elem_spans` -- the packed path
    and the host fallback share one clipping rule by construction.
    """
    f = np.asarray(flags, np.int64).ravel()
    excl = np.concatenate(([0], np.cumsum(f)[:-1])) if f.size else f
    out = []
    for lo, hi in changed_elem_spans(f, block_elems, nelems):
        out.append((lo, hi, int(excl[lo // block_elems]) * block_elems))
    return out


def _pack_kernel(flag_ref, cur_hbm, packed_hbm, count_ref, sem):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        count_ref[0] = 0

    base = i * PACK_ROWS

    def wait_one():  # every copy moves one block: same-size descriptor
        pltpu.make_async_copy(cur_hbm.at[0], packed_hbm.at[0], sem).wait()

    def issue(r, carry):
        k, inflight = carry
        f = flag_ref[r]
        full = jnp.logical_and(f != 0, inflight == MAX_INFLIGHT)

        @pl.when(full)
        def _retire():
            wait_one()

        @pl.when(f != 0)
        def _copy():
            pltpu.make_async_copy(cur_hbm.at[base + r], packed_hbm.at[k],
                                  sem).start()

        return k + f, inflight + f - full.astype(jnp.int32)

    count, inflight = jax.lax.fori_loop(0, PACK_ROWS, issue,
                                        (count_ref[0], jnp.int32(0)))
    def drain(_, c):
        wait_one()
        return c

    jax.lax.fori_loop(0, inflight, drain, 0)
    count_ref[0] = count


def pack_rows_tpu(flags: jax.Array, cur: jax.Array, *,
                  interpret: bool = False):
    """flags (nblocks,) int32 0/1; cur (nblocks, S, 128) ->
    ``(packed like cur, count (1,) int32)``: ``packed[:count]`` are the
    flagged blocks of ``cur`` in block order."""
    nb = cur.shape[0]
    steps = pl.cdiv(nb, PACK_ROWS)
    flags = jnp.pad(flags.astype(jnp.int32), (0, steps * PACK_ROWS - nb))
    return pl.pallas_call(
        _pack_kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec((PACK_ROWS,), lambda i: (i,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec((1,), lambda i: (0,),
                                memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct(cur.shape, cur.dtype),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="pack_rows",
    )(flags, cur)


def diff_pack_tpu(cur: jax.Array, snap: jax.Array, *,
                  block_rows: int | None = None, interpret: bool = False):
    """cur, snap: (nblocks, S, 128) uint32, uint16 or uint8 tiles
    (:func:`~repro.kernels.dirty_diff.dirty_diff_tpu`).

    Returns ``(flags (nb,) int32, packed like cur, count (1,) int32)``.
    ``packed[:count]`` are the dirty blocks in block order; rows past
    ``count`` are uninitialized.
    """
    flags = dirty_diff_tpu(cur, snap, block_rows=block_rows,
                           interpret=interpret)
    packed, count = pack_rows_tpu(flags, cur, interpret=interpret)
    return flags, packed, count
