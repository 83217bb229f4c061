"""Pallas TPU kernel for selective-sync dirty-block detection.

The paper's ``MPI_Win_sync`` is *selective*: only dirty pages are flushed.
When the authoritative state lives on-device (TPU HBM), detecting which
checkpoint blocks actually changed would otherwise cost a device->host copy
of everything.  This kernel reduces (current, snapshot) block pairs to a
per-block changed flag entirely on-device in one streaming pass; only the
tiny bitmap plus the dirty blocks then cross PCIe, feeding the same
``DirtyTracker`` bitmap as the host-side compare-on-write path
(``Window.sync_from_device`` / ``flush_async(mask=...)``).

Layout: every block is a whole number of 4 KiB tiles in the width the
TPU stores it in -- the inputs are ``(nblocks, S, 128)`` uint32 words
with ``S % 8 == 0``, uint16 with ``S % 16 == 0`` or uint8 with
``S % 32 == 0`` (a 4 KiB page is exactly one tile; ``repro.kernels.ops``
builds this view from any dtype and zero-pads smaller blocks).  Each grid
step compares ``block_rows`` blocks at once: a packed 16- or 8-bit block
is read as the uint32 words its tiles already are (``pltpu.bitcast``, no
work), each block's tiles fold to one 128-lane row, and a transpose lets
the step's flags leave as one lane-dense ``(1, block_rows)`` int32 row.
The last step may be partial: the rows it reads past ``nblocks`` are
garbage and their flags are cropped.

The compare is on *bit patterns*, so an unchanged block full of NaNs
stays clean (IEEE ``NaN != NaN`` would dirty it), matching the host page
cache's byte-level compare exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dirty_diff_tpu", "changed_elem_spans"]

LANES = 128
#: the widths the kernel takes, each in whole 4 KiB tiles
TILE_DTYPES = (jnp.dtype(jnp.uint32), jnp.dtype(jnp.uint16),
               jnp.dtype(jnp.uint8))
#: words each input block of one grid step aims at (1 MiB of uint32):
#: two inputs, double-buffered, stay well inside the default scoped VMEM
STEP_WORDS = 1 << 18


def _default_block_rows(sublanes: int) -> int:
    """Blocks per grid step for blocks of ``sublanes`` x 128 words."""
    return max(8, STEP_WORDS // (sublanes * LANES)) // 8 * 8


def changed_elem_spans(flags, block_elems: int,
                       nelems: int) -> list[tuple[int, int]]:
    """Geometry helper: changed-flag bitmap -> coalesced element spans.

    Translates the kernel's per-block flags into contiguous
    ``[lo_elem, hi_elem)`` runs clipped to ``nelems`` (the last block may
    be partial).  These are exactly the spans that must cross the
    device->host boundary -- and, under a remote-owner transport, ride the
    masked span-write message -- so every consumer of the bitmap shares
    one clipping rule.
    """
    from repro.core.storage import dirty_runs  # host-side, jax-free
    out = []
    for b0, b1 in dirty_runs(flags):
        lo = b0 * block_elems
        hi = min(b1 * block_elems, nelems)
        if lo < hi:
            out.append((lo, hi))
    return out


def _kernel(words, cur_ref, snap_ref, flag_ref):
    """``words``: rows of 128 uint32 words in one block."""
    cur, snap = cur_ref[...], snap_ref[...]
    if cur.dtype != jnp.uint32:  # packed narrow tiles: the same words
        cur = pltpu.bitcast(cur, jnp.uint32)
        snap = pltpu.bitcast(snap, jnp.uint32)
    ne = (cur != snap).astype(jnp.int32)
    rows = ne.shape[0] // words
    per_block = jnp.max(ne.reshape(rows, words, LANES), axis=1)
    flag_ref[0] = jnp.max(per_block.T, axis=0, keepdims=True)


def dirty_diff_tpu(cur: jax.Array, snap: jax.Array, *,
                   block_rows: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """cur, snap: (nblocks, S, 128) uint32, uint16 or uint8 whose blocks
    are whole tiles (``S`` a multiple of 8, 16 or 32) -> (nblocks,) int32
    (1 = changed)."""
    if cur.shape != snap.shape or cur.dtype != snap.dtype:
        raise ValueError("cur/snap shape or dtype mismatch")
    nb, sub, lanes = cur.shape
    size = cur.dtype.itemsize
    if (lanes != LANES or cur.dtype not in TILE_DTYPES
            or sub % (32 // size)):
        raise ValueError(f"need (nblocks, S, 128) uint32, uint16 or uint8 "
                         f"in whole tiles, got {cur.shape} {cur.dtype}")
    words = sub * size // 4  # word rows of one block
    rows = block_rows or _default_block_rows(words)
    if rows % 8:
        raise ValueError(f"block_rows must be a multiple of 8, got {rows}")
    if nb < rows:  # a single step: pad it whole (small by construction)
        rows = -(-nb // 8) * 8
        cur = jnp.pad(cur, ((0, rows - nb), (0, 0), (0, 0)))
        snap = jnp.pad(snap, ((0, rows - nb), (0, 0), (0, 0)))
    steps = pl.cdiv(cur.shape[0], rows)
    flat = (cur.shape[0] * sub, LANES)  # same tiled layout: a bitcast
    out = pl.pallas_call(
        functools.partial(_kernel, words),
        grid=(steps,),
        in_specs=[pl.BlockSpec((rows * sub, LANES), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((1, 1, rows), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((steps, 1, rows), jnp.int32),
        interpret=interpret,
        name="dirty_diff",
    )(cur.reshape(flat), snap.reshape(flat))
    return out.reshape(-1)[:nb]
