"""Public jit-ready kernel wrappers with backend dispatch + padding.

On TPU the Pallas kernels run; elsewhere (CPU runs, unit tests)
the pure-jnp references execute, with ``interpret=True`` available to run
the actual kernel bodies on CPU for validation.  Wrappers normalize layouts
and pad to block multiples so callers never see alignment constraints.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dirty_diff import dirty_diff_tpu
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.pack_diff import diff_pack_tpu
from repro.kernels.rg_lru import rg_lru_tpu
from repro.kernels.ssd_scan import ssd_scan_tpu

__all__ = ["flash_attention", "ssd_scan", "rg_lru_scan", "dirty_blocks",
           "dirty_pack", "narrow_tiles", "use_pallas", "resolve_impl"]

#: bytes of one TPU tile of 32-bit words, (8, 128): the device-sync
#: kernels' unit (narrower dtypes pack 16 or 32 rows into it)
TILE_BYTES = 8 * 128 * 4


def use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, axis: int, mult: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    q_block=512, kv_block=512, impl: str | None = None):
    """q: (B,H,S,d); k/v: (B,K,T,d).  impl: None=auto | 'pallas' |
    'interpret' | 'ref'."""
    impl = impl or ("pallas" if use_pallas() else "ref")
    if impl == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       scale=scale)
    qp, S = _pad_to(q, 2, q_block)
    kp, T = _pad_to(k, 2, kv_block)
    vp, _ = _pad_to(v, 2, kv_block)
    out = flash_attention_tpu(qp, kp, vp, causal=causal, window=window,
                              scale=scale, q_block=q_block, kv_block=kv_block,
                              t_actual=T, interpret=(impl == "interpret"))
    return out[:, :, :S]


def ssd_scan(x, dt, A, Bm, C, *, chunk=256, impl: str | None = None):
    """x: (B,H,S,P); dt: (B,H,S); A: (H,); Bm/C: (B,H,S,N) -> (B,H,S,P) f32."""
    impl = impl or ("pallas" if use_pallas() else "ref")
    if impl == "ref":
        return ref.ssd_scan_ref(x, dt, A, Bm, C)
    xp, S = _pad_to(x, 2, chunk)
    dtp, _ = _pad_to(dt, 2, chunk)   # dt=0 padding -> exact no-op steps
    Bp, _ = _pad_to(Bm, 2, chunk)
    Cp, _ = _pad_to(C, 2, chunk)
    y = ssd_scan_tpu(xp, dtp, A.astype(jnp.float32), Bp, Cp, chunk=chunk,
                     interpret=(impl == "interpret"))
    return y[:, :, :S]


def rg_lru_scan(a, gx, *, block=256, impl: str | None = None):
    """a, gx: (B,S,W) -> y (B,S,W) f32.  Padding a=1,gx=0 is a no-op tail."""
    impl = impl or ("pallas" if use_pallas() else "ref")
    if impl == "ref":
        return ref.rg_lru_ref(a, gx)
    S = a.shape[1]
    pad = (-S) % block
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        gx = jnp.pad(gx, ((0, 0), (0, pad), (0, 0)))
    y = rg_lru_tpu(a, gx, block=block, interpret=(impl == "interpret"))
    return y[:, :S]


def narrow_tiles(dtype) -> bool:
    """Whether a shard of ``dtype`` is diffed and packed in its own width
    (native tiles) rather than as uint32 words."""
    return jnp.dtype(dtype).itemsize < 4


def _blocks(x, block_elems: int):
    """Flatten ``x``, zero-pad to a block multiple, and view its bit
    patterns as ``(nblocks, n)``: uint32 words for 32-bit and wider dtypes,
    the same-width unsigned integer below that (a free bitcast -- no
    strided slice or shift, which a TPU runs as a lane shuffle)."""
    x = jnp.asarray(x).reshape(-1)
    pad = (-x.shape[0]) % block_elems
    if pad:
        x = jnp.pad(x, (0, pad))
    size = x.dtype.itemsize
    if narrow_tiles(x.dtype):
        x = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * size}"))
        return x.reshape(-1, block_elems)
    x = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return x.reshape(-1, block_elems * size // 4)


def _tiles(x):
    """(nblocks, n) -> (nblocks, S, 128): each block zero-padded to whole
    4 KiB tiles of its dtype -- (8, 128) uint32, (16, 128) uint16, (32, 128)
    uint8 -- the tiles the TPU stores it in (a 4 KiB page is exactly one)."""
    nb, n = x.shape
    pad = (-n) % (TILE_BYTES // x.dtype.itemsize)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(nb, -1, 128)


def resolve_impl(impl: str | None) -> str:
    """The kernel implementation ``impl`` names; None picks the platform's
    (compiled Pallas on a TPU, the jnp reference elsewhere)."""
    impl = impl or ("pallas" if use_pallas() else "ref")
    if impl not in ("pallas", "interpret", "ref"):
        raise ValueError(f"unknown impl {impl!r}")
    return impl


_STATIC = ("block_elems", "block_rows", "impl")


@functools.partial(jax.jit, static_argnames=_STATIC)
def dirty_blocks(cur, snap, *, block_elems=1024, block_rows=None,
                 impl: str | None = None):
    """Flatten two same-shape tensors into blocks; return int32 changed flags.

    Feeds DirtyTracker.mark_blocks for device-state incremental checkpoints
    (``Window.sync_from_device`` sizes ``block_elems`` so one flag covers one
    tracker page).  The compare is on bit patterns under every impl, so an
    unchanged NaN block stays clean.  ``block_rows`` sets how many blocks
    one kernel grid step compares (a multiple of 8).
    """
    impl = resolve_impl(impl)
    c = _blocks(cur, block_elems)
    s = _blocks(snap, block_elems)
    if impl == "ref":
        return ref.dirty_diff_ref(c, s)
    return dirty_diff_tpu(_tiles(c), _tiles(s), block_rows=block_rows,
                          interpret=(impl == "interpret"))


@functools.partial(jax.jit, static_argnames=_STATIC)
def dirty_pack(cur, snap, *, block_elems=1024, block_rows=None,
               impl: str | None = None):
    """Fused diff+pack: ``(flags (nb,) int32, packed (nb, n), count (1,)
    int32)``.

    ``packed[:count]`` holds the changed blocks' bytes in block order, one
    row per block, so one device->host fetch of those rows moves every
    changed byte; ``repro.kernels.pack_diff.packed_run_layout`` maps the
    bitmap to span geometry shared with the non-fused path.  A row is
    ``block_elems * itemsize / 4`` uint32 words for 32-bit and wider
    dtypes, and ``block_elems`` elements of the same-width unsigned integer
    (:func:`narrow_tiles`) below that; either way its row-major bytes are
    the block's bytes.  Layout normalization (flatten, zero-pad to a block
    multiple, bit-pattern view) matches :func:`dirty_blocks` exactly, so
    the two bitmaps always agree.  Every impl packs at every size; on a TPU
    the default is the compiled kernel.
    """
    impl = resolve_impl(impl)
    c = _blocks(cur, block_elems)
    s = _blocks(snap, block_elems)
    if impl == "ref":
        return ref.diff_pack_ref(c, s)
    flags, packed, count = diff_pack_tpu(
        _tiles(c), _tiles(s), block_rows=block_rows,
        interpret=(impl == "interpret"))
    # crop tile padding so a run of packed rows is one contiguous byte blob
    return flags, packed.reshape(c.shape[0], -1)[:, :c.shape[1]], count
