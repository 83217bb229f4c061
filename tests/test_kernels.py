"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _mk(shape, key, dtype=jnp.float32, scale=0.4):
    return (jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
            * scale).astype(dtype)


@pytest.mark.parametrize("B,H,K,S,T,d", [
    (1, 2, 2, 64, 64, 32),
    (2, 4, 2, 96, 96, 16),     # GQA 2:1
    (1, 4, 1, 40, 72, 32),     # MQA, ragged sizes (padding path)
    (2, 2, 2, 33, 65, 64),
])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 24)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, K, S, T, d, causal, window, dtype):
    if causal and S != T:
        pytest.skip("causal assumes aligned q/kv ends")
    q = _mk((B, H, S, d), 0, dtype)
    k = _mk((B, K, T, d), 1, dtype)
    v = _mk((B, K, T, d), 2, dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_block=32, kv_block=32, impl="interpret")
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,S,P,N,chunk", [
    (1, 2, 64, 16, 8, 32),
    (2, 3, 50, 8, 16, 16),     # ragged (padding path)
    (1, 1, 128, 32, 4, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(B, H, S, P, N, chunk, dtype):
    x = _mk((B, H, S, P), 3, dtype)
    dt = jax.nn.softplus(_mk((B, H, S), 4)).astype(jnp.float32)
    A = -jnp.exp(_mk((H,), 5, scale=0.3))
    Bm = _mk((B, H, S, N), 6, dtype)
    C = _mk((B, H, S, N), 7, dtype)
    out = ops.ssd_scan(x, dt, A, Bm, C, chunk=chunk, impl="interpret")
    want = ref.ssd_scan_ref(x, dt, A, Bm, C)
    denom = max(1e-3, float(jnp.abs(want).max()))
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    assert float(jnp.abs(out - want).max()) / denom < tol


@pytest.mark.parametrize("B,S,W,block", [(1, 64, 16, 32), (2, 70, 32, 32),
                                         (1, 256, 8, 64)])
def test_rg_lru_sweep(B, S, W, block):
    a = jax.nn.sigmoid(_mk((B, S, W), 8))
    gx = _mk((B, S, W), 9)
    out = ops.rg_lru_scan(a, gx, block=block, impl="interpret")
    want = ref.rg_lru_ref(a, gx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_dirty_diff_sweep(dtype):
    rng = jax.random.PRNGKey(10)
    cur = (jax.random.normal(rng, (7, 512)) * 10).astype(dtype)
    snap = cur.at[2, 17].add(jnp.asarray(1, dtype)).at[5, 0].add(
        jnp.asarray(1, dtype))
    flags = ops.dirty_blocks(cur, snap, block_elems=512, impl="interpret")
    want = ref.dirty_diff_ref(cur.reshape(7, -1), snap.reshape(7, -1))
    assert (np.asarray(flags) == np.asarray(want)).all()
    assert flags[2] == 1 and flags[5] == 1 and int(flags.sum()) == 2


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8,
                                   jnp.uint16])
@pytest.mark.parametrize("block_elems,n", [
    (128, 1024),    # aligned
    (96, 960),      # odd block size
    (100, 930),     # odd block size + ragged tail (last partial block)
])
@pytest.mark.parametrize("pattern", ["sparse", "all_clean", "all_dirty"])
def test_dirty_diff_matrix_matches_host_compare_on_write(
        tmp_path, dtype, block_elems, n, pattern):
    """The device kernel (interpret mode) and the host compare-on-write
    tracker must produce the identical bitmap for the same state change."""
    from repro.core.storage import CachedBacking

    key = jax.random.PRNGKey(n + block_elems)
    if jnp.issubdtype(dtype, jnp.integer):
        snap = jax.random.randint(key, (n,), -100, 100, jnp.int32).astype(dtype)
    else:
        snap = (jax.random.normal(key, (n,), jnp.float32) * 4).astype(dtype)
    nblocks = -(-n // block_elems)
    if pattern == "sparse":
        dirty = sorted({0, nblocks // 2, nblocks - 1})
    elif pattern == "all_dirty":
        dirty = list(range(nblocks))
    else:
        dirty = []
    cur = snap
    for b in dirty:
        idx = min(b * block_elems + (b % block_elems), n - 1)
        cur = cur.at[idx].add(jnp.asarray(1, dtype))
    flags = ops.dirty_blocks(cur, snap, block_elems=block_elems,
                             block_rows=8, impl="interpret")
    want = np.zeros(nblocks, dtype=bool)
    want[dirty] = True
    assert (np.asarray(flags, dtype=bool) == want).all()

    # host path: page cache with compare-on-write, page == element block
    itemsize = np.dtype(dtype).itemsize
    page = block_elems * itemsize
    # cache must hold every block: a ragged tail rounds size//page down,
    # and an evicted dirty block is written back (bit cleared) early
    backing = CachedBacking(str(tmp_path / "b.bin"), n * itemsize,
                            page_size=page, cache_bytes=nblocks * page,
                            compare_on_write=True)
    snap_b = np.frombuffer(np.asarray(snap).tobytes(), np.uint8)
    cur_b = np.frombuffer(np.asarray(cur).tobytes(), np.uint8)
    backing.write(0, snap_b)
    backing.sync()  # baseline persisted, tracker clean
    backing.write(0, cur_b)
    host_bits = backing.tracker._bits.copy()
    backing.close(unlink=True)
    assert (host_bits == np.asarray(flags, dtype=bool)).all(), \
        "device bitmap != host compare-on-write bitmap"


@pytest.mark.parametrize("impl", ["interpret", "ref"])
def test_dirty_diff_tiled_bit_exact_nan(impl):
    """Tiling sweeps tiles of one block into one flag, and the bit-pattern
    compare keeps an unchanged NaN block clean (value compare would not) --
    under BOTH impls, so ref and pallas stay interchangeable."""
    cur = jnp.zeros((3, 500), jnp.float32).at[1, 499].set(jnp.nan)
    snap = cur.at[2, 0].add(1.0)
    flags = ops.dirty_blocks(cur.reshape(-1), snap.reshape(-1),
                             block_elems=500, block_rows=8, impl=impl)
    assert flags.tolist() == [0, 0, 1]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8,
                                   jnp.uint16])
@pytest.mark.parametrize("block_elems,n", [
    (128, 1024),    # aligned
    (96, 960),      # odd block size
    (100, 930),     # odd block size + ragged tail (last partial block)
])
@pytest.mark.parametrize("pattern", ["sparse", "all_clean", "all_dirty"])
def test_dirty_pack_matrix_matches_host_compare_on_write(
        tmp_path, dtype, block_elems, n, pattern):
    """The fused diff+pack kernel (interpret mode) must agree with the host
    compare-on-write tracker on the bitmap AND emit the changed blocks'
    exact bytes, compacted in block order, in ``packed[:count]``."""
    from repro.core.storage import CachedBacking

    key = jax.random.PRNGKey(n * 3 + block_elems)
    if jnp.issubdtype(dtype, jnp.integer):
        snap = jax.random.randint(key, (n,), -100, 100, jnp.int32).astype(dtype)
    else:
        snap = (jax.random.normal(key, (n,), jnp.float32) * 4).astype(dtype)
    nblocks = -(-n // block_elems)
    if pattern == "sparse":
        dirty = sorted({0, nblocks // 2, nblocks - 1})
    elif pattern == "all_dirty":
        dirty = list(range(nblocks))
    else:
        dirty = []
    cur = snap
    for b in dirty:
        idx = min(b * block_elems + (b % block_elems), n - 1)
        cur = cur.at[idx].add(jnp.asarray(1, dtype))
    flags, packed, count = ops.dirty_pack(cur, snap, block_elems=block_elems,
                                          block_rows=8, impl="interpret")
    want = np.zeros(nblocks, dtype=bool)
    want[dirty] = True
    assert (np.asarray(flags, dtype=bool) == want).all()
    assert int(np.asarray(count)[0]) == len(dirty)

    # packed rows: changed blocks' bytes in block order (tail zero-padded,
    # exactly like the dirty_blocks layout normalization)
    itemsize = np.dtype(dtype).itemsize
    page = block_elems * itemsize
    cur_bytes = np.asarray(cur).tobytes()
    cur_rows = np.zeros((nblocks, page), np.uint8)
    cur_rows.reshape(-1)[:len(cur_bytes)] = np.frombuffer(cur_bytes, np.uint8)
    got_rows = np.asarray(packed)[:len(dirty)]
    got_rows = got_rows.view(np.uint8).reshape(len(dirty), page)
    assert (got_rows == cur_rows[want]).all(), \
        "packed rows != changed blocks' bytes"

    # host path: page cache with compare-on-write must see the same bitmap
    backing = CachedBacking(str(tmp_path / "p.bin"), n * itemsize,
                            page_size=page, cache_bytes=nblocks * page,
                            compare_on_write=True)
    snap_b = np.frombuffer(np.asarray(snap).tobytes(), np.uint8)
    backing.write(0, snap_b)
    backing.sync()
    backing.write(0, np.frombuffer(cur_bytes, np.uint8))
    host_bits = backing.tracker._bits.copy()
    backing.close(unlink=True)
    assert (host_bits == np.asarray(flags, dtype=bool)).all(), \
        "device bitmap != host compare-on-write bitmap"


@pytest.mark.parametrize("impl", ["interpret", "ref"])
def test_dirty_pack_nan_and_layout(impl):
    """Bit-pattern compare keeps an unchanged NaN block clean, and
    packed_run_layout maps the bitmap to (lo, hi, packed_off) spans whose
    packed offsets are an exclusive prefix sum over dirty blocks."""
    from repro.kernels.pack_diff import packed_run_layout
    cur = jnp.zeros((4, 500), jnp.float32).at[1, 499].set(jnp.nan)
    snap = cur.at[2, 0].add(1.0).at[3, 10].add(2.0)
    flags, packed, count = ops.dirty_pack(cur.reshape(-1), snap.reshape(-1),
                                          block_elems=500, block_rows=8,
                                          impl=impl)
    assert flags.tolist() == [0, 0, 1, 1] and int(np.asarray(count)[0]) == 2
    runs = packed_run_layout(np.asarray(flags, bool), 500, 2000)
    assert runs == [(1000, 2000, 0)]  # adjacent dirty blocks coalesce
    rows = np.asarray(packed)[:2].view(np.uint8).reshape(2, -1)[:, :2000]
    want = np.asarray(cur, np.float32)[2:4].reshape(2, -1).view(np.uint8)
    assert (rows == want).all()


@pytest.mark.parametrize("dtype", [jnp.uint16, jnp.bfloat16, jnp.int8])
def test_dirty_pack_page_is_one_native_tile(tmp_path, dtype):
    """A 4 KiB page of a sub-32-bit dtype is one (16, 128) or (32, 128)
    tile of its own width: the kernels (interpret mode) agree bit for bit
    with the jnp reference and the host compare-on-write bitmap, pack the
    pages in the shard's width, and keep an unchanged NaN page clean."""
    from repro.core.storage import CachedBacking

    itemsize = jnp.dtype(dtype).itemsize
    be = 4096 // itemsize
    pages = 21
    n = pages * be - 3  # ragged last page
    rng = np.random.default_rng(itemsize)
    snap = jnp.asarray(rng.integers(-100, 100, n), jnp.int32).astype(dtype)
    if dtype == jnp.bfloat16:
        snap = snap.at[4 * be:5 * be].set(jnp.nan)  # page 4: NaN, unchanged
    dirty = [0, 5, 6, 13, pages - 1]
    cur = snap
    for b in dirty:
        cur = cur.at[min(b * be + 7 * b, n - 1)].add(jnp.asarray(1, dtype))
    assert ops._tiles(ops._blocks(cur, be)).shape == (pages, 32 // itemsize,
                                                      128)
    got = ops.dirty_pack(cur, snap, block_elems=be, block_rows=8,
                         impl="interpret")
    want = ops.dirty_pack(cur, snap, block_elems=be, impl="ref")
    flags = np.asarray(got[0])
    assert flags.nonzero()[0].tolist() == dirty
    assert (flags == np.asarray(want[0])).all()
    assert (np.asarray(ops.dirty_blocks(cur, snap, block_elems=be,
                                        block_rows=8, impl="interpret"))
            == flags).all()
    k = int(np.asarray(got[2])[0])
    assert k == int(np.asarray(want[2])[0]) == len(dirty)
    rows = np.asarray(got[1])[:k]
    assert rows.dtype == np.dtype(f"uint{8 * itemsize}")
    assert rows.shape == (k, be)
    assert np.array_equal(rows, np.asarray(want[1])[:k])
    cur_bytes = np.asarray(cur).tobytes()
    cur_rows = np.zeros((pages, 4096), np.uint8)
    cur_rows.reshape(-1)[:len(cur_bytes)] = np.frombuffer(cur_bytes, np.uint8)
    assert (rows.view(np.uint8) == cur_rows[dirty]).all()

    backing = CachedBacking(str(tmp_path / "t.bin"), n * itemsize,
                            page_size=4096, cache_bytes=pages * 4096,
                            compare_on_write=True)
    backing.write(0, np.frombuffer(np.asarray(snap).tobytes(), np.uint8))
    backing.sync()
    backing.write(0, np.frombuffer(cur_bytes, np.uint8))
    host_bits = backing.tracker._bits.copy()
    backing.close(unlink=True)
    assert (host_bits == flags.astype(bool)).all()


def test_dirty_diff_feeds_tracker():
    """Device-side diff plugs into the host DirtyTracker bitmap."""
    from repro.core.storage import DirtyTracker
    cur = jnp.arange(4096, dtype=jnp.float32)
    snap = cur.at[1030].add(1.0)
    flags = ops.dirty_blocks(cur, snap, block_elems=1024, impl="ref")
    t = DirtyTracker(4096 * 4, page_size=1024 * 4)
    t.mark_blocks(np.asarray(flags, bool))
    assert t.dirty_count == 1 and t.is_dirty(1)


def test_flash_matches_model_attention():
    """Kernel layout (B,H,S,d) == model layout (B,S,H,d) blockwise path."""
    from repro.models.attention import blockwise_attention
    B, H, K, S, d = 2, 4, 2, 64, 32
    q = _mk((B, S, H, d), 11)
    k = _mk((B, S, K, d), 12)
    v = _mk((B, S, K, d), 13)
    a = blockwise_attention(q, k, v, causal=True, q_block=32, kv_block=32)
    b = ops.flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=True,
                            q_block=32, kv_block=32, impl="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b.transpose(0, 2, 1, 3)),
                               atol=2e-5, rtol=2e-5)


def test_dirty_pack_above_8mib_matches_ref_bit_exact():
    """A 9 MiB shard packs through the kernels (interpret mode) with the
    same flags, count and packed rows as the jnp reference -- no size
    routes the kernel path to the reference any more."""
    pages, epp = 2304 + 3, 1024  # odd page count: a partial last grid step
    rng = np.random.default_rng(7)
    snap = rng.standard_normal(pages * epp).astype(np.float32)
    snap[5 * epp:6 * epp] = np.nan  # unchanged NaN page stays clean
    cur = snap.copy()
    dirty = np.sort(rng.choice(np.delete(np.arange(pages), 5),
                               size=pages // 12, replace=False))
    cur[dirty * epp + 7] += 1.0
    got = ops.dirty_pack(cur, snap, block_elems=epp, impl="interpret")
    want = ops.dirty_pack(cur, snap, block_elems=epp, impl="ref")
    flags = np.asarray(got[0])
    assert flags.nonzero()[0].tolist() == dirty.tolist()
    assert (flags == np.asarray(want[0])).all()
    k = int(np.asarray(got[2])[0])
    assert k == int(np.asarray(want[2])[0]) == len(dirty)
    rows = np.asarray(got[1])[:k]
    assert rows.dtype == np.uint32
    assert (rows == np.asarray(want[1])[:k]).all()
    assert (rows.view(np.uint8) ==
            cur.reshape(pages, -1)[dirty].view(np.uint8)).all()


def test_dirty_pack_fully_dirty_matches_ref_bit_exact():
    """Every page dirty, over more pages than one grid step's flags and
    far more than the copies the pack kernel keeps in flight: same flags,
    count and packed rows as the jnp reference (interpret mode)."""
    from repro.kernels.pack_diff import MAX_INFLIGHT, PACK_ROWS
    pages, epp = PACK_ROWS + 3 * MAX_INFLIGHT + 5, 1024
    snap = np.random.default_rng(3).standard_normal(pages * epp)
    snap = snap.astype(np.float32)
    cur = snap.copy()
    cur[np.arange(pages) * epp + 11] += 1.0
    got = ops.dirty_pack(cur, snap, block_elems=epp, impl="interpret")
    want = ops.dirty_pack(cur, snap, block_elems=epp, impl="ref")
    assert np.asarray(got[0]).all()
    assert int(np.asarray(got[2])[0]) == int(np.asarray(want[2])[0]) == pages
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
