"""Backings: dirty tracking, striping, page cache vs mmap equivalence."""

import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.storage import (CachedBacking, DirtyTracker, MmapBacking,
                                StripedFile, make_backing)


# -- DirtyTracker ------------------------------------------------------------

def test_tracker_basic():
    t = DirtyTracker(10000, page_size=1024)
    assert t.num_blocks == 10 and t.dirty_count == 0
    t.mark(1500, 10)
    assert t.dirty_count == 1 and t.is_dirty(1)
    t.mark(1020, 3000)  # spans blocks 0..3
    assert t.dirty_count == 4
    mask = t.snapshot_and_clear()
    assert mask.sum() == 4 and t.dirty_count == 0


@given(ops=st.lists(st.tuples(st.integers(0, 9999), st.integers(1, 5000)),
                    max_size=30))
def test_tracker_matches_model(ops):
    t = DirtyTracker(10000, page_size=512)
    model = np.zeros(10000, bool)
    for off, n in ops:
        n = min(n, 10000 - off)
        if n <= 0:
            continue
        t.mark(off, n)
        model[off:off + n] = True
    blocks = model.reshape(-1, 512) if model.size % 512 == 0 else None
    expect = np.zeros(t.num_blocks, bool)
    for b in range(t.num_blocks):
        expect[b] = model[b * 512:(b + 1) * 512].any()
    got = t.snapshot_and_clear()
    assert (got == expect).all()


def test_dirty_runs():
    t = DirtyTracker(8192, page_size=1024)
    t.mark(0, 1024)
    t.mark(3 * 1024, 2048)
    runs = t.dirty_runs()
    assert runs == [(0, 1), (3, 5)]


# -- StripedFile ----------------------------------------------------------------

@settings(deadline=None, max_examples=25)
@given(factor=st.integers(1, 4), unit=st.sampled_from([64, 256, 1000]),
       writes=st.lists(st.tuples(st.integers(0, 4000), st.binary(min_size=1,
                                                                 max_size=600)),
                       max_size=10))
def test_striped_file_matches_flat_model(tmp_path_factory, factor, unit, writes):
    d = tmp_path_factory.mktemp("stripe")
    size = 5000
    sf = StripedFile(str(d / "f.bin"), size, striping_factor=factor,
                     striping_unit=unit)
    model = bytearray(size)
    try:
        for off, data in writes:
            data = data[: size - off]
            if not data:
                continue
            sf.pwrite(off, data)
            model[off:off + len(data)] = data
        assert sf.pread(0, size) == bytes(model)
    finally:
        sf.close(unlink=True)


def test_striping_actually_splits(tmp_path):
    sf = StripedFile(str(tmp_path / "s.bin"), 4096, striping_factor=4,
                     striping_unit=512)
    sf.pwrite(0, b"\xff" * 4096)
    sf.close()
    for i in range(4):
        assert os.path.getsize(tmp_path / f"s.bin.stripe{i}") == 1024


# -- backings ---------------------------------------------------------------------

@pytest.mark.parametrize("mechanism", ["mmap", "cached"])
def test_backing_roundtrip_and_sync(tmp_file, mechanism):
    b = make_backing(tmp_file, 8192, mechanism=mechanism)
    data = np.arange(256, dtype=np.uint8)
    b.write(100, data)
    assert (b.read(100, 256) == data).all()
    flushed = b.sync()
    assert flushed > 0
    assert b.sync() == 0  # selective: nothing dirty anymore
    b.close()


@settings(deadline=None, max_examples=20)
@given(ops=st.lists(
    st.tuples(st.sampled_from(["r", "w"]), st.integers(0, 4000),
              st.integers(1, 900)), min_size=1, max_size=25))
def test_cached_equals_mmap(tmp_path_factory, ops):
    """The user-level page cache is observationally identical to mmap."""
    d = tmp_path_factory.mktemp("eq")
    size = 4096 + 1000
    a = make_backing(str(d / "a.bin"), size, mechanism="mmap")
    b = make_backing(str(d / "b.bin"), size, mechanism="cached",
                     cache_bytes=3 * 4096)  # small cache: forces eviction
    rng = np.random.default_rng(1)
    try:
        for kind, off, n in ops:
            n = min(n, size - off)
            if n <= 0:
                continue
            if kind == "w":
                data = rng.integers(0, 256, n).astype(np.uint8)
                a.write(off, data)
                b.write(off, data)
            else:
                assert (a.read(off, n) == b.read(off, n)).all()
        a.sync(); b.sync()
        raw_a = a.read(0, size)
        raw_b = b.read(0, size)
        assert (raw_a == raw_b).all()
    finally:
        a.close(); b.close()


def test_cached_eviction_persists(tmp_file):
    """Evicted dirty blocks must be written back, not lost."""
    b = CachedBacking(tmp_file, 64 * 4096, cache_bytes=2 * 4096)
    for blk in range(64):
        b.write(blk * 4096, np.full(4096, blk % 251, np.uint8))
    for blk in range(64):
        assert (b.read(blk * 4096, 4096) == blk % 251).all()
    assert b.evictions > 0
    b.close()


def test_compare_on_write_keeps_clean(tmp_file):
    b = CachedBacking(tmp_file, 4 * 4096, compare_on_write=True)
    data = np.full(4096, 7, np.uint8)
    b.write(0, data)
    assert b.sync() == 4096
    b.write(0, data)            # identical content
    assert b.sync() == 0        # stays clean
    data2 = data.copy(); data2[100] = 8
    b.write(0, data2)
    assert b.sync() == 4096     # real change flushes
    b.close()


def test_dirty_ratio_forces_flush(tmp_file):
    b = CachedBacking(tmp_file, 10 * 4096, dirty_ratio=0.3)
    for blk in range(10):
        b.write(blk * 4096, np.full(4096, 1, np.uint8))
    # vm.dirty_ratio analogue: flushes happened inside write()
    assert b.bytes_flushed > 0
    b.close()


def test_background_flusher(tmp_file):
    import time
    b = CachedBacking(tmp_file, 4 * 4096, writeback_interval=0.05)
    b.write(0, np.full(4096, 3, np.uint8))
    time.sleep(0.4)
    assert b.tracker.dirty_count == 0  # flusher cleaned it
    assert b.sync() == 0
    b.close()


def test_unlink_and_discard(tmp_path):
    p = str(tmp_path / "u.bin")
    b = make_backing(p, 4096, mechanism="cached")
    b.write(0, np.full(10, 1, np.uint8))
    b.close(unlink=True)
    assert not os.path.exists(p)


def test_cached_streams_large_spans_past_a_small_cache(tmp_file):
    """A span the cache could take only by evicting, none of it resident,
    goes straight to the file (no per-page eviction) and back, and the
    next sync reports and fsyncs it; a span with a resident page still
    goes through the cache."""
    from repro.core.storage import STREAM_MIN_BYTES
    ps = 4096
    n = 2 * STREAM_MIN_BYTES // ps           # pages per large span
    b = CachedBacking(tmp_file, 4 * n * ps, cache_bytes=n // 2 * ps)
    data = np.random.default_rng(0).integers(0, 256, 2 * n * ps, np.uint8)
    b.write(0, data[:n * ps])                # fits in no cache: streams
    assert b.evictions == 0 and b.tracker.dirty_count == 0
    assert b.sync() == n * ps and b.sync() == 0
    b.write(2 * n * ps, data[:ps])           # one page, cached and dirty
    b.write(2 * n * ps, data)                # resident page: via the cache
    assert b.tracker.dirty_count > 0
    assert (b.read(0, n * ps) == data[:n * ps]).all()
    assert (b.read(2 * n * ps, 2 * n * ps) == data).all()
    b.close()
    raw = np.fromfile(tmp_file, np.uint8)
    assert (raw[:n * ps] == data[:n * ps]).all()
    assert (raw[2 * n * ps:] == data).all()


def test_cached_cold_read_loads_a_run_with_one_read(tmp_file):
    """A cold multi-page read with room in the cache lands in the next
    free slots in one read: same bytes, every page then resident."""
    ps = 4096
    data = np.random.default_rng(1).integers(0, 256, 64 * ps - 100, np.uint8)
    b = CachedBacking(tmp_file, data.nbytes)
    b.write(0, data)
    b.close()
    b = CachedBacking(tmp_file, data.nbytes)
    page_reads = []
    pread = b.file.pread
    b.file.pread = lambda *a: page_reads.append(a) or pread(*a)
    assert (b.read(3 * ps + 17, 10 * ps) == data[3 * ps + 17:13 * ps + 17]).all()
    assert not page_reads
    assert b.faults == 11 and (b._slot_of[3:14] >= 0).all()
    assert (b.read(60 * ps, data.nbytes - 60 * ps) == data[60 * ps:]).all()
    assert (b.read(0, data.nbytes) == data).all()
    b.close()
