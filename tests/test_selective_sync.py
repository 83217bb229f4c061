"""Device-mask selective sync + backpressure semantics end to end.

Covers the intersection rules of ``flush_async(mask=...)`` /
``sync(mask=...)``, the ``sync_from_device`` pipeline (Pallas dirty_diff ->
window mask -> masked write-back), combined-window mask offset translation,
the checkpoint manager's snapshot-diff staging, and the crash-replay
invariant: a killed write-back pipeline never commits a manifest ahead of
its data, and the retry replays everything (never skips).
"""

import numpy as np
import pytest

from repro.ckpt import CheckpointManager
from repro.core import Communicator, Request, Window

PAGE = 4096
PAGES = 16


def storage_info(tmp_path, name="w.bin"):
    return {"alloc_type": "storage",
            "storage_alloc_filename": str(tmp_path / name)}


def _mask(*blocks, n=PAGES):
    m = np.zeros(n, dtype=bool)
    for b in blocks:
        m[b] = True
    return m


# -- mask intersection rules --------------------------------------------------

def test_masked_sync_flushes_only_intersection(tmp_path):
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    for pg in (1, 3, 5):
        win.put(np.full(16, pg + 1, np.uint8), 0, pg * PAGE)
    # mask selects a dirty page (3) and a clean one (7): only 3 flushes
    assert win.sync(0, mask=_mask(3, 7)) == PAGE
    disk = np.fromfile(tmp_path / "w.bin", np.uint8)
    assert (disk[3 * PAGE: 3 * PAGE + 16] == 4).all()
    assert not (disk[1 * PAGE: 1 * PAGE + 16] == 2).any()  # outside mask
    # dirty-outside-mask stays dirty: the later unmasked sync persists it
    assert win.dirty_bytes(0) == 2 * PAGE
    assert win.sync(0) == 2 * PAGE
    disk = np.fromfile(tmp_path / "w.bin", np.uint8)
    assert (disk[1 * PAGE: 1 * PAGE + 16] == 2).all()
    assert (disk[5 * PAGE: 5 * PAGE + 16] == 6).all()
    win.free()


def test_masked_flush_async_ordered_after_rput(tmp_path):
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    win.rput(np.full(PAGE, 9, np.uint8), 0, 2 * PAGE)
    req = win.flush_async(0, mask=_mask(2))
    assert isinstance(req, Request)
    assert req.wait(timeout=10.0) == PAGE
    assert (np.fromfile(tmp_path / "w.bin", np.uint8)[2 * PAGE: 3 * PAGE]
            == 9).all()
    win.free()


def test_mask_requires_rank_and_non_dynamic(tmp_path):
    from repro.core import WindowError, alloc_mem
    comm = Communicator(2)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    with pytest.raises(WindowError):
        win.sync(None, mask=_mask(0))
    with pytest.raises(WindowError):
        win.flush_async(mask=_mask(0))
    win.free()
    dyn = Window.create_dynamic(Communicator(1))
    dyn.attach(0, alloc_mem(PAGE, info=storage_info(tmp_path, "d.bin")))
    with pytest.raises(WindowError):
        dyn.flush_async(0, mask=_mask(0, n=1))
    dyn.free()


def test_mask_on_memory_window_is_noop():
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE)
    win.put(np.full(8, 3, np.uint8), 0, 0)
    assert win.sync(0, mask=_mask(0)) == 0  # nothing to persist
    win.free()


def test_mask_wrong_length_raises(tmp_path):
    """A short mask would silently leave a dirty tail unselected (the old
    DirtyTracker truncation); the window now validates the block count and
    raises instead.  2-D masks of the right total size ravel cleanly."""
    from repro.core import WindowError
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    win.put(np.full(16, 1, np.uint8), 0, (PAGES - 1) * PAGE)  # dirty tail
    with pytest.raises(WindowError, match="blocks"):
        win.sync(0, mask=np.ones(PAGES - 1, bool))   # short
    with pytest.raises(WindowError, match="blocks"):
        win.sync(0, mask=np.ones(PAGES + 3, bool))   # long
    with pytest.raises(WindowError, match="blocks"):
        win.flush_async(0, mask=np.ones(2, bool))
    # the spans kwarg gets no padding leniency either (only the internal
    # device-diff path may pad, and it normalizes before reaching here)
    with pytest.raises(WindowError, match="blocks"):
        win.sync(0, mask=np.ones(2, bool),
                 spans=[(15 * PAGE, np.ones(16, np.uint8))])
    assert win.dirty_bytes(0) == PAGE  # nothing was taken by the rejects
    m2 = np.zeros((4, PAGES // 4), bool)
    m2[3, 3] = True  # ravels to block 15 -- the dirty tail page
    assert win.sync(0, mask=m2) == PAGE
    assert win.dirty_bytes(0) == 0
    win.free()


def test_mask_wrong_length_raises_combined(tmp_path):
    """Combined windows validate against the *window* block count, not the
    storage subrange's: a storage-coordinate mask is a geometry bug."""
    from repro.core import WindowError
    comm = Communicator(1)
    info = {**storage_info(tmp_path, "c.bin"), "storage_alloc_factor": "0.5"}
    win = Window.allocate(comm, PAGES * PAGE, info=info)
    assert win.flavor == "combined"
    win.put(np.full(16, 2, np.uint8), 0, 10 * PAGE)
    with pytest.raises(WindowError, match="blocks"):
        win.sync(0, mask=np.ones(8, bool))  # storage blocks, not window
    assert win.sync(0, mask=np.ones(PAGES, bool)) == PAGE
    win.free()


# -- sync_from_device ---------------------------------------------------------

def test_sync_from_device_ships_and_flushes_only_changed_pages(tmp_path):
    jnp = pytest.importorskip("jax.numpy")
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    elems = PAGES * PAGE // 4
    snap = np.arange(elems, dtype=np.float32)
    win.put(snap, 0, 0)
    win.sync(0)
    backing = win.segments[0].backing
    base_flushed = backing.bytes_flushed
    cur = snap.copy()
    cur[(PAGE // 4) * 4 + 1] += 1.0   # page 4
    cur[(PAGE // 4) * 11] += 2.0      # page 11
    req = win.sync_from_device(0, jnp.asarray(cur), jnp.asarray(snap))
    assert req.wait(timeout=10.0) == 2 * PAGE
    assert backing.bytes_flushed - base_flushed == 2 * PAGE
    assert (np.fromfile(tmp_path / "w.bin", np.float32) == cur).all()
    assert win.dirty_bytes(0) == 0
    win.free()


def test_sync_from_device_all_clean_is_free(tmp_path):
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    snap = np.arange(PAGES * PAGE // 4, dtype=np.float32)
    win.put(snap, 0, 0)
    win.sync(0)
    assert win.sync_from_device(0, snap, snap, blocking=True) == 0
    win.free()


def test_sync_from_device_unaligned_disp_conservative(tmp_path):
    """A non-page-aligned target_disp straddles window pages; the masked
    flush must still persist every changed byte."""
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    disp = PAGE + 100  # element 0 sits 100 bytes into page 1
    n = 4 * PAGE // 4
    snap = np.arange(n, dtype=np.float32)
    win.put(snap, 0, disp)
    win.sync(0)
    cur = snap.copy()
    cur[0] += 1.0
    cur[-1] += 1.0
    flushed = win.sync_from_device(0, cur, snap, target_disp=disp,
                                   blocking=True)
    assert flushed >= 2 * PAGE  # straddling may flush the extra page
    raw = np.fromfile(tmp_path / "w.bin", np.uint8)
    got = raw[disp: disp + n * 4].view(np.float32)
    assert (got == cur).all()
    win.free()


def test_device_dirty_mask_feeds_flush(tmp_path):
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    snap = np.zeros(PAGES * PAGE // 4, np.float32)
    cur = snap.copy()
    cur[(PAGE // 4) * 6 + 7] = 5.0
    mask = win.device_dirty_mask(0, cur, snap)
    assert mask.tolist() == _mask(6).tolist()
    # the mask composes with host-side writes: put everything, flush masked
    win.put(cur, 0, 0)
    assert win.sync(0, mask=mask) == PAGE
    assert win.dirty_bytes(0) == (PAGES - 1) * PAGE
    win.free()


# -- sharded device state: merged masks, one flush ----------------------------

def test_sync_shards_from_device_merges_masks(tmp_path):
    jnp = pytest.importorskip("jax.numpy")
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    a_snap = np.zeros(3 * PAGE // 4, np.float32)   # pages 0-2
    b_snap = np.ones(4 * PAGE // 4, np.float32)    # pages 8-11
    win.put(a_snap, 0, 0)
    win.put(b_snap, 0, 8 * PAGE)
    win.sync(0)
    backing = win.segments[0].backing
    base_flushed = backing.bytes_flushed
    a_cur = a_snap.copy()
    a_cur[(PAGE // 4) + 1] = 5.0                   # page 1
    b_cur = b_snap.copy()
    b_cur[0] = 6.0                                 # page 8
    b_cur[-1] = 7.0                                # page 11
    req = win.sync_shards_from_device(
        0, [(jnp.asarray(a_cur), jnp.asarray(a_snap), 0),
            (jnp.asarray(b_cur), jnp.asarray(b_snap), 8 * PAGE)])
    assert req.wait(timeout=30.0) == 3 * PAGE
    assert backing.bytes_flushed - base_flushed == 3 * PAGE
    disk = np.fromfile(tmp_path / "w.bin", np.float32)
    assert (disk[: a_cur.size] == a_cur).all()
    assert (disk[8 * PAGE // 4: 12 * PAGE // 4] == b_cur).all()
    assert win.dirty_bytes(0) == 0
    win.free()


def test_sync_shards_validation(tmp_path):
    from repro.core import WindowError
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    with pytest.raises(WindowError, match="at least one shard"):
        win.sync_shards_from_device(0, [], blocking=True)
    a = np.zeros(PAGE // 4, np.float32)
    with pytest.raises(WindowError, match="dtype mismatch"):
        win.sync_shards_from_device(
            0, [(a, a.astype(np.float64), 0)], blocking=True)
    with pytest.raises(WindowError, match="shape mismatch"):
        win.sync_shards_from_device(0, [(a, a[:-1], 0)], blocking=True)
    win.free()


def test_sync_shards_overlap_raises(tmp_path):
    """Overlapping (target_disp, nelems) shard regions are rejected: they
    would be applied in list order, silently losing earlier writes."""
    from repro.core import WindowError
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    a = np.zeros(2 * PAGE // 4, np.float32)        # bytes [0, 2*PAGE)
    b = np.ones(PAGE // 4, np.float32)             # bytes [PAGE, 2*PAGE)
    with pytest.raises(WindowError, match="overlap"):
        win.sync_shards_from_device(
            0, [(a, a.copy(), 0), (b, b.copy(), PAGE)], blocking=True)
    # adjacent (touching, not overlapping) regions stay legal
    win.sync_shards_from_device(
        0, [(a, a.copy(), 0), (b, b.copy(), 2 * PAGE)], blocking=True)
    win.free()


def test_sync_shards_packed_single_transfer(tmp_path):
    """The fused diff+pack path moves all changed bytes of a shard set in
    ONE device->host payload transfer (plus one tiny bitmap fetch), and
    the on-disk result is byte-identical to the per-span fallback."""
    jnp = pytest.importorskip("jax.numpy")
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    a_snap = np.zeros(4 * PAGE // 4, np.float32)   # pages 0-3
    b_snap = np.ones(4 * PAGE // 4, np.float32)    # pages 8-11
    win.put(a_snap, 0, 0)
    win.put(b_snap, 0, 8 * PAGE)
    win.sync(0)
    a_cur = a_snap.copy()
    a_cur[1] = 5.0                                 # page 0
    a_cur[3 * PAGE // 4 + 7] = 6.0                 # page 3
    b_cur = b_snap.copy()
    b_cur[PAGE // 4] = 7.0                         # page 9
    shards = [(jnp.asarray(a_cur), jnp.asarray(a_snap), 0),
              (jnp.asarray(b_cur), jnp.asarray(b_snap), 8 * PAGE)]
    win.sync_shards_from_device(0, shards, impl="interpret", blocking=True)
    st = win.device_sync_stats()
    assert st["syncs"] == 1
    assert st["payload_transfers"] == 1, st       # ONE fetch per shard set
    assert st["bitmap_transfers"] == 1
    assert st["span_transfers"] == 0              # no per-span slicing
    assert st["payload_bytes"] == 3 * PAGE        # exactly the dirty pages
    disk = np.fromfile(tmp_path / "w.bin", np.float32)
    assert (disk[: a_cur.size] == a_cur).all()
    assert (disk[8 * PAGE // 4: 12 * PAGE // 4] == b_cur).all()

    # host fallback over the same change set: same bytes, per-span fetches
    win2 = Window.allocate(comm, PAGES * PAGE,
                           info=storage_info(tmp_path, "w2.bin"))
    win2.put(a_snap, 0, 0)
    win2.put(b_snap, 0, 8 * PAGE)
    win2.sync(0)
    win2.sync_shards_from_device(0, shards, impl="ref", blocking=True)
    st2 = win2.device_sync_stats()
    assert st2["payload_transfers"] == 0 and st2["span_transfers"] == 3
    disk2 = np.fromfile(tmp_path / "w2.bin", np.float32)
    assert (disk2 == disk).all()                  # byte-identical layout
    win2.free()
    win.free()


@pytest.mark.parametrize("narrow_dirty", [True, False])
def test_sync_shards_mixed_widths(tmp_path, narrow_dirty):
    """An f32 shard and a uint16 shard at page-aligned displacements: the
    uint16 one is diffed and packed in its own tiles (counted in
    ``narrow_tile_shards``), the file ends up equal to both shards' bytes,
    and the payload takes one transfer per width that has dirty pages."""
    jnp = pytest.importorskip("jax.numpy")
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    f_snap = np.arange(4 * PAGE // 4, dtype=np.float32)     # pages 0-3
    h_snap = np.arange(6 * PAGE // 2, dtype=np.uint16)      # pages 6-11
    win.put(f_snap, 0, 0)
    win.put(h_snap, 0, 6 * PAGE)
    win.sync(0)
    f_cur = f_snap.copy()
    f_cur[PAGE // 4 + 3] = -1.0                             # page 1
    h_cur = h_snap.copy()
    if narrow_dirty:
        h_cur[5] ^= 0x8000                                  # page 6
        h_cur[4 * PAGE // 2 + 9] += 1                       # page 10
    win.sync_shards_from_device(
        0, [(jnp.asarray(f_cur), jnp.asarray(f_snap), 0),
            (jnp.asarray(h_cur), jnp.asarray(h_snap), 6 * PAGE)],
        impl="interpret", blocking=True)
    st = win.device_sync_stats()
    assert st["narrow_tile_shards"] == 1
    assert st["bitmap_transfers"] == 1
    assert st["payload_transfers"] == (2 if narrow_dirty else 1), st
    assert st["payload_bytes"] == (3 if narrow_dirty else 1) * PAGE
    raw = np.fromfile(tmp_path / "w.bin", np.uint8)
    assert (raw[:f_cur.nbytes] == f_cur.view(np.uint8)).all()
    assert (raw[6 * PAGE:6 * PAGE + h_cur.nbytes]
            == h_cur.view(np.uint8)).all()
    win.free()


def test_offload_opt_sync_masters_from_device(tmp_path):
    """Device-resident master weights persist through the merged shard
    mask: only the changed pages of the changed tensors flush."""
    pytest.importorskip("jax.numpy")
    from repro.train.offload_opt import OutOfCoreAdamW
    from repro.train.optimizer import AdamWConfig

    cfg = AdamWConfig(lr=1e-2, warmup_steps=0, total_steps=10,
                      clip_norm=0.0, weight_decay=0.0)
    shapes = {"w": ((2 * PAGE // 4,), np.float32),
              "b": ((PAGE // 4,), np.float32)}
    params = {k: np.arange(int(np.prod(s[0])), dtype=np.float32)
              for k, s in shapes.items()}
    oo = OutOfCoreAdamW(Communicator(1), shapes, str(tmp_path), cfg)
    oo.initialize(params)
    oo.state.sync()  # clean baseline
    old = oo.masters()
    new = {k: v.copy() for k, v in old.items()}
    new["w"][(PAGE // 4) + 3] += 1.0   # one page of w; b untouched
    flushed = oo.sync_masters_from_device(new, old)
    assert flushed == PAGE
    assert (oo.state.get("master/w") == new["w"]).all()
    assert (oo.state.get("master/b") == old["b"]).all()
    assert oo.state.win.dirty_bytes(0) == 0
    # sparse update: a name absent from masters is skipped outright
    assert oo.sync_masters_from_device({}, {}) == 0
    with pytest.raises(ValueError, match="window layout"):
        oo.sync_masters_from_device(
            {"w": new["w"].astype(np.float64)},
            {"w": old["w"].astype(np.float64)})
    oo.free()


# -- combined windows: mask offsets respect the memory/storage split ----------

def test_combined_mask_offset_translation(tmp_path):
    comm = Communicator(1)
    info = {**storage_info(tmp_path, "c.bin"), "alloc_type": "storage",
            "storage_alloc_factor": "0.5"}
    win = Window.allocate(comm, PAGES * PAGE, info=info)
    assert win.flavor == "combined"
    seg = win.segments[0]
    assert seg.mem_bytes == 8 * PAGE and seg.sto_bytes == 8 * PAGE
    # window page 10 = storage page 2 (memory_first: storage starts at 8)
    win.put(np.full(32, 7, np.uint8), 0, 10 * PAGE)
    win.put(np.full(32, 8, np.uint8), 0, 12 * PAGE)
    assert win.sync(0, mask=_mask(10)) == PAGE
    disk = np.fromfile(tmp_path / "c.bin", np.uint8)
    assert (disk[2 * PAGE: 2 * PAGE + 32] == 7).all()
    assert win.dirty_bytes(0) == PAGE  # page 12 still dirty
    # a mask naming only memory pages selects nothing storage-side
    assert win.sync(0, mask=_mask(0, 3, 7)) == 0
    assert win.sync(0) == PAGE
    win.free()


# -- checkpoint manager: snapshot-diff staging --------------------------------

def test_ckpt_snapshot_diff_puts_and_flushes_only_changed(tmp_path):
    comm = Communicator(1)
    specs = {"big": ((1 << 16,), np.float32), "tiny": ((4,), np.float32)}
    cm = CheckpointManager(str(tmp_path), comm, specs, double_buffer=False)
    big = np.random.default_rng(0).standard_normal(1 << 16).astype(np.float32)
    f1 = cm.save(1, {"big": big, "tiny": np.zeros(4, np.float32)})
    backing = cm.windows["a"].win.segments[0].backing
    writes_before = backing.tracker.dirty_count
    f2 = cm.save(2, {"big": big, "tiny": np.ones(4, np.float32)})
    assert f1 >= (1 << 18) and f2 == PAGE  # exactly the changed page
    assert writes_before == 0  # staging itself dirtied nothing extra
    r = cm.restore()
    assert r.step == 2 and (r.tree["big"] == big).all() \
        and (r.tree["tiny"] == 1).all()
    cm.close()


def test_ckpt_snapshot_diff_async_roundtrip(tmp_path):
    comm = Communicator(1)
    specs = {"w": ((256, 256), np.float32)}
    cm = CheckpointManager(str(tmp_path), comm, specs)
    w = np.ones((256, 256), np.float32)
    cm.save_async(1, {"w": w})
    w2 = w.copy()
    w2[0, 0] = 5.0
    cm.save_async(2, {"w": w2})   # window B: first save, full
    cm.save_async(3, {"w": w})    # window A again: diff vs step 1
    cm.wait()
    assert cm.saves == 3
    r = cm.restore()
    assert r.step == 3 and (r.tree["w"] == w).all()
    cm.close()


def _per_byte_model_pages(wt, t_old, t_new, ps) -> int:
    """Independent per-byte model of the snapshot diff: lay both trees out
    at their slot offsets and count pages holding any differing byte."""
    size = wt.win.segments[0].size
    bufs = []
    for tree in (t_old, t_new):
        buf = np.zeros(size, np.uint8)
        for k, slot in wt.slots.items():
            raw = np.ascontiguousarray(tree[k], slot.dtype).view(
                np.uint8).ravel()
            buf[slot.offset: slot.offset + raw.nbytes] = raw
        bufs.append(buf)
    old, new = bufs
    changed = 0
    for lo in range(0, size, ps):
        if not np.array_equal(old[lo: lo + ps], new[lo: lo + ps]):
            changed += 1
    return changed


def test_ckpt_sharded_merged_mask_matches_per_byte_model(tmp_path):
    """Each slot stages as a shard; the merged mask's flush must equal the
    per-byte model's changed-page count exactly -- across slots, scattered
    changes, and an untouched tensor."""
    comm = Communicator(1)
    specs = {"a": ((4 * PAGE // 4,), np.float32),
             "b": ((6 * PAGE // 4,), np.float32),
             "c": ((8,), np.float32)}
    cm = CheckpointManager(str(tmp_path), comm, specs, double_buffer=False)
    rng = np.random.default_rng(11)
    t1 = {k: rng.standard_normal(int(np.prod(s[0]))).astype(np.float32)
          for k, s in specs.items()}
    cm.save(1, t1)
    t2 = {k: v.copy() for k, v in t1.items()}
    t2["a"][PAGE // 4 + 5] += 1.0        # one page of a
    t2["b"][0] += 1.0                    # first page of b
    t2["b"][-1] += 1.0                   # last (partial) page of b
    wt = cm.windows["a"]
    expected = _per_byte_model_pages(wt, t1, t2, PAGE)
    f2 = cm.save(2, t2)
    assert f2 == expected * PAGE == 3 * PAGE
    r = cm.restore()
    assert r.step == 2
    for k in specs:
        assert (r.tree[k] == t2[k]).all(), k
    cm.close()


# -- crash-replay: manifest never ahead of data -------------------------------

class _DiskDies(OSError):
    pass


def _fail_after(backing, n_calls):
    """Kill the write-back pipeline after ``n_calls`` pwrites (mid-flush)."""
    orig = backing.file.pwrite
    state = {"n": 0}

    def dying(offset, data):
        state["n"] += 1
        if state["n"] > n_calls:
            raise _DiskDies("disk died mid-flush")
        return orig(offset, data)

    backing.file.pwrite = dying
    return lambda: setattr(backing.file, "pwrite", orig)


def _manifest_step(tmp_path) -> int:
    import json
    with open(tmp_path / "manifest.json") as f:
        return int(json.load(f)["step"])


def test_crash_mid_save_async_never_commits_manifest_ahead_of_data(tmp_path):
    comm = Communicator(1)
    specs = {"w": ((1 << 15,), np.float32)}
    cm = CheckpointManager(str(tmp_path), comm, specs, double_buffer=False)
    w1 = np.random.default_rng(1).standard_normal(1 << 15).astype(np.float32)
    cm.save(1, {"w": w1})
    backing = cm.windows["a"].win.segments[0].backing

    # change two *scattered* page regions -> two dirty runs -> two pwrites;
    # killing after the first dies genuinely mid-flush
    w2 = w1.copy()
    w2[: PAGE // 4] += 1.0
    w2[-(PAGE // 4):] += 1.0
    undo = _fail_after(backing, 1)  # first run lands, then the disk dies
    req = cm.save_async(2, {"w": w2})
    with pytest.raises(_DiskDies):
        req.wait(timeout=30.0)
    # the manifest was never committed ahead of the (partial) data flush
    assert _manifest_step(tmp_path) == 1
    with pytest.raises(_DiskDies):
        cm.wait()  # surfaces the failure to the manager (invalidates snap)
    assert cm.saves == 1
    undo()

    # replay-but-never-skip: the retry must rewrite *everything* the failed
    # flush took (tracker restore + snapshot invalidation), so the
    # recommitted checkpoint CRC-validates from a cold restart
    cm.save(2, {"w": w2})
    assert _manifest_step(tmp_path) == 2
    cm2 = CheckpointManager.open_for_restore(str(tmp_path), Communicator(1),
                                             specs)
    r = cm2.restore()
    assert r is not None and not r.fell_back
    assert r.step == 2 and (r.tree["w"] == w2).all()
    cm2.close()
    cm.close()


def test_crash_mid_blocking_save_keeps_previous_checkpoint(tmp_path):
    comm = Communicator(1)
    specs = {"w": ((1 << 14,), np.float32)}
    cm = CheckpointManager(str(tmp_path), comm, specs, double_buffer=False)
    w1 = np.full(1 << 14, 3.0, np.float32)
    cm.save(7, {"w": w1})
    backing = cm.windows["a"].win.segments[0].backing
    undo = _fail_after(backing, 0)  # nothing lands
    with pytest.raises(_DiskDies):
        cm.save(8, {"w": w1 * 2})
    undo()
    assert _manifest_step(tmp_path) == 7
    # "crash": restart cold -- disk still holds step 7's bytes, CRC intact
    # (the in-process page cache holds the staged-but-unflushed step 8)
    cm2 = CheckpointManager.open_for_restore(str(tmp_path), Communicator(1),
                                             specs)
    r = cm2.restore()
    assert r is not None and r.step == 7 and (r.tree["w"] == 3.0).all()
    cm2.close()
    cm.close()


# -- out-of-core optimizer: write-behind skips untouched blocks ---------------

def test_offload_opt_selective_write_behind(tmp_path):
    from repro.train.offload_opt import OutOfCoreAdamW
    from repro.train.optimizer import AdamWConfig

    cfg = AdamWConfig(lr=1e-2, warmup_steps=0, total_steps=10,
                      clip_norm=0.0, weight_decay=0.01)
    rng = np.random.default_rng(3)
    params = {"w": rng.standard_normal((64, 16)).astype(np.float32),
              "norm/b": np.zeros(2048, np.float32)}  # not decayed
    oo = OutOfCoreAdamW(Communicator(1),
                        {k: (v.shape, v.dtype) for k, v in params.items()},
                        str(tmp_path), cfg, block_bytes=1024)
    oo.initialize(params)
    oo.state.sync()  # clean baseline

    grads = {"w": rng.standard_normal((64, 16)).astype(np.float32),
             "norm/b": np.zeros(2048, np.float32)}
    out = oo.update(grads)
    assert set(out) == {"norm/b", "w"}
    assert (out["norm/b"] == 0).all()  # provable no-op, still returned
    assert oo.blocks_skipped == 8  # zero-grad blocks never wrote back
    # touched-only sync flushes just w's state pages (m, v, master)
    flushed = oo.sync(touched_only=True)
    assert 0 < flushed <= 3 * 2 * PAGE
    assert oo.state.win.dirty_bytes(0) == 0  # skipped blocks stayed clean

    # sparse update: a key absent from grads is untouched end to end
    out = oo.update({"w": grads["w"]})
    assert set(out) == {"w"}
    assert oo.sync(touched_only=True) > 0
    assert oo.sync(touched_only=True) == 0  # nothing touched since
    oo.free()


def test_offload_opt_touched_mask_survives_flush_failure(tmp_path):
    """A failed touched-only flush must restore the mask: the retry replays
    the touched blocks instead of reporting 0 (replay-never-skip)."""
    from repro.train.offload_opt import OutOfCoreAdamW
    from repro.train.optimizer import AdamWConfig

    cfg = AdamWConfig(lr=1e-2, warmup_steps=0, total_steps=10,
                      clip_norm=0.0, weight_decay=0.01)
    params = {"w": np.ones((256, 16), np.float32)}
    oo = OutOfCoreAdamW(Communicator(1), {"w": ((256, 16), np.float32)},
                        str(tmp_path), cfg, block_bytes=1024)
    oo.initialize(params)
    oo.state.sync()
    oo.update({"w": np.ones((256, 16), np.float32)})
    backing = oo.state.win.segments[0].backing
    undo = _fail_after(backing, 0)
    with pytest.raises(_DiskDies):
        oo.sync(touched_only=True)
    undo()
    assert oo.sync(touched_only=True) > 0  # mask restored, retry flushes
    assert oo.state.win.dirty_bytes(0) == 0
    oo.free()


def test_ckpt_span_apply_failure_invalidates_snapshot(tmp_path):
    """A failure while the masked span-write applies the staged spans (a
    cache write dying mid-way) leaves a mixed page cache; the snapshot must
    be dropped so the next save replays a full put + unmasked flush and the
    checkpoint CRC-validates."""
    comm = Communicator(1)
    specs = {"w": ((1 << 14,), np.float32)}
    cm = CheckpointManager(str(tmp_path), comm, specs, double_buffer=False)
    w1 = np.random.default_rng(5).standard_normal(1 << 14).astype(np.float32)
    cm.save(1, {"w": w1})
    wt = cm.windows["a"]

    w2 = w1.copy()
    w2[: PAGE // 4] += 1.0
    w2[-(PAGE // 4):] += 1.0  # two scattered changed regions -> two spans
    seg = wt.win.segments[0]
    orig_write = seg.write
    calls = {"n": 0}

    def dying_write(offset, data):
        calls["n"] += 1
        if calls["n"] > 1:
            raise _DiskDies("cache write hit a dead disk")
        return orig_write(offset, data)

    seg.write = dying_write
    with pytest.raises(_DiskDies):
        cm.save(2, {"w": w2})
    seg.write = orig_write
    assert calls["n"] == 2  # died genuinely mid-apply
    assert "a" not in cm._snapshots  # stale snapshot dropped
    assert _manifest_step(tmp_path) == 1

    cm.save(2, {"w": w2})  # full replay: no diff against the mixed cache
    cm2 = CheckpointManager.open_for_restore(str(tmp_path), Communicator(1),
                                             specs)
    r = cm2.restore()
    assert r is not None and not r.fell_back
    assert r.step == 2 and (r.tree["w"] == w2).all()
    cm2.close()
    cm.close()


# -- window-level backpressure ------------------------------------------------

def test_backpressure_no_deadlock_inside_exclusive_epoch(tmp_path):
    """rput batching inside an exclusive lock epoch (the module's documented
    MPI pattern) must not deadlock under backpressure: the queued tasks are
    blocked on the caller's own lock, so the stall is bypassed for that
    thread (bytes still charged, watermark transiently exceeded)."""
    high = 8 * PAGE
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path),
                          max_inflight_bytes=high, low_watermark=2 * PAGE)
    data = np.full(4 * PAGE, 5, np.uint8)
    win.lock(0, exclusive=True)
    try:
        reqs = [win.rput(data, 0, 0), win.rput(data, 0, 4 * PAGE),
                win.rput(data, 0, 8 * PAGE)]  # 12 pages queued > high mark
        assert not reqs[0].test()  # all blocked on our exclusive lock
    finally:
        win.unlock(0)
    Request.waitall(reqs, timeout=30.0)
    assert (win.get(0, 8 * PAGE, 4 * PAGE) == 5).all()
    win.free()


def test_backpressure_no_deadlock_inside_shared_epoch(tmp_path):
    """The shared-epoch variant: the caller's reader hold blocks a queued
    exclusive-acquiring task (raccumulate) whose charge keeps in-flight
    above the watermark; a stalled submit could never drain, so the epoch
    holder bypasses the stall."""
    high = 4 * PAGE
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path),
                          max_inflight_bytes=high, low_watermark=PAGE)
    acc = np.ones(high // 8, np.int64)  # charge == high watermark
    win.lock(0, exclusive=False)
    try:
        blocked = win.raccumulate(acc, 0, 0, "sum")  # waits on our reader
        reqs = [win.rput(np.full(2 * PAGE, 3, np.uint8), 0, 8 * PAGE)
                for _ in range(3)]  # would stall without the epoch bypass
        assert not blocked.test()
    finally:
        win.unlock(0)
    Request.waitall([blocked] + reqs, timeout=30.0)
    assert (win.get(0, 8 * PAGE, 2 * PAGE) == 3).all()
    win.free()


def test_flush_charge_full_counts_only_storage_bytes(tmp_path):
    """full=True charges what a flush can actually write: the combined
    window's storage subrange, never the pinned memory part."""
    comm = Communicator(1)
    info = {**storage_info(tmp_path, "c.bin"), "storage_alloc_factor": "0.5"}
    win = Window.allocate(comm, PAGES * PAGE, info=info)
    assert win._flush_charge(0, True, None) == 8 * PAGE  # sto_bytes only
    win.free()
    mem = Window.allocate(comm, PAGES * PAGE)
    assert mem._flush_charge(0, True, None) == 0  # nothing to persist
    mem.free()


def test_window_backpressure_stats_and_bound(tmp_path):
    high, low = 8 * PAGE, 2 * PAGE
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path),
                          max_inflight_bytes=high, low_watermark=low)
    data = np.full(PAGE, 1, np.uint8)
    for i in range(64):
        win.rput(data, 0, (i % PAGES) * PAGE)
    win.flush(0)
    stats = win.pool_stats()
    assert stats["max_inflight_bytes"] <= high
    assert stats["completed_bytes"] == stats["submitted_bytes"] == 64 * PAGE
    win.free()


try:
    import multiprocessing.shared_memory  # noqa: F401
    _HAVE_SHM = True
except ImportError:  # pragma: no cover - exotic platforms
    _HAVE_SHM = False


@pytest.mark.skipif(not _HAVE_SHM,
                    reason="multiprocessing.shared_memory unavailable")
def test_crash_replay_mp_worker_death_never_commits_manifest(tmp_path):
    """The crash-replay invariant under the mp transport: a save whose
    owning worker is SIGKILLed fails loudly (TransportError) without
    committing its manifest, and a cold cross-transport restart restores
    the previous checkpoint CRC-intact (manifest never ahead of data)."""
    comm = Communicator(1, transport="mp")
    specs = {"w": ((1 << 14,), np.float32)}
    cm = CheckpointManager(str(tmp_path), comm, specs, double_buffer=False)
    w1 = np.random.default_rng(4).standard_normal(1 << 14).astype(np.float32)
    cm.save(5, {"w": w1})
    assert _manifest_step(tmp_path) == 5

    # SIGKILL the page-cache-owning worker: the next save's span apply
    # (flush task) dies before any of step 6's bytes can reach storage, so
    # no manifest may name step 6 -- the error surfaces at wait()
    comm.transport._procs[0].kill()
    comm.transport._procs[0].join(timeout=10)
    from repro.core import TransportError
    req = cm.save_async(6, {"w": w1 * 2})
    with pytest.raises(TransportError):
        req.wait(timeout=30.0)
    assert _manifest_step(tmp_path) == 5
    with pytest.raises(TransportError):
        cm.close()

    # cold restart under the *in-process* transport over the same files
    # (the byte-identical on-disk layout is the recovery contract)
    cm2 = CheckpointManager.open_for_restore(str(tmp_path), Communicator(1),
                                             specs, double_buffer=False)
    r = cm2.restore()
    assert r is not None and not r.fell_back
    assert r.step == 5 and (r.tree["w"] == w1).all()
    cm2.close()
    comm.close()


@pytest.mark.skipif(not _HAVE_SHM,
                    reason="multiprocessing.shared_memory unavailable")
def test_mp_codec_halves_wire_bytes_and_disk_is_exact(tmp_path):
    """Under the mp transport a compressible masked-span flush crosses the
    control channel encoded: wire bytes <= 50% of logical bytes, the
    owner decodes before applying, and the on-disk layout is byte-for-byte
    what the raw path would have written."""
    comm = Communicator(1, transport="mp")
    win = Window.allocate(comm, PAGES * PAGE, info=storage_info(tmp_path))
    data = np.zeros(4 * PAGE, np.uint8)            # pages 0-3, mostly zero
    data[::512] = 7
    win.sync(0, mask=_mask(0, 1, 2, 3),
             spans=[(0, data)])                    # staged-span flush path
    ws = comm.transport.wire_stats_snapshot()
    assert ws is not None and ws["spans_encoded_msgs"] >= 1
    assert ws["spans_logical_bytes"] >= 4 * PAGE
    assert ws["spans_wire_bytes"] * 2 <= ws["spans_logical_bytes"], ws

    # aggregated op trains take the same codec on their put payloads
    for i in range(16):
        win.rput(np.zeros(1024, np.uint8), 0, 8 * PAGE + i * 1024)
    win.flush(0)
    ws2 = comm.transport.wire_stats_snapshot()
    assert ws2["ops_encoded_msgs"] >= 1
    assert ws2["ops_wire_bytes"] * 2 <= ws2["ops_logical_bytes"], ws2

    disk = np.fromfile(tmp_path / "w.bin", np.uint8)
    assert (disk[: data.size] == data).all()       # decoded before applied
    assert not disk[data.size:].any()
    win.free()
    comm.close()
