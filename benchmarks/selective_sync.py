"""Device-mask selective sync vs full sync + bounded write-back queue.

Models the paper's core claim (selective ``MPI_Win_sync``) with the state
living "on device": each iteration mutates a small fraction of the window's
pages.  The *full* path re-puts the whole state and flushes everything; the
*selective* path runs ``Window.sync_from_device`` -- the Pallas
``dirty_diff`` bitmap restricts both the host copy and the write-back to
the changed pages.  Acceptance: with <=10% of blocks dirty the selective
path writes <=15% of the full path's bytes.

The suite runs cross-process too (``--transport mp`` or
``REPRO_TRANSPORT=mp``): the rank's page cache then lives in a real worker
process, the full path ships the whole state over the control channel every
iteration, and the selective path ships one masked span-write message --
the <=15% byte gate must hold with genuine process-boundary traffic.

Two lanes quantify the PCIe/wire halves of that pipeline.  The *fused
pack* lane runs the diff+pack kernel path of ``sync_shards_from_device``
and asserts, from the window's transfer accounting, that every changed
byte of a shard set crosses device->host in ONE compacted payload
transfer.  The *codec* lane (encoding transports only; ``--codec-only``
runs it standalone, jax-free) replays the same staged-span flush with the
span-wire codec forced off then on: compressible dirty pages must cross
the control channel at <=50% of the raw bytes, and incompressible noise
must take the RAW fallback at <=1.05x logical (header-only overhead).

The second half exercises backpressure: a window allocated with
``max_inflight_bytes`` (high watermark) takes a burst of rput+flush_async
traffic; queued write-back bytes must never exceed the high mark (the
pool's ``max_inflight_bytes`` stat is the observed high-water mark), so a
slow disk throttles producers instead of growing the queue without limit.
"""

from __future__ import annotations

import argparse

import numpy as np

from benchmarks.common import Bench, timer, workdir
from repro.core import Communicator, Window

PAGE = 4096
PAGES = 2048                 # 8 MiB window
SIZE = PAGES * PAGE
DIRTY_FRAC = 0.08            # <=10% of blocks dirty per iteration
ITERS = 4

CODEC_PAGES = 64             # compressed-vs-raw lane span payload (256 KiB)
PACK_PAGES = 128             # fused-pack lane window

HIGH_WATERMARK = 1 << 20     # backpressure: 1 MiB in flight max
LOW_WATERMARK = 256 << 10
BURST_CHUNK = 128 << 10
BURSTS = 64                  # 8 MiB total through a 1 MiB-bounded queue


def _mk_win(d: str, name: str, comm: Communicator, **kw) -> Window:
    return Window.allocate(comm, SIZE, info={
        "alloc_type": "storage",
        "storage_alloc_filename": f"{d}/{name}.bin"}, **kw)


def _mutate(rng, state: np.ndarray) -> np.ndarray:
    """Touch DIRTY_FRAC of the pages (one element each, page-spread)."""
    out = state.copy()
    elems_per_page = PAGE // 4
    pages = rng.choice(PAGES, size=int(PAGES * DIRTY_FRAC), replace=False)
    out[pages * elems_per_page] += 1.0
    return out


def run(bench: Bench, transport: str | None = None,
        codec_only: bool = False) -> None:
    # every window targets rank 0 only: pin the world to one rank so a
    # lane-wide REPRO_NRANKS doesn't spawn idle workers/segments
    comm = Communicator.from_env(1, transport=transport, nranks=1)
    try:
        _run_suites(bench, comm, codec_only=codec_only)
    finally:
        bench.record_wire(comm)
        comm.close()  # never leak mp workers, even on a failed gate


def _run_suites(bench: Bench, comm: Communicator,
                codec_only: bool = False) -> None:
    label = f"[{comm.transport.kind}]"
    with workdir("selsync") as d:
        if codec_only:
            # jax-free CI lane: just the span-wire codec gates
            _codec_suite(bench, comm, d, label)
            return
        _full_vs_selective_and_codec(bench, comm, d, label)


def _full_vs_selective_and_codec(bench: Bench, comm: Communicator, d: str,
                                 label: str) -> None:
    rng = np.random.default_rng(0)
    state = rng.standard_normal(SIZE // 4).astype(np.float32)

    # -- full path: re-put everything, flush everything ------------------
    win_f = _mk_win(d, "full", comm)
    win_f.put(state, 0, 0)
    win_f.sync(0)
    cur = _mutate(rng, state)  # warmup iteration (outside the timer)
    win_f.put(cur, 0, 0)
    win_f.sync(0, full=True)
    full_bytes = 0
    with timer() as tf:
        for _ in range(ITERS):
            cur = _mutate(rng, cur)
            win_f.put(cur, 0, 0)
            full_bytes += win_f.sync(0, full=True)
    win_f.free()

    # -- selective path: device diff -> masked flush ---------------------
    rng = np.random.default_rng(0)  # identical mutation sequence
    win_s = _mk_win(d, "selective", comm)
    win_s.put(state, 0, 0)
    win_s.sync(0)
    snap = _mutate(rng, state)  # warmup: jit the diff kernel off-clock
    win_s.sync_from_device(0, snap, state).wait()
    sel_bytes = 0
    with timer() as ts:
        for _ in range(ITERS):
            cur = _mutate(rng, snap)
            sel_bytes += win_s.sync_from_device(0, cur, snap).wait()
            snap = cur
    win_s.free()

    ratio = sel_bytes / max(1, full_bytes)
    bench.add(f"full_put_sync{label}", tf["s"], calls=ITERS,
              derived=f"{full_bytes >> 20}MiB")
    bench.add(f"selective_device_mask{label}", ts["s"], calls=ITERS,
              derived=f"{sel_bytes >> 10}KiB")
    bench.add(f"selective_vs_full_bytes{label}", 0.0,
              derived=f"{ratio:.3f}")
    assert ratio <= 0.15, (
        f"selective flush wrote {ratio:.1%} of full-sync bytes (>15%)")

    # -- compressed-vs-raw wire + fused-pack accounting ------------------
    _codec_suite(bench, comm, d, label)
    _fused_pack_suite(bench, comm, d, label)

    # -- backpressure: bounded in-flight write-back ----------------------
    win_b = _mk_win(d, "bounded", comm,
                    max_inflight_bytes=HIGH_WATERMARK,
                    low_watermark=LOW_WATERMARK)
    data = np.full(BURST_CHUNK, 7, np.uint8)
    with timer() as tb:
        for i in range(BURSTS):
            win_b.rput(data, 0, (i % (SIZE // BURST_CHUNK)) * BURST_CHUNK)
            if i % 8 == 7:
                win_b.flush_async(0)
        win_b.flush(0)
    stats = win_b.pool_stats()
    win_b.free()

    peak = stats["max_inflight_bytes"]
    bench.add(f"bounded_queue_burst{label}", tb["s"], calls=BURSTS,
              derived=f"peak={peak >> 10}KiB stalls={stats['stalls']}")
    bench.add(f"queue_peak_vs_watermark{label}", 0.0,
              derived=f"{peak / HIGH_WATERMARK:.2f}")
    assert peak <= HIGH_WATERMARK, (
        f"in-flight bytes peaked at {peak} > high watermark "
        f"{HIGH_WATERMARK}")


def _codec_suite(bench: Bench, comm: Communicator, d: str,
                 label: str) -> None:
    """Span-wire codec: compressed vs raw control-channel bytes.

    Only meaningful on encoding transports (mp/spmd): the same staged-span
    flush runs with the codec forced off, then forced on, and the wire-byte
    delta is gated at <=50% for compressible dirty pages.  Incompressible
    noise must take the RAW fallback: wire <= 1.05x logical (the per-message
    header is the only overhead), enforced as a second gate.
    """
    policy = comm.transport.codec_policy
    if policy is None:
        bench.add(f"codec_wire{label}", 0.0,
                  derived="skipped (in-process transport: no wire)")
        return
    win = _mk_win(d, "codec", comm)
    stats = comm.transport.wire_stats
    dirty = np.zeros(CODEC_PAGES * PAGE, np.uint8)
    dirty[::512] = 7             # sparse hot bytes: the selective-sync shape
    noise = np.random.default_rng(1).integers(
        0, 256, CODEC_PAGES * PAGE, dtype=np.uint8)
    mask = np.zeros(PAGES, bool)
    mask[:CODEC_PAGES] = True
    saved_mode = policy.mode

    def _flush(mode: str, payload: np.ndarray):
        policy.mode = mode
        before = stats.snapshot()
        with timer() as t:
            win.sync(0, mask=mask, spans=[(0, payload)])
        after = stats.snapshot()
        return (after["spans_logical_bytes"] - before["spans_logical_bytes"],
                after["spans_wire_bytes"] - before["spans_wire_bytes"],
                t["s"])

    try:
        _flush("off", dirty)     # warmup (page cache + channel)
        raw_l, raw_w, raw_t = _flush("off", dirty)
        enc_l, enc_w, enc_t = _flush("force", dirty)
        ratio = enc_w / max(1, raw_w)
        bench.add(f"codec_raw_spans{label}", raw_t,
                  derived=f"{raw_w >> 10}KiB wire")
        bench.add(f"codec_enc_spans{label}", enc_t,
                  derived=f"{enc_w}B wire")
        ok = bench.gate(f"codec_wire_ratio{label}", ratio, 0.5, unit="x")
        assert ok, (
            f"compressed spans used {ratio:.1%} of raw wire bytes (>50%)")

        noise_l, noise_w, noise_t = _flush("force", noise)
        overhead = noise_w / max(1, noise_l)
        bench.add(f"codec_noise_fallback{label}", noise_t,
                  derived=f"wire/logical={overhead:.4f} "
                          f"t={noise_t / max(raw_t, 1e-9):.2f}x raw")
        ok = bench.gate(f"codec_noise_overhead{label}", overhead, 1.05,
                        unit="x")
        assert ok, (
            f"raw fallback wire overhead {overhead:.3f}x > 1.05x logical")
    finally:
        policy.mode = saved_mode
        win.free()


def _fused_pack_suite(bench: Bench, comm: Communicator, d: str,
                      label: str) -> None:
    """Fused diff+pack: one device->host payload transfer per shard set.

    The reference path fetches every dirty run separately; the packed
    path must fetch exactly ONE compacted payload (plus one tiny bitmap)
    per ``sync_shards_from_device`` call, asserted from the window's
    transfer accounting.  The platform picks the implementation: the
    lane runs where that is the compiled kernel (a TPU).
    """
    try:
        import jax
        import jax.numpy as jnp

        from repro.kernels.ops import use_pallas
    except ImportError:
        bench.add(f"fused_pack{label}", 0.0, derived="skipped (no jax)")
        return
    if not use_pallas():
        bench.add(f"fused_pack{label}", 0.0,
                  derived=f"skipped ({jax.default_backend()} runs the "
                          "reference path)")
        return
    win = Window.allocate(comm, PACK_PAGES * PAGE, info={
        "alloc_type": "storage",
        "storage_alloc_filename": f"{d}/pack.bin"})
    rng = np.random.default_rng(2)
    elems = PACK_PAGES * PAGE // 4
    snap = rng.standard_normal(elems).astype(np.float32)
    win.put(snap, 0, 0)
    win.sync(0)
    epp = PAGE // 4
    # warmup: trace/compile the pack kernel off-clock
    cur = snap.copy()
    cur[0] += 1.0
    win.sync_shards_from_device(0, [(jnp.asarray(cur), jnp.asarray(snap), 0)],
                                blocking=True)
    snap = cur
    with timer() as tp:
        for _ in range(ITERS):
            cur = snap.copy()
            pages = rng.choice(PACK_PAGES,
                               size=max(1, PACK_PAGES // 12), replace=False)
            cur[pages * epp] += 1.0
            win.sync_shards_from_device(
                0, [(jnp.asarray(cur), jnp.asarray(snap), 0)],
                blocking=True)
            snap = cur
    st = win.device_sync_stats()
    win.free()
    assert st["pallas_syncs"] == st["syncs"], st
    per_sync = st["payload_transfers"] / max(1, st["syncs"])
    bench.add(f"fused_pack{label}", tp["s"], calls=ITERS,
              derived=f"{st['payload_bytes'] >> 10}KiB in "
                      f"{st['payload_transfers']} transfers")
    ok = bench.gate(f"pack_transfers_per_sync{label}", per_sync, 1.0,
                    unit="x")
    assert ok and st["span_transfers"] == 0, (
        f"fused pack did {per_sync:.2f} payload transfers/sync "
        f"(want 1) + {st['span_transfers']} span fetches (want 0)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--transport", choices=("inproc", "mp", "tcp"), default=None,
                    help="window transport (default: $REPRO_TRANSPORT or "
                         "inproc)")
    ap.add_argument("--codec-only", action="store_true",
                    help="run only the span-wire codec gates (jax-free; "
                         "the CI compressed-sync lane)")
    args = ap.parse_args()
    b = Bench("selective_sync")
    # every gate asserts on failure (exit 1): <=15% selective bytes,
    # <=50% compressed wire, <=1.05x raw fallback, 1 transfer/sync
    run(b, transport=args.transport, codec_only=args.codec_only)
    b.emit()
