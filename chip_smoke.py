#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, at full width.

    python chip_smoke.py              # one chip: device, window_sync, train
    python chip_smoke.py --chips 4    # the sharded fused-training phase only

Phases, in one process (no child process touches JAX):

* ``device``      -- the TPU JAX sees, versions, host RAM and free disk.
  Anything but a TPU ends the run with a non-zero exit.
* ``window_sync`` -- 1 GiB of f32 state on the chip, ~8% of its 4 KiB
  pages dirtied from ``--seed``, synced into a storage window through
  ``Window.sync_from_device`` by the compiled Pallas kernels; the storage
  file must match the expected bytes bit for bit.  Then three shards of
  unequal size (f32, bf16, int8; the int8 one fully dirty) through
  ``sync_shards_from_device``.
* ``train``       -- internlm2-1.8b, the whole config (1.89 B params),
  offload mode (the out-of-core AdamW keeps the f32 masters and Adam
  moments in a storage window on the host), batch 4 x seq 2048, 4 steps,
  async checkpoints every 2 steps; then a fresh Trainer restores and its
  params must equal the saved ones bit for bit.  Where the host cannot
  hold the 22.7 GB of optimizer state beside what the process holds,
  ``offload_memory_budget`` bounds it and the rest spills to storage.
* ``mesh_train`` (``--chips 4`` only) -- internlm2-1.8b fused at full
  width on a (data, model) mesh of the 4 chips: 4 uninterrupted steps,
  then 2 steps with a checkpoint at step 2, a restore, and steps 2-3
  again; their losses must be bit-identical.

Each phase prints one JSON line.  The last line of a passing run is
``{"ok": true, "device": {...}}``; a failing phase raises, and the run
exits non-zero without that line.  Data, windows and checkpoints go to
``--run-dir`` (default ``<checkout>/chip_smoke_run``), emptied before and
after the run.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

PAGE = 4096
GiB = 1 << 30
ARCH = "internlm2-1.8b"
#: host bytes per param the train phase holds besides the optimizer
#: state: the restored tree of bf16 params
CHECK_BYTES_PER_PARAM = 2
#: host memory kept free for the walk's per-tensor buffers (f32 gradient
#: and new param of the vocab-sized tensors, ~2.5 GB), the runtime's
#: growth, and the limit a machine may enforce below its MemTotal (a
#: one-chip TPU v5e machine reports 45 GiB and stops a process at 40)
HOST_RESERVE = 12 * GiB


def emit(phase: str, t0: float, **fields) -> None:
    print(json.dumps({"phase": phase, "seconds": time.perf_counter() - t0,
                      **fields}), flush=True)


def progress(what: str, **fields) -> None:
    """A progress line on stderr: the end of a failed run says how far it
    got, and stdout keeps one line per phase."""
    print(json.dumps({"at": time.strftime("%H:%M:%S"), "what": what,
                      **host_memory(), **fields}),
          file=sys.stderr, flush=True)


def meminfo() -> dict[str, int]:
    out = {}
    with open("/proc/meminfo") as f:
        for line in f:
            key, val = line.split(":", 1)
            out[key] = int(val.split()[0]) * 1024
    return out


def host_memory() -> dict[str, int]:
    """This process's resident bytes (by kind where the kernel says) and
    the host's available memory."""
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            key, val = line.split(":", 1)
            if key in ("VmRSS", "RssAnon", "RssFile", "RssShmem"):
                out[key] = int(val.split()[0]) * 1024
    out["MemAvailable"] = meminfo()["MemAvailable"]
    return out


def host_limit() -> int:
    """Host memory this process may use: the host's, capped by the
    cgroup's limit where one is set."""
    limit = meminfo()["MemTotal"]
    for path in ("/sys/fs/cgroup/memory.max",
                 "/sys/fs/cgroup/memory/memory.limit_in_bytes"):
        try:
            with open(path) as f:
                raw = f.read().strip()
        except OSError:
            continue
        if raw.isdigit():
            limit = min(limit, int(raw))
    return limit


def release_host_memory() -> None:
    """Between phases: drop JAX's caches of compiled programs and the
    host buffers they hold, and hand freed heap back to the OS."""
    import ctypes

    import jax
    jax.clear_caches()
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):  # not glibc
        pass


def peak_bytes(devices) -> list[int | None]:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


# -- phase 1 -------------------------------------------------------------------

def device_phase(run_dir: str) -> dict:
    import importlib.metadata

    import jax
    t0 = time.perf_counter()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX sees {len(devs)} "
                         f"{devs[0].platform} device(s)")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    mem = meminfo()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("device", t0, device=device, jax=jax.__version__, libtpu=libtpu,
         host_ram_bytes=mem["MemTotal"],
         host_ram_available_bytes=mem["MemAvailable"],
         host_limit_bytes=host_limit(), **host_memory(),
         disk_free_bytes=shutil.disk_usage(run_dir).free)
    return device


# -- phase 2 -------------------------------------------------------------------

def _dirty(x, pages: int, frac: float, rng):
    """Change one seeded element in ``frac`` of the pages of 1-D ``x``;
    returns (new x, sorted dirty page ids)."""
    import jax.numpy as jnp
    import numpy as np
    epp = PAGE // x.dtype.itemsize
    dirty = np.sort(rng.choice(pages, size=max(1, int(pages * frac)),
                               replace=False))
    idx = dirty * epp + rng.integers(0, epp, size=len(dirty))
    return x.at[jnp.asarray(idx)].add(jnp.asarray(1, x.dtype)), dirty


def _check_flags(cur, snap, dirty, impl) -> None:
    """Kernel flags == jnp reference flags == the seeded dirty pages."""
    import numpy as np

    from repro.kernels import ops
    be = PAGE // cur.dtype.itemsize
    got = np.asarray(ops.dirty_blocks(cur, snap, block_elems=be, impl=impl))
    want = np.asarray(ops.dirty_blocks(cur, snap, block_elems=be,
                                       impl="ref"))
    if not (got == want).all():
        raise AssertionError(f"{cur.dtype}: kernel flags != reference flags")
    if got.nonzero()[0].tolist() != dirty.tolist():
        raise AssertionError(f"{cur.dtype}: flags != the pages dirtied")


def _check_file(path: str, want: bytes, what: str) -> None:
    import numpy as np
    got = np.fromfile(path, np.uint8)
    ok = got.size == len(want) and np.array_equal(
        got, np.frombuffer(want, np.uint8))
    if not ok:
        raise AssertionError(f"{what}: storage file != expected bytes")


def _check_stats(st: dict, impl: str, syncs: int, payload: int,
                 logical: int) -> None:
    want = {"syncs": syncs, f"{impl}_syncs": syncs, "payload_transfers":
            payload, "span_transfers": 0, "logical_bytes": logical}
    bad = {k: (st[k], v) for k, v in want.items() if st[k] != v}
    others = [k for k in ("pallas_syncs", "interpret_syncs", "ref_syncs")
              if k != f"{impl}_syncs" and st[k]]
    if bad or others:
        raise AssertionError(f"device sync ran {st}, want {want}")


def window_sync_phase(run_dir: str, *, nbytes: int = GiB,
                      shard_pages=(100_003, 65_537, 33_331),
                      impl: str | None = None, seed: int = 0,
                      dirty_frac: float = 0.08) -> dict:
    """``impl`` None: the platform's default, which must be the compiled
    Pallas kernel; the CPU rehearsal passes ``"interpret"``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Communicator, Window
    from repro.kernels.ops import resolve_impl
    want_impl = resolve_impl(impl)
    if impl is None and want_impl != "pallas":
        raise AssertionError("the platform default is not the Pallas kernel")
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    comm = Communicator(1)
    info = {"alloc_type": "storage"}

    # one 1 GiB shard through sync_from_device
    pages = nbytes // PAGE
    snap = jax.random.normal(key, (nbytes // 4,), jnp.float32)
    cur, dirty = _dirty(snap, pages, dirty_frac, rng)
    path = os.path.join(run_dir, "window_sync.bin")
    win = Window.allocate(comm, nbytes,
                          info=dict(info, storage_alloc_filename=path))
    try:
        win.put(np.asarray(snap), 0, 0)
        win.sync(0)
        progress("window_sync: baseline on storage")
        t1 = time.perf_counter()
        flushed = win.sync_from_device(0, cur, snap, impl=impl,
                                       blocking=True)
        sync_s = time.perf_counter() - t1
        _check_stats(win.device_sync_stats(), want_impl, 1, 1,
                     len(dirty) * PAGE)
    finally:
        win.free()
    t1 = time.perf_counter()
    _check_file(path, np.asarray(cur).tobytes(), "sync_from_device")
    _check_flags(cur, snap, dirty, impl)
    check_s = time.perf_counter() - t1
    progress("window_sync: 1 GiB shard checked", sync_s=sync_s,
             check_s=check_s)
    del cur, snap

    # three shards of unequal size and dtype through one merged sync; the
    # int8 one fully dirty, as a trained param is after every step
    dtypes = (jnp.float32, jnp.bfloat16, jnp.int8)
    fracs = (dirty_frac, dirty_frac, 1.0)
    shards, blobs, disp, nshard_dirty = [], [], 0, 0
    for i, (np_, dt, frac) in enumerate(zip(shard_pages, dtypes, fracs)):
        n = np_ * PAGE // jnp.dtype(dt).itemsize
        k = jax.random.fold_in(key, i + 1)
        s = (jax.random.normal(k, (n,), jnp.float32).astype(dt)
             if dt != jnp.int8 else
             jax.random.randint(k, (n,), -128, 128, jnp.int32).astype(dt))
        if dt == jnp.float32:  # an unchanged NaN page stays clean
            s = s.at[:PAGE // 4].set(jnp.nan)
        c, d = _dirty(s, np_, frac, rng)
        if dt == jnp.float32:
            d = d[d != 0]
            c = c.at[:PAGE // 4].set(jnp.nan)
        shards.append((c, s, disp, d))
        blobs.append((np.asarray(s).tobytes(), np.asarray(c).tobytes()))
        disp += np_ * PAGE
        nshard_dirty += len(d)
    path = os.path.join(run_dir, "window_shards.bin")
    win = Window.allocate(comm, disp,
                          info=dict(info, storage_alloc_filename=path))
    try:
        win.put(np.frombuffer(b"".join(b[0] for b in blobs), np.uint8), 0, 0)
        win.sync(0)
        progress("window_sync: shard baseline on storage")
        t1 = time.perf_counter()
        win.sync_shards_from_device(0, [sh[:3] for sh in shards],
                                    impl=impl, blocking=True)
        shards_s = time.perf_counter() - t1
        st = win.device_sync_stats()
        # packed rows come in each shard's width: one payload per width
        _check_stats(st, want_impl, 1, len(dtypes), nshard_dirty * PAGE)
        if st["narrow_tile_shards"] != 2:
            raise AssertionError(f"bf16 and int8 not in native tiles: {st}")
    finally:
        win.free()
    _check_file(path, b"".join(b[1] for b in blobs), "sync_shards")
    for c, s, _, d in shards:
        _check_flags(c, s, d, impl)
    out = {"impl": want_impl, "shard_bytes": nbytes,
           "dirty_pages": int(len(dirty)), "pages": int(pages),
           "flushed_bytes": int(flushed), "sync_s": sync_s,
           "check_s": check_s,
           "shards": [[int(p), jnp.dtype(dt).name, f]
                      for p, dt, f in zip(shard_pages, dtypes, fracs)],
           "shard_dirty_pages": nshard_dirty, "shards_sync_s": shards_s,
           "payload_bytes": st["payload_bytes"], "bit_exact": True}
    emit("window_sync", t0, **out)
    return out


# -- phase 3 -------------------------------------------------------------------

def _opts(run_dir: str, **kw) -> dict:
    opts = {"arch": ARCH, "smoke": False, "steps": 4, "batch": 4,
            "seq": 2048, "microbatches": 1, "lr": 3e-4,
            "ckpt_dir": os.path.join(run_dir, "ckpt"), "ckpt_every": 2,
            "mode": "offload", "compression": False, "probe_interval": 1.0}
    opts.update(kw)
    return opts


def _step_progress(step: int, rec: dict) -> None:
    progress("step", step=step, loss=rec["loss"], step_s=rec["time"])


def _batches(ds, step: int = 0):
    while True:
        yield ds.batch_at(step)
        step += 1


def _offload_budget(n_params: int) -> int | None:
    """Host bytes the optimizer window may pin, or None when all of its
    state (12 bytes a param) fits.  What the process holds is counted as
    its resident set: on a TPU host that includes the chip's mapped
    device memory and the runtime's transfer buffers, which a machine's
    memory limit may count too."""
    other = CHECK_BYTES_PER_PARAM * n_params + HOST_RESERVE
    avail = host_limit() - host_memory()["VmRSS"]
    return None if 12 * n_params + other <= avail else max(0, avail - other)


def _grads_compile_s(tr, opts) -> float:
    """Compile the offload grads step ahead of the run (it lands in the
    persistent cache, where the first step finds it)."""
    import jax
    import jax.numpy as jnp
    shapes = {k: jax.ShapeDtypeStruct(v.shape, jnp.bfloat16)
              for k, v in tr.specs.items()}
    tok = jax.ShapeDtypeStruct((opts["microbatches"], opts["batch"],
                                opts["seq"]), jnp.int32)
    t = time.perf_counter()
    tr._grads_step.lower(shapes, {"inputs": tok, "targets": tok}).compile()
    return time.perf_counter() - t


def train_phase(run_dir: str, *, budget="auto", **overrides) -> dict:
    """Offload training, async checkpoints, stop, restore bit-exact.
    ``budget``: ``"auto"`` sizes ``offload_memory_budget`` from host RAM;
    an int or None sets it."""
    import jax
    import numpy as np

    from repro.core import Communicator
    from repro.launch.train import _build_trainer
    from repro.train.offload_opt import to_host
    t0 = time.perf_counter()
    opts = _opts(run_dir, **overrides)
    comm = Communicator(1)
    tr, ds = _build_trainer(opts, comm)
    n_params = sum(int(np.prod(s.shape)) for s in tr.specs.values())
    layers = tr.model_cfg.n_layers
    if budget == "auto":
        budget = _offload_budget(n_params)
    tr.tcfg.offload_memory_budget = budget
    tr.tcfg.log_every = 0
    progress("train: trainer built", offload_memory_budget=budget)
    compile_s = _grads_compile_s(tr, opts)
    progress("train: grads step compiled", compile_s=compile_s)
    params, _ = tr.run(_batches(ds), on_step=_step_progress)
    log = tr.metrics_log
    losses = [m["loss"] for m in log]
    if len(log) != opts["steps"] or not np.isfinite(losses).all():
        raise AssertionError(f"losses {losses}")
    # the params the run ended with, kept on disk for the restore check
    saved = os.path.join(run_dir, "saved")
    os.makedirs(saved)
    dtypes = {}
    for i, (k, v) in enumerate(sorted(params.items())):
        host = to_host(v)
        dtypes[k] = host.dtype
        host.view(np.uint8).tofile(os.path.join(saved, str(i)))
    peak = peak_bytes(jax.devices()[:1])
    saves = tr._ckpt.saves
    tr.close()
    del tr, params
    gc.collect()
    progress(f"train: {len(log)} steps done, restoring")

    t1 = time.perf_counter()
    tr, _ = _build_trainer(opts, comm)
    tr.tcfg.offload_memory_budget = budget
    restored, _ = tr.run(iter(()), stop_after=0)
    restore_s = time.perf_counter() - t1
    if tr.restored_step != opts["steps"]:
        raise AssertionError(f"restored step {tr.restored_step}")
    for i, (k, dtype) in enumerate(sorted(dtypes.items())):
        got = to_host(restored[k])
        want = np.fromfile(os.path.join(saved, str(i)), np.uint8)
        if got.dtype != dtype or not np.array_equal(
                got.reshape(-1).view(np.uint8), want):
            raise AssertionError(f"restored {k} != saved")
    tr.close()
    del tr, restored
    gc.collect()
    out = {"arch": ARCH, "mode": opts["mode"],
           "layers": layers, "params": n_params,
           "batch": opts["batch"], "seq": opts["seq"],
           "offload_memory_budget": budget, "compile_s": compile_s,
           "step_s": [m["time"] for m in log], "losses": losses,
           "checkpoints": saves, "restore_s": restore_s,
           "restored_step": opts["steps"], "restore_bit_exact": True,
           "peak_bytes_in_use": peak}
    emit("train", t0, **out)
    return out


# -- phase 4 (--chips 4) -------------------------------------------------------

def _state_bytes(params, opt_state) -> int:
    leaves = [*params.values(), *opt_state["m"].values(),
              *opt_state["v"].values()]
    return sum(x.nbytes for x in leaves)


def _check_placement(tr, params, opt_state) -> dict:
    """Every param and both moments on their spec's sharding; returns the
    state bytes each device holds."""
    held: dict = {}
    for k, v in params.items():
        for arr in (v, opt_state["m"][k], opt_state["v"][k]):
            if not arr.sharding.is_equivalent_to(tr.shardings[k], v.ndim):
                raise AssertionError(f"{k} on {arr.sharding}, want "
                                     f"{tr.shardings[k]}")
            for s in arr.addressable_shards:
                held[s.device] = held.get(s.device, 0) + s.data.nbytes
    return held


def mesh_train_phase(run_dir: str, **overrides) -> dict:
    import jax

    from repro.core import Communicator
    from repro.launch.mesh import make_production_mesh
    from repro.launch.train import _build_trainer
    from repro.runtime.sharding import train_rules
    t0 = time.perf_counter()
    mesh, rules = make_production_mesh(), train_rules()
    comm = Communicator(1)

    def run(ckpt_every, start=0, stop_after=None):
        opts = _opts(run_dir, mode="fused", ckpt_every=ckpt_every,
                     **overrides)
        tr, ds = _build_trainer(opts, comm, mesh=mesh, rules=rules)
        tr.tcfg.log_every = 0
        progress("mesh_train: trainer built", ckpt_every=ckpt_every,
                 start=start)
        params, opt_state = tr.run(_batches(ds, start),
                                   stop_after=stop_after,
                                   on_step=_step_progress)
        held = _check_placement(tr, params, opt_state)
        total = _state_bytes(params, opt_state)
        if max(held.values()) >= total:
            raise AssertionError(f"one device holds the whole state {held}")
        losses = [m["loss"] for m in tr.metrics_log]
        times = [m["time"] for m in tr.metrics_log]
        restored = tr.restored_step
        tr.close()
        del tr, params, opt_state
        gc.collect()
        return losses, times, restored, total, held

    want, times, _, total, held = run(ckpt_every=0)
    run(ckpt_every=2, stop_after=2)       # checkpoint at step 2, stop
    # restore, then steps 2-3 under a cadence that saves nothing more:
    # the restore is what is under test, not a second 21 GiB save
    got, resumed_times, restored, _, _ = run(ckpt_every=1000, start=2)
    if restored != 2 or got != want[2:]:
        raise AssertionError(f"resumed at {restored}: losses {got} != "
                             f"{want[2:]}")
    out = {"arch": ARCH, "mode": "fused", "mesh": dict(mesh.shape),
           "state_bytes": total,
           "state_bytes_per_device": [held[d] for d in mesh.devices.flat],
           "losses": want, "step_s": times, "resumed_losses": got,
           "resumed_step_s": resumed_times, "resume_bit_exact": True,
           "peak_bytes_in_use": peak_bytes(list(mesh.devices.flat))}
    emit("mesh_train", t0, **out)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--run-dir", default=os.path.join(HERE, "chip_smoke_run"))
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    shutil.rmtree(args.run_dir, ignore_errors=True)
    os.makedirs(args.run_dir)
    try:
        device = device_phase(args.run_dir)
        if device["count"] < args.chips:
            raise SystemExit(f"--chips {args.chips}: JAX sees "
                             f"{device['count']} chip(s)")
        if args.chips == 4:
            mesh_train_phase(args.run_dir)
        else:
            window_sync_phase(args.run_dir, seed=args.seed)
            release_host_memory()
            train_phase(args.run_dir)
    finally:
        shutil.rmtree(args.run_dir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
