"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/tools/calibrate.py --workload <name> --seeds 1,2,.. \\
        [--control-seeds 7,8,9] [--fault-seeds 4,5,6]

One process, one line of JSON per reading on stdout:

* ``program``: the numbers compared for a sound run of the program;
* ``control``: the same numbers with the control in the program's place
  (training: the plain reference with float8 matmul operands, the
  precision below the configuration's bfloat16; HACC: the checkpoint
  written by a plain host writer that keeps each float field in bfloat16
  precision; resume: a restore that hands back float32 state in bfloat16
  precision -- both break the bit-exact guarantee);
* ``fault``: a fault planted in the program (training: half of each
  batch's targets left out).

The benchmark's own runs never run this.  Training readings run only the
compared steps (no window, no save); HACC readings are whole runs of the
cell with a short window.  ``bench/tests`` holds the same controls at a
tiny size on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402


def emit(kind: str, seed: int, values: dict, **extra) -> None:
    print(json.dumps({"kind": kind, "seed": seed, **values, **extra}),
          flush=True)


def train(spec: dict, args, run_dir: str) -> None:
    drv = harness.load_module(spec["driver"], "bench_driver")
    from bench.reference.internlm2 import fp8_cast
    conf, traffic = spec["config"], spec["traffic"]
    for kind, seeds in (("program", args.seeds), ("fault", args.fault_seeds)):
        for seed in seeds:
            t = time.monotonic()
            c = drv.Cell(conf, traffic, seed, run_dir, readings_only=True,
                         fault="half_batch" if kind == "fault" else None)
            try:
                c.setup()
                got = c.program_readings()
            finally:
                c.close()
            want = drv.reference_readings(conf, traffic, seed)
            emit(kind, seed, drv.gaps(got, want),
                 seconds=time.monotonic() - t, losses=got["losses"],
                 ref_losses=want["losses"])
    for seed in args.control_seeds:
        t = time.monotonic()
        got = drv.reference_readings(conf, traffic, seed, cast=fp8_cast)
        want = drv.reference_readings(conf, traffic, seed)
        emit("control", seed, drv.gaps(got, want),
             seconds=time.monotonic() - t)


def hacc_control(cell) -> None:
    """HACC's control: each field written from the host by a plain writer
    that keeps the float fields in bfloat16 precision."""
    import jax.numpy as jnp
    import numpy as np

    def sync(cur, snap):
        for k in cell.layout["names"]:
            x = cur[k]
            if x.dtype == jnp.float32:
                x = x.astype(jnp.bfloat16).astype(jnp.float32)
            host = np.asarray(x).reshape(-1).view(np.uint8)
            cell.win.put(host, 0, cell.layout["disp"][k])
        cell.win.sync(0)

    cell._sync = sync


def resume_control(cell) -> None:
    """Resume's control: the restore hands back every float32 array in
    bfloat16 precision."""
    import numpy as np

    from repro.ckpt import manager
    real = manager.CheckpointManager._try_restore

    def restore(self, path):
        res = real(self, path)
        if res is not None:
            for k, v in res.tree.items():
                if v.dtype == np.float32:
                    bits = v.view(np.uint32) & np.uint32(0xFFFF0000)
                    res.tree[k] = bits.view(np.float32)
        return res

    manager.CheckpointManager._try_restore = restore


def whole_runs(spec: dict, args, run_dir: str, control) -> None:
    """Runs of the cell with a short window: sound, and with the control
    in the program's place."""
    drv = harness.load_module(spec["driver"], "bench_driver")
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds)):
        for seed in seeds:
            shutil.rmtree(run_dir, ignore_errors=True)
            os.makedirs(run_dir)
            c = drv.Cell(spec["config"], spec["traffic"], seed, run_dir)
            try:
                c.setup()
                if kind == "control":
                    control(c)
                c.window(args.seconds, lambda _n: contextlib.nullcontext())
                c.release()
                checks = c.check()
            finally:
                c.close()
            emit(kind, seed, {ch["name"]: ch["value"] for ch in checks},
                 correct=all(ch["ok"] for ch in checks),
                 attempted=c.counters()["attempted"])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    for k in ("seeds", "control_seeds", "fault_seeds"):
        setattr(args, k, [int(x) for x in getattr(args, k).split(",") if x])
    spec = harness.resolve(harness.load_benchmark(), args.workload)
    try:
        harness.device_info(spec["cell"]["chips"])
    except harness.NoChip as e:
        print(str(e), file=sys.stderr)
        return 3
    harness.enable_cache()
    run_dir = os.path.join(harness.RUN_ROOT, "calibrate")
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    try:
        driver = spec["traffic"]["driver"]
        if driver == "train_ckpt":
            train(spec, args, run_dir)
        else:
            whole_runs(spec, args, run_dir, {"hacc_ckpt": hacc_control,
                                             "train_resume": resume_control
                                             }[driver])
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
