"""Machine facts of the host a benchmark runs on, as inputs to predictions.

    python bench/tools/machine_facts.py [--gib 2] [--dir DIR]

Measures, once, on the machine that holds the chip: storage write+fsync
and cold-read bandwidth of a file under ``--dir`` (default the checkout's
``.bench_run``), and device->host / host->device bandwidth of one
contiguous array.  Prints one JSON object.  Writes ``--gib`` GiB to disk.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _disk(path: str, nbytes: int, chunk: int = 64 << 20) -> dict:
    import numpy as np
    buf = np.random.default_rng(0).integers(0, 256, chunk, np.uint8)
    fd = os.open(path, os.O_RDWR | os.O_CREAT | os.O_TRUNC, 0o644)
    try:
        t = time.perf_counter()
        for off in range(0, nbytes, chunk):
            os.pwrite(fd, buf[:min(chunk, nbytes - off)], off)
        t_w = time.perf_counter() - t
        os.fsync(fd)
        t_ws = time.perf_counter() - t
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        t = time.perf_counter()
        for off in range(0, nbytes, chunk):
            os.pread(fd, min(chunk, nbytes - off), off)
        t_r = time.perf_counter() - t
    finally:
        os.close(fd)
        os.unlink(path)
    return {"disk_bytes": nbytes, "write_to_cache_s": t_w,
            "write_fsync_s": t_ws, "write_fsync_GBps": nbytes / t_ws / 1e9,
            "cold_read_s": t_r, "cold_read_GBps": nbytes / t_r / 1e9}


def _transfers(nbytes: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    dev = jax.devices()[0]
    x = jnp.ones((nbytes // 4,), jnp.float32) * 3
    x.block_until_ready()
    out = {}
    for i in range(3):
        y = x + i  # a fresh array: no cached host copy
        y.block_until_ready()
        t = time.perf_counter()
        np.asarray(y)
        out[f"d2h_s_{i}"] = time.perf_counter() - t
    host = np.ones(nbytes // 4, np.float32)
    for i in range(3):
        t = time.perf_counter()
        jax.device_put(host, dev).block_until_ready()
        out[f"h2d_s_{i}"] = time.perf_counter() - t
    out["d2h_GBps"] = nbytes / min(out[f"d2h_s_{i}"] for i in range(3)) / 1e9
    out["h2d_GBps"] = nbytes / min(out[f"h2d_s_{i}"] for i in range(3)) / 1e9
    out["transfer_bytes"] = nbytes
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--gib", type=float, default=2.0)
    ap.add_argument("--dir", default=os.path.join(ROOT, ".bench_run"))
    args = ap.parse_args()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU: JAX sees {devs[0].platform}", file=sys.stderr)
        return 1
    os.makedirs(args.dir, exist_ok=True)
    nbytes = int(args.gib * (1 << 30))
    facts = {"device_kind": devs[0].device_kind, "count": len(devs),
             "cpus": os.cpu_count(),
             "disk_free_bytes": shutil.disk_usage(args.dir).free}
    facts.update(_disk(os.path.join(args.dir, "facts.bin"), nbytes))
    facts.update(_transfers(1 << 30))
    print(json.dumps(facts), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
