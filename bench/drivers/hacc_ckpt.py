"""HACC I/O checkpointing from device memory (the application side).

The particles of one rank live on the chip, one device array per HACC
field.  Each cycle the traffic changes some of them (seeded page-aligned
particle ranges, ``bench/gen/hacc.py``), then the rank checkpoints: ``Window.sync_shards_from_device`` diffs each field against
the state the storage file already holds, ships the changed pages and
makes them durable.  The driver uses only the public ``Window`` API.

The file layout is HACC I/O's: one shared file, the rank's segment at
``rank * record_bytes * particles``, each field contiguous inside it.

Every update is exact in any IEEE float32 arithmetic (one correctly
rounded add per value; the drift multiplies by a power of two), so
``bench/reference/hacc.py`` replays it in numpy bit for bit.
"""

from __future__ import annotations

import os
import time

import numpy as np

from bench.gen import hacc as gen
from bench.reference import hacc as ref


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, run_dir: str,
                 *, impl: str | None = None):
        self.config = config
        self.seed = seed
        self.run_dir = run_dir
        self.impl = impl
        self.layout = gen.layout(config)
        self.plan = gen.Plan(config, traffic, seed)
        self.path = os.path.join(run_dir, "hacc.bin")
        self.win = None
        self.comm = None
        self.state = None
        self.cycles_done = 0
        self.attempted = 0
        self.window_s = 0.0
        self.ckpt_s: list[float] = []
        self.stats0: dict = {}
        self.stats1: dict = {}

    # -- set-up ----------------------------------------------------------------
    def setup(self) -> None:
        import jax

        from repro.core import Communicator, Window
        self._update = jax.jit(gen.device_update)
        self._mask = jax.jit(gen.device_mask, static_argnums=0)
        self.state = gen.device_particles(self.config, self.seed)
        jax.block_until_ready(self.state)
        self.comm = Communicator(1)
        self.win = Window.allocate(
            self.comm, self.layout["rank_bytes"],
            info={"alloc_type": "storage",
                  "storage_alloc_filename": self.path})
        # the first checkpoint writes the whole state, one field at a time
        for k in self.layout["names"]:
            host = np.asarray(self.state[k]).reshape(-1).view(np.uint8)
            self.win.put(host, 0, self.layout["disp"][k])
            del host
        self.win.sync(0)
        # every cycle has the same shapes: these warm each program up
        for i in range(gen.WARM_CYCLES):
            self._cycle(self.plan.cycle(i))
        self.stats0 = dict(self.win.device_sync_stats())

    def _sync(self, cur: dict, snap: dict) -> None:
        self.win.sync_shards_from_device(
            0, [(cur[k], snap[k], self.layout["disp"][k])
                for k in self.layout["names"]],
            blocking=True, impl=self.impl)

    def _cycle(self, cyc: dict, span=None) -> None:
        import contextlib

        import jax
        span = span or (lambda _n: contextlib.nullcontext())
        with span("update"):
            mask = self._mask(self.plan.pages, cyc["starts"], cyc["lens"])
            new = self._update(self.state, mask, cyc["kick"], cyc["dphi"])
            jax.block_until_ready(new)
        with span("checkpoint"):
            self._sync(new, self.state)
        self.state = new

    # -- the measured window -------------------------------------------------
    def window(self, seconds: float, span) -> None:
        t0 = time.monotonic()
        end = t0 + seconds
        while time.monotonic() < end:
            cyc = self.plan.cycle(gen.WARM_CYCLES + self.cycles_done)
            self.attempted += 1
            t = time.monotonic()
            self._cycle(cyc, span)
            self.ckpt_s.append(time.monotonic() - t)
            self.cycles_done += 1
        self.window_s = time.monotonic() - t0
        self.stats1 = dict(self.win.device_sync_stats())

    def end_to_end(self) -> dict:
        state_bytes = self.layout["rank_bytes"]
        return {"ckpt_GBps": self.cycles_done * state_bytes
                / self.window_s / 1e9}

    def counters(self) -> dict:
        d = {k: self.stats1.get(k, 0) - self.stats0.get(k, 0)
             for k in ("syncs", "pallas_syncs", "payload_bytes",
                       "logical_bytes", "payload_transfers")}
        return {"attempted": self.attempted,
                "failed": self.attempted - self.cycles_done,
                "checkpoints": self.cycles_done, "window_s": self.window_s,
                "state_bytes": self.layout["rank_bytes"],
                "shard_bytes": [self.layout["bytes"][k]
                                for k in self.layout["names"]],
                "dirty_pages": d["payload_bytes"] // gen.PAGE,
                "ckpt_s": self.ckpt_s, **d}

    # -- after the window ------------------------------------------------------
    def release(self) -> None:
        """Fetch the final device state, then free the program's: the
        window closes (its last flush is durable) and the arrays go."""
        self.final = {k: np.asarray(v) for k, v in self.state.items()}
        self.state = None
        if self.win is not None:
            self.win.free()
            self.win = None
        if self.comm is not None:
            self.comm.close()
            self.comm = None

    def check(self) -> list[dict]:
        return ref.check(self.config, self.plan, self.cycles_done,
                         self.final, self.path, self.attempted)

    def close(self) -> None:
        if self.win is not None:
            self.win.free()
        if self.comm is not None:
            self.comm.close()
        self.state = None
