"""Time to resume training from a checkpoint on storage.

Set-up trains the job's first steps in one ``Trainer`` and commits one
checkpoint (``Trainer.run``, fused, one async save), keeps the loss of the
step after it, and makes one resume to warm up.  Each resume of the window
is what a job restarted on another node does: drop the checkpoint files'
pages from the OS cache (``posix_fadvise DONTNEED``), build a fresh
``Trainer``, restore (``CheckpointManager.restore``: read, CRC32, place
on the chip) and run the first step to ``block_until_ready``.  The window
runs whole resumes and ends at the boundary nearest to ``--seconds``.

What ``correct`` compares: each resume's restored state against the state
the checkpoint was taken from (a checksum of every weight's and moment's
bits, taken on the chip before the first step, with the resume's clock
stopped), each resume's first loss
against the uninterrupted job's loss at that step (bit for bit), and that
loss against the plain reference's (``bench/reference/internlm2.py``).
"""

from __future__ import annotations

import functools
import gc
import os
import time

import numpy as np

from bench.drivers.train_ckpt import (judge, make_batch, model_config,
                                      reference_readings)


class StopJob(Exception):
    pass


@functools.cache
def _checksums_fn():
    import jax
    import jax.numpy as jnp

    def one(x):
        w = jax.lax.bitcast_convert_type(x.reshape(-1), jnp.uint32)
        i = jnp.arange(w.shape[0], dtype=jnp.uint32)
        return jnp.stack([jnp.sum(w), jnp.sum(w * (i | 1))])

    return jax.jit(lambda t: {k: one(v) for k, v in t.items()})


def checksums(tree: dict):
    """Per array, two 32-bit sums of its bits on the device: the plain
    sum of its words and the sum weighted by position."""
    return _checksums_fn()(tree)


def _flat_state(params: dict, opt_state: dict) -> dict:
    out = dict(params)
    out.update({f"opt_m/{k}": v for k, v in opt_state["m"].items()})
    out.update({f"opt_v/{k}": v for k, v in opt_state["v"].items()})
    out["opt_step"] = opt_state["step"].reshape(1)
    return out


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, run_dir: str):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.run_dir = run_dir
        self.every = int(traffic["ckpt_every"])
        self.ckpt_dir = os.path.join(run_dir, "ckpt")
        self.resumes: list[dict] = []
        self.attempted = 0
        self.window_s = 0.0
        self.paused_s = 0.0  # the window's time in the benchmark's checks
        self.comm = None

    # -- the job ----------------------------------------------------------------
    def _trainer(self):
        from repro.train import AdamWConfig, TrainConfig, Trainer
        o = self.traffic["optimizer"]
        opt = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                          weight_decay=o["weight_decay"],
                          clip_norm=o["clip_global_norm"],
                          warmup_steps=o["warmup_steps"],
                          total_steps=o["total_steps"])
        tcfg = TrainConfig(steps=1 << 40,
                           microbatches=self.traffic["microbatches"],
                           mode="fused", ckpt_dir=self.ckpt_dir,
                           ckpt_every=self.every, ckpt_async=True,
                           log_every=0, seed=self.seed & 0x7FFFFFFF)
        return Trainer(self.cfg, opt, tcfg, comm=self.comm)

    def _batches(self, first: int, stop: int):
        t = self.traffic
        for s in range(first, stop):
            yield make_batch(self.seed, s, self.config["vocab_size"],
                             t["microbatches"], t["batch"], t["seq"])
        raise StopJob

    def _run(self, tr, first: int, stop: int, params=None) -> None:
        try:
            tr.run(self._batches(first, stop), params, restore=params is None)
        except StopJob:
            pass

    def setup(self) -> None:
        import jax

        from bench.reference.internlm2 import make_weights
        from repro.core import Communicator
        self.cfg = model_config(self.config)
        self.comm = Communicator(1)
        tr = self._trainer()
        tree = tr._ckpt_tree

        def keep(params, opt_state):
            self.saved_sums = checksums(_flat_state(params, opt_state))
            return tree(params, opt_state)

        tr._ckpt_tree = keep
        weights = make_weights(self.config, self.seed)
        jax.block_until_ready(weights)
        # steps 0 .. every-1, the save, and the step after it
        self._run(tr, 0, self.every + 1, weights)
        del weights
        self.saved_sums = {k: np.asarray(v) for k, v in
                           self.saved_sums.items()}
        self.losses = [m["loss"] for m in tr.metrics_log]
        tr.close()
        del tr
        gc.collect()
        self.resume()  # warm-up: the first resume of a process
        self.resumes.clear()
        self.paused_s = 0.0

    def resume(self) -> float:
        """One resume; returns its seconds: from the pages' drop to the
        first step's new state on the device, less the time the
        benchmark's checksums of the restored state take (the step
        donates its inputs, so they are taken before it, with the clock
        stopped once the restored state is on the device)."""
        import jax
        t0 = time.monotonic()
        for name in os.listdir(self.ckpt_dir):
            if name.endswith(".bin"):
                fd = os.open(os.path.join(self.ckpt_dir, name), os.O_RDONLY)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                os.close(fd)
        tr = self._trainer()
        real = tr._fused_step
        got = {"paused": 0.0}

        def first_step(params, opt_state, ef, batch):
            jax.block_until_ready((params, opt_state))
            t = time.monotonic()
            sums = checksums(_flat_state(params, opt_state))
            got["sums"] = {k: np.asarray(v) for k, v in sums.items()}
            got["paused"] = time.monotonic() - t
            tr._fused_step = real
            return real(params, opt_state, ef, batch)

        tr._fused_step = first_step
        # the loop ends the step on its new params, on the device
        self._run(tr, self.every, self.every + 1)
        dt = time.monotonic() - t0 - got["paused"]
        self.paused_s += got["paused"]
        self.resumes.append({"step": tr.restored_step,
                             "loss": tr.metrics_log[0]["loss"]
                             if tr.metrics_log else None,
                             "sums": got.get("sums"), "seconds": dt})
        tr.close()
        return dt

    # -- the measured window -------------------------------------------------------
    def window(self, seconds: float, span) -> None:
        """Whole resumes, ending at the boundary nearest to ``seconds``:
        a window whose length is close to a whole number of resumes then
        holds the same number in every run."""
        t0 = time.monotonic()
        last = 0.0
        while not self.attempted or \
                time.monotonic() - t0 + last / 2 < seconds:
            self.attempted += 1
            t = time.monotonic()
            with span("resume"):
                self.resume()
            last = time.monotonic() - t
        self.window_s = time.monotonic() - t0

    def end_to_end(self) -> dict:
        return {"resume_s": (self.window_s - self.paused_s)
                / len(self.resumes)}

    def counters(self) -> dict:
        return {"attempted": self.attempted,
                "failed": self.attempted - len(self.resumes),
                "resumes": len(self.resumes), "window_s": self.window_s,
                "checksum_s": self.paused_s,
                "resume_s_each": [r["seconds"] for r in self.resumes]}

    # -- after the window ---------------------------------------------------------
    def release(self) -> None:
        gc.collect()

    def check(self) -> list[dict]:
        want_loss = self.losses[self.every]
        state_bad = loss_bad = step_bad = 0
        for r in self.resumes:
            sums = r["sums"]
            if sums is None or set(sums) != set(self.saved_sums):
                state_bad += 1
            else:
                state_bad += int(any(not np.array_equal(sums[k],
                                                        self.saved_sums[k])
                                     for k in sums))
            loss_bad += int(r["loss"] != want_loss)
            step_bad += int(r["step"] != self.every)
        t = dict(self.traffic, compare_steps=self.every + 1)
        ref = reference_readings(self.config, t, self.seed)["losses"]
        gap = max(abs(r["loss"] - ref[self.every]) / abs(ref[self.every])
                  for r in self.resumes) if self.resumes else 1.0
        return [
            {"name": "resumes_state_differs", "value": state_bad,
             "limit": 0, "ok": state_bad == 0},
            {"name": "resumes_loss_differs", "value": loss_bad, "limit": 0,
             "ok": loss_bad == 0},
            {"name": "resumes_wrong_step", "value": step_bad, "limit": 0,
             "ok": step_bad == 0},
        ] + judge({"loss_gap": gap},
                  {"loss_gap": self.traffic["limits"]["loss_gap"]})

    def close(self) -> None:
        if self.comm is not None:
            self.comm.close()
            self.comm = None

