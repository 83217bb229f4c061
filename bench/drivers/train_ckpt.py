"""Fused training with asynchronous A/B checkpoints (``Trainer.run``).

One ``Trainer`` runs the whole job in one ``Trainer.run`` call, in a
thread of its own: set-up is the steps up to the ``setup_saves``-th save
(the first compiles the step; the first save to each of A and B takes the
no-snapshot path), the window is the whole save cycles after it (each
``ckpt_every`` steps, the wait on the previous flush, and the next save's
staging) until ``--seconds`` have passed.  The data feed is the only hook:
it hands out seeded batches, marks the cycle boundaries and stops the run
by raising at the window's last boundary.

What ``correct`` compares (``bench/reference/internlm2.py`` is the plain
reference; the weights are made from the seed by the benchmark):

* the losses of the first ``compare_steps`` steps, the norm of each
  weight's first gradient as AdamW gets it (read from its first moment
  after one step), and the norm of each weight's change over those steps,
  worst weight first, against the reference's;
* the last committed checkpoint, restored from its files past the OS page
  cache, against the device state it was taken from, byte for byte.
"""

from __future__ import annotations

import gc
import os
import threading
import time

import numpy as np

from bench.reference import internlm2 as ref


class StopWindow(Exception):
    pass


def model_config(config: dict):
    """The program's ``ModelConfig`` of a dense GQA decoder with a gated
    SiLU MLP and an untied head, from the file's numbers."""
    from repro.models.config import ModelConfig
    if (config["hidden_act"], config["tie_word_embeddings"],
            config["bias"]) != ("silu", False, False):
        raise ValueError("the driver runs SiLU-gated, untied, bias-free "
                         "decoders")
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab=config["vocab_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"])


def make_batch(seed: int, step: int, vocab: int, mb: int, batch: int,
               seq: int) -> dict:
    """Step ``step``'s token ids from the seed: next-token targets, the
    last position without one (-1).  Every row of every step differs."""
    rng = np.random.default_rng([seed, 4, step])
    toks = rng.integers(0, vocab, size=(mb, batch, seq)).astype(np.int32)
    tgt = np.roll(toks, -1, axis=-1)
    tgt[..., -1] = -1
    return {"inputs": toks, "targets": tgt}


def leaf_norms(tree: dict, scale: float = 1.0):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v))) * scale
                              for k, v in t.items()})(tree)


def diff_norms(a: dict, b: dict):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda x, y: {k: jnp.sqrt(jnp.sum(jnp.square(x[k] - y[k])))
                                 for k in x})(a, b)


class Feed:
    """The batches of the run, and the window's boundaries."""

    def __init__(self, cell: "Cell"):
        self.cell = cell
        self.step = 0
        self.t0 = self.t1 = None
        self.first = self.last = None
        self.marks: list[float] = []  # the window's cycle boundaries
        self._span = None

    def __iter__(self):
        return self

    def _close_span(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __next__(self):
        c, s = self.cell, self.step
        self._close_span()
        k = c.every
        if c.readings_only and s == c.compare:
            raise StopWindow
        if s == c.setup_steps:
            c.setup_done.set()
            c.go.wait()
            self.t0, self.first = time.monotonic(), s
            self.marks.append(self.t0)
        elif self.t0 is not None and s % k == 0:
            self.marks.append(time.monotonic())
            if self.marks[-1] - self.t0 >= c.seconds:
                self.t1, self.last = self.marks[-1], s
                raise StopWindow
        if self.t0 is not None and c.span is not None:
            self._span = c.span("step+save" if (s + 1) % k == 0 else "step")
            self._span.__enter__()
        self.step += 1
        return c.batch(s)


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, run_dir: str,
                 *, fault: str | None = None, readings_only: bool = False):
        """``readings_only``: run just the compared steps, saving nothing
        (the calibration of the limits); ``fault`` plants one for the
        tests and the calibration."""
        self.config, self.traffic, self.seed = config, traffic, seed
        self.readings_only = readings_only
        self.run_dir = run_dir
        self.fault = fault
        self.every = int(traffic["ckpt_every"])
        self.setup_steps = int(traffic["setup_saves"]) * self.every
        self.compare = int(traffic["compare_steps"])
        self.setup_done = threading.Event()
        self.go = threading.Event()
        self.seconds = 0.0
        self.span = None
        self.error: Exception | None = None
        self.obs: dict = {}
        self.saved: dict | None = None
        self.checks_ckpt: list[dict] = []

    # -- the job ------------------------------------------------------------------
    def batch(self, step: int) -> dict:
        t = self.traffic
        b = make_batch(self.seed, step, self.config["vocab_size"],
                       t["microbatches"], t["batch"], t["seq"])
        if self.fault == "half_batch" and step < self.compare:
            b = dict(b, targets=b["targets"].copy())
            b["targets"][:, b["targets"].shape[1] // 2:] = -1
        return b

    def setup(self) -> None:
        import jax

        from repro.core import Communicator
        from repro.train import AdamWConfig, TrainConfig, Trainer
        t = self.traffic
        o = t["optimizer"]
        self.cfg = model_config(self.config)
        self.opt = AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"],
                               eps=o["eps"], weight_decay=o["weight_decay"],
                               clip_norm=o["clip_global_norm"],
                               warmup_steps=o["warmup_steps"],
                               total_steps=o["total_steps"])
        tcfg = TrainConfig(steps=1 << 40, microbatches=t["microbatches"],
                           mode="fused",
                           ckpt_dir=(None if self.readings_only else
                                     os.path.join(self.run_dir, "ckpt")),
                           ckpt_every=0 if self.readings_only else self.every,
                           ckpt_async=True,
                           log_every=0, seed=self.seed & 0x7FFFFFFF)
        self.comm = Communicator(1)
        self.tr = Trainer(self.cfg, self.opt, tcfg, comm=self.comm)
        self._observe()
        weights = ref.make_weights(self.config, self.seed)
        jax.block_until_ready(weights)
        self.feed = Feed(self)
        self.thread = threading.Thread(target=self._job, args=(weights,),
                                       name="train", daemon=True)
        del weights
        self.thread.start()
        while not self.setup_done.wait(0.5):
            if not self.thread.is_alive():
                break
        self._raise()
        if not self.thread.is_alive() and not self.readings_only:
            raise RuntimeError("the job ended before its window")

    def program_readings(self) -> dict:
        """The compared steps' losses and norms, as floats."""
        out = {k: {n: float(x) for n, x in v.items()}
               for k, v in self.obs.items()}
        out["losses"] = [m["loss"] for m in self.tr.metrics_log[:self.compare]]
        return out

    def _observe(self) -> None:
        """Read the first steps' state through the step the job calls, and
        keep the device state each save is taken from."""
        tr, real, b1 = self.tr, self.tr._fused_step, self.opt.b1
        seen = [0]

        def step(params, opt_state, ef, batch):
            i = seen[0]
            if self.fault == "unchanged":
                out = _unchanged(real, params, opt_state, ef, batch)
            else:
                out = real(params, opt_state, ef, batch)
            if i == 0:
                self.obs["grad0"] = leaf_norms(out[1]["m"], 1 / (1 - b1))
            if i == self.compare - 1:
                w0 = ref.make_weights(self.config, self.seed)
                self.obs["change"] = diff_norms(out[0], w0)
                del w0
            seen[0] += 1
            if seen[0] >= self.compare and self.fault is None:
                tr._fused_step = real
            return out

        tr._fused_step = step
        tree = tr._ckpt_tree

        def ckpt_tree(params, opt_state):
            self.saved = {"step": None, "params": params,
                          "opt_state": opt_state}
            return tree(params, opt_state)

        tr._ckpt_tree = ckpt_tree

    def _job(self, weights) -> None:
        try:
            self.tr.run(self.feed, weights, restore=False)
        except StopWindow:
            pass
        except Exception as e:  # raised again by setup() or window()
            self.error = e
        finally:
            self.feed._close_span()
            self.setup_done.set()

    def _raise(self) -> None:
        if self.error is not None:
            raise self.error

    # -- the measured window -----------------------------------------------------
    def window(self, seconds: float, span) -> None:
        self.seconds, self.span = seconds, span
        self.go.set()
        self.thread.join()
        self._raise()
        if self.feed.t1 is None:
            raise RuntimeError("the window did not close")

    def _window_log(self) -> list[dict]:
        f = self.feed
        return [m for m in self.tr.metrics_log if f.first <= m["step"] < f.last]

    def end_to_end(self) -> dict:
        f, t = self.feed, self.traffic
        tokens = (f.last - f.first) * t["batch"] * t["seq"] * t["microbatches"]
        return {"train_tokens_per_s": tokens / (f.t1 - f.t0)}

    def counters(self) -> dict:
        from bench import costs
        f, t, c = self.feed, self.traffic, self.config
        log = self._window_log()
        steps = f.last - f.first
        return {"attempted": steps, "failed": steps - len(log),
                "steps": len(log), "saves": steps // self.every,
                "window_s": f.t1 - f.t0,
                "cycle_s": list(np.diff(f.marks)),
                "step_s": sum(m["time"] for m in log),
                "step_flops": costs.lm_train_flops(
                    d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
                    n_heads=c["num_attention_heads"],
                    n_kv_heads=c["num_key_value_heads"],
                    head_dim=c["head_dim"], d_ff=c["intermediate_size"],
                    vocab=c["vocab_size"], batch=t["batch"] * t["microbatches"],
                    seq=t["seq"])}

    # -- after the window ------------------------------------------------------------
    def release(self) -> None:
        """Make the last save durable, check it against the device state it
        was taken from, then free the program's state."""
        import jax.numpy as jnp

        from repro.ckpt import CheckpointManager
        tr = self.tr
        saved = self.saved
        specs = tr._ckpt_specs(saved["params"])
        want_step = self.feed.last
        if tr._ckpt is not None:
            tr._ckpt.wait()
        tr.close()
        if self.fault == "ckpt_byte":
            _flip_byte(os.path.join(self.run_dir, "ckpt"))
        d = os.path.join(self.run_dir, "ckpt")
        for name in os.listdir(d):
            if name.endswith(".bin"):
                fd = os.open(os.path.join(d, name), os.O_RDONLY)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
                os.close(fd)
        mgr = CheckpointManager.open_for_restore(d, self.comm, specs)
        try:
            res = mgr.restore()
            dev = dict(saved["params"])
            dev.update({f"opt_m/{k}": v for k, v in
                        saved["opt_state"]["m"].items()})
            dev.update({f"opt_v/{k}": v for k, v in
                        saved["opt_state"]["v"].items()})
            dev["opt_step"] = saved["opt_state"]["step"]
            bad = 0 if res is not None else sum(
                np.asarray(v).nbytes for v in dev.values())
            if res is not None:
                for k in sorted(dev):
                    # a fresh copy off the device, not the save's own
                    a = np.ascontiguousarray(np.asarray(jnp.copy(dev[k])))
                    a = a.view(np.uint8)
                    b = np.ascontiguousarray(res.tree[k]).view(np.uint8)
                    bad += (int(np.count_nonzero(a.ravel() != b.ravel()))
                            if a.size == b.size else max(a.size, b.size))
            step_gap = (abs(res.step - want_step) if res is not None
                        else want_step)
        finally:
            mgr.close()
        self.checks_ckpt = [
            {"name": "ckpt_vs_device_bytes", "value": bad, "limit": 0,
             "ok": bad == 0},
            {"name": "ckpt_step_gap", "value": step_gap, "limit": 0,
             "ok": step_gap == 0}]
        self.readings = self.program_readings()
        self.saved = None
        del saved, dev
        self.tr = None
        gc.collect()

    def check(self) -> list[dict]:
        want = reference_readings(self.config, self.traffic, self.seed)
        values = gaps(self.readings, want)
        return judge(values, self.traffic["limits"]) + self.checks_ckpt

    def close(self) -> None:
        thread = getattr(self, "thread", None)
        if thread is not None and thread.is_alive():
            # a window never run: the job stops at its first boundary
            self.go.set()
            thread.join()
        tr = getattr(self, "tr", None)
        if tr is not None:
            tr.close()
            self.tr = None
        comm = getattr(self, "comm", None)
        if comm is not None:
            comm.close()


def reference_readings(config: dict, traffic: dict, seed: int,
                       cast=None) -> dict:
    """The reference's losses and norms over the compared steps, from the
    same weights and batches; ``cast`` makes it the control."""
    import jax
    refr = ref.Reference(config, traffic["optimizer"], cast)
    w0 = ref.make_weights(config, seed)
    batches = []
    for s in range(int(traffic["compare_steps"])):
        b = make_batch(seed, s, config["vocab_size"], traffic["microbatches"],
                       traffic["batch"], traffic["seq"])
        batches.append((b["inputs"][0], b["targets"][0]))
    out = refr.run(w0, batches)
    got = {"losses": out["losses"],
           "grad0": {k: float(x) for k, x in leaf_norms(out["grad0"]).items()},
           "change": {k: float(x) for k, x in
                      diff_norms(out["weights"], w0).items()}}
    del out, w0, refr
    jax.clear_caches()
    return got


def gaps(got: dict, want: dict) -> dict:
    """The three numbers compared, from the program's readings ``got`` and
    the reference's ``want``.  Each weight's gap is measured against the
    larger of its reference norm and the median weight's.  Weights whose
    reference gradient is under a thousandth of the median weight's are
    left out of the change: they move by round-off alone."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(got["losses"], want["losses"]))
    g_ref, c_ref = want["grad0"], want["change"]
    gmed = float(np.median(list(g_ref.values())))
    grad = max(abs(got["grad0"][k] - g_ref[k]) / max(g_ref[k], gmed)
               for k in g_ref)
    moved = [k for k in c_ref if g_ref[k] >= 1e-3 * gmed]
    cmed = float(np.median([c_ref[k] for k in moved]))
    change = max(abs(got["change"][k] - c_ref[k]) / max(c_ref[k], cmed)
                 for k in moved)
    return {"loss_gap": loss, "grad_norm_gap": grad,
            "change_norm_gap": change}


def judge(values: dict, limits: dict) -> list[dict]:
    """Each number beside its limit; a number passes when it is at most
    its limit."""
    return [{"name": k, "value": v, "limit": limits[k], "ok": v <= limits[k]}
            for k, v in values.items()]


def _unchanged(real, params, opt_state, ef, batch):
    """A fault for the tests: the step runs, and returns its state as it
    got it."""
    import jax
    import jax.numpy as jnp
    copy = jax.tree.map(jnp.copy, (params, opt_state))
    out = real(*copy, ef, batch)
    return (params, opt_state) + tuple(out[2:])


def _flip_byte(d: str) -> None:
    """A fault for the tests: one byte of each checkpoint file altered on
    storage after the flush."""
    for name in ("ckpt_a.bin", "ckpt_b.bin"):
        path = os.path.join(d, name)
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            b = f.read(1)
            f.seek(-1, os.SEEK_CUR)
            f.write(bytes([b[0] ^ 0xFF]))
