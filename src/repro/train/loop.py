"""Training loop with windows-backed transparent checkpointing.

The loop wires every substrate together:

* pjit'd train step (grad accumulation over microbatches via lax.scan,
  optional int8+EF compression stage, AdamW fused on device) -- or, in
  *offload* mode, a grads-only device step plus the out-of-core AdamW
  walking storage windows (the paper's technique as the optimizer).
* transparent checkpointing: params (+ fused opt state) live in an A/B
  double-buffered CheckpointManager; saves are selective (dirty blocks
  only) and asynchronous (flush overlaps the next steps).
* fault hooks: heartbeats + straggler detector feed ``plan_recovery``;
  ``Trainer.run`` restores from the last valid manifest, so a kill at any
  point resumes exactly (see tests/test_train_loop.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Iterator, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.ckpt import CheckpointManager
from repro.core.comm import Communicator
from repro.core.resilience import FailureDetector
from repro.models import init_params, make_loss_fn, param_specs
from repro.models.config import ModelConfig
from repro.models.spec import param_specs_to_shapes
from repro.perf.trace import span
from repro.runtime.compress import compress_with_feedback, init_error_feedback
from repro.runtime.fault import HeartbeatMonitor, StragglerDetector
from repro.runtime.sharding import named_sharding, use_rules
from repro.train.offload_opt import OutOfCoreAdamW, to_host
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state

__all__ = ["TrainConfig", "Trainer"]

#: page cache of each offload-mode checkpoint window
OFFLOAD_CKPT_CACHE_BYTES = 64 << 20


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    microbatches: int = 1
    mode: str = "fused"            # fused | offload
    ckpt_dir: str | None = None
    ckpt_every: int = 0
    ckpt_async: bool = True
    compression: bool = False      # int8 + error feedback on grads
    log_every: int = 10
    seed: int = 0
    offload_memory_budget: int | None = None
    # FailureDetector probe rate-limit (seconds): SPMD smoke lanes and
    # tests tighten it to catch rank death quickly; 1s keeps probing off
    # the hot path in production
    probe_interval_s: float = 1.0


class _HostTree(Mapping):
    """Device arrays by name, each fetched to the host when it is read:
    staging a checkpoint holds one tensor on the host at a time."""

    def __init__(self, arrays: dict):
        self._arrays = arrays

    def __getitem__(self, k: str) -> np.ndarray:
        return to_host(self._arrays[k])

    def __iter__(self):
        return iter(self._arrays)

    def __len__(self) -> int:
        return len(self._arrays)


class Trainer:
    def __init__(self, model_cfg: ModelConfig, opt_cfg: AdamWConfig,
                 tcfg: TrainConfig, *, comm: Communicator | None = None,
                 mesh=None, rules=None):
        with span("train.build"):
            self._build(model_cfg, opt_cfg, tcfg, comm, mesh, rules)

    def _build(self, model_cfg, opt_cfg, tcfg, comm, mesh, rules) -> None:
        self.model_cfg = model_cfg
        self.opt_cfg = opt_cfg
        self.tcfg = tcfg
        self.comm = comm or Communicator(1)
        self.mesh = mesh
        self.rules = rules
        self.loss_fn = make_loss_fn(model_cfg)
        self.specs = param_specs(model_cfg)
        # with a mesh, every param (and its Adam moments) lives on the
        # sharding its spec's logical axes give under ``rules``
        self.shardings = None if mesh is None else {
            k: named_sharding(v.axes, v.shape, rules, mesh, context=k)
            for k, v in self.specs.items()}
        self.metrics_log: list[dict[str, float]] = []
        self.hb = HeartbeatMonitor(self.comm.size)
        # probe-driven liveness: under the mp transport the other ranks are
        # real worker processes, and only Transport.probe can observe their
        # death -- self-reported beats would keep every rank but our own
        # permanently silent on the monitor.  interval rate-limits the
        # actual probing so the per-step poll() stays off the hot path
        self.detector = FailureDetector(self.comm, self.hb,
                                        interval=tcfg.probe_interval_s)
        self.straggler = StragglerDetector(self.comm.size)
        self._build_steps()
        self._ckpt: CheckpointManager | None = None
        self._oo_opt: OutOfCoreAdamW | None = None
        # step of the manifest run() restored from (None = fresh start);
        # resume tests read this rather than inferring it from metrics
        self.restored_step: int | None = None

    # -- step builders --------------------------------------------------------
    def _grad_fn(self):
        vg = jax.value_and_grad(self.loss_fn, has_aux=True)

        def accum(params, batch):
            """batch leaves have a leading microbatch axis."""
            def micro(carry, mb):
                (l_sum, g_sum) = carry
                (loss, _), grads = vg(params, mb)
                return (l_sum + loss,
                        {k: g_sum[k] + grads[k] for k in g_sum}), None

            zero = {k: jnp.zeros(v.shape, jnp.float32)
                    for k, v in params.items()}
            (l_sum, g_sum), _ = jax.lax.scan(micro, (jnp.zeros(()), zero), batch)
            n = self.tcfg.microbatches
            return l_sum / n, {k: v / n for k, v in g_sum.items()}

        return accum

    def _build_steps(self):
        accum = self._grad_fn()
        compression = self.tcfg.compression

        def fused_step(params, opt_state, ef, batch):
            loss, grads = accum(params, batch)
            if compression:
                grads, ef = compress_with_feedback(grads, ef)
            params, opt_state, stats = adamw_update(params, grads, opt_state,
                                                    self.opt_cfg)
            return params, opt_state, ef, loss, stats

        def grads_step(params, batch):
            loss, grads = accum(params, batch)
            return loss, {k: g.astype(jnp.bfloat16) for k, g in grads.items()}

        if self.shardings is None:
            self._fused_step = jax.jit(fused_step, donate_argnums=(0, 1, 2))
            self._grads_step = jax.jit(grads_step)
            return
        # pin the outputs to the inputs' shardings: the next step then
        # takes them as they are (no reshard, no second compile)
        psh, rep = self.shardings, self._replicated()
        ef_sh = psh if compression else rep
        self._fused_step = jax.jit(
            fused_step, donate_argnums=(0, 1, 2),
            out_shardings=(psh, self._opt_shardings(), ef_sh, rep, rep))
        self._grads_step = jax.jit(grads_step, out_shardings=(rep, psh))

    # -- placement --------------------------------------------------------------
    def _replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def _opt_shardings(self) -> dict:
        return {"m": self.shardings, "v": self.shardings,
                "step": self._replicated()}

    def _init_state(self, rng):
        """Fresh params + (fused mode) Adam state, built where they live."""
        fused = self.tcfg.mode == "fused"
        if self.shardings is None:
            params = init_params(self.specs, rng)
            return params, init_opt_state(params) if fused else None
        params = jax.jit(lambda r: init_params(self.specs, r),
                         out_shardings=self.shardings)(rng)
        opt_state = (jax.jit(init_opt_state,
                             out_shardings=self._opt_shardings())(params)
                     if fused else None)
        return params, opt_state

    def _place(self, items, dtype=None) -> dict:
        """``(name, host array)`` pairs -> device arrays keyed by name, each
        on its param's sharding when the trainer has a mesh.  A generator
        of pairs is placed one array at a time: the host never holds them
        all at once."""
        if self.shardings is None:
            return {k: jnp.asarray(v, dtype) for k, v in items}
        return {k: jax.device_put(np.asarray(v, dtype), self.shardings[k])
                for k, v in items}

    def _place_restored(self, tree: Mapping[str, np.ndarray], opt_state):
        """A restored checkpoint's params (and fused Adam state) on the
        device; ``opt_state`` passes through in offload mode."""
        params = self._place((k, tree[k]) for k in self.specs)
        if self.tcfg.mode == "fused":
            opt_state = {
                "m": self._place((k, tree[f"opt_m/{k}"]) for k in self.specs),
                "v": self._place((k, tree[f"opt_v/{k}"]) for k in self.specs),
                "step": (jnp.asarray(tree["opt_step"])
                         if self.shardings is None else
                         jax.device_put(tree["opt_step"],
                                        self._replicated())),
            }
        return params, opt_state

    # -- checkpoint plumbing -----------------------------------------------------
    def _ckpt_specs(self, params) -> dict[str, tuple[tuple[int, ...], Any]]:
        out = {k: (tuple(v.shape), np.dtype(jnp.dtype(v.dtype).name))
               for k, v in params.items()}
        if self.tcfg.mode == "fused":
            for k, v in params.items():
                out[f"opt_m/{k}"] = (tuple(v.shape), np.float32)
                out[f"opt_v/{k}"] = (tuple(v.shape), np.float32)
            out["opt_step"] = ((), np.int32)
        return out

    def _ckpt_tree(self, params, opt_state) -> Mapping[str, np.ndarray]:
        tree = dict(params)
        if self.tcfg.mode == "fused":
            tree.update({f"opt_m/{k}": v for k, v in opt_state["m"].items()})
            tree.update({f"opt_v/{k}": v for k, v in opt_state["v"].items()})
            tree["opt_step"] = opt_state["step"]
        return _HostTree(tree)

    # -- main entry ---------------------------------------------------------------
    def run(self, data_iter: Iterator[dict[str, np.ndarray]],
            params: dict | None = None, *, restore: bool = True,
            stop_after: int | None = None,
            on_step: Callable[[int, dict], None] | None = None):
        with (use_rules(self.rules, self.mesh) if self.mesh is not None
              else contextlib.nullcontext()):
            return self._run(data_iter, params, restore=restore,
                             stop_after=stop_after, on_step=on_step)

    def _run(self, data_iter, params, *, restore, stop_after, on_step):
        tcfg = self.tcfg
        rng = jax.random.PRNGKey(tcfg.seed)
        if params is None:
            with span("train.init"):
                params, opt_state = self._init_state(rng)
        elif tcfg.mode == "fused":
            opt_state = init_opt_state(params)
        if tcfg.mode != "fused":
            shapes = {k: (tuple(v.shape), v.dtype) for k, v in params.items()}
            self._oo_opt = OutOfCoreAdamW(
                self.comm, shapes, tcfg.ckpt_dir or "/tmp/repro_opt",
                self.opt_cfg, memory_budget=tcfg.offload_memory_budget)
            self._oo_opt.initialize(params)
            params = self._place(((k, self._oo_opt.master(k))
                                  for k in self._oo_opt.param_keys),
                                 jnp.bfloat16)
            opt_state = None
        ef = init_error_feedback(params) if tcfg.compression else {
            k: jnp.zeros((1,), jnp.float32) for k in list(params)[:1]}
        if self.shardings is not None:
            # where the step puts it: the second step then compiles nothing
            ef = jax.device_put(ef, self.shardings if tcfg.compression
                                else self._replicated())

        start_step = 0
        if tcfg.ckpt_dir and tcfg.ckpt_every:
            # offload mode is for state the host barely holds: its
            # checkpoints keep no snapshot (every param changes every
            # step) and stream through a small page cache, so the host
            # holds no copy of the params between saves
            offload = tcfg.mode != "fused"
            self._ckpt = CheckpointManager(
                tcfg.ckpt_dir, self.comm, self._ckpt_specs(params),
                snapshot_diff=not offload,
                cache_bytes=OFFLOAD_CKPT_CACHE_BYTES if offload else None)
            if restore:
                res = self._ckpt.restore()
                if res is not None:
                    start_step = res.step
                    self.restored_step = res.step
                    with span("train.place", nbytes=sum(
                            v.nbytes for v in res.tree.values())):
                        params, opt_state = self._place_restored(
                            res.tree, opt_state)
                    del res  # the host copy of the state

        end = tcfg.steps if stop_after is None else min(tcfg.steps,
                                                        start_step + stop_after)
        step = start_step
        for step in range(start_step, end):
            batch = next(data_iter)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            t0 = time.monotonic()
            with span("train.step"):
                if tcfg.mode == "fused":
                    params, opt_state, ef, loss, stats = self._fused_step(
                        params, opt_state, ef, batch)
                else:
                    loss, grads = self._grads_step(params, batch)
                    # the walk fetches one gradient and yields one new
                    # param at a time, placed as it comes: no host copy of
                    # them all.  Only keys present in grads come back
                    # (sparse/MoE updates skip the rest) -- merge, never
                    # replace wholesale
                    params = {**params, **self._place(
                        self._oo_opt.iter_update(grads), jnp.bfloat16)}
                    del grads  # off the device before the next step
                    stats = {"lr": 0.0, "gnorm": 0.0}
                # the step ends when its new params are on the device
                jax.block_until_ready(params)
            dt = time.monotonic() - t0
            self.hb.beat(self.comm.rank, step)
            # beat every *probed-live* rank through the communicator (and
            # force-mark probed-dead ones), so the monitor tracks real
            # worker liveness, not just this process's self-report
            self.detector.poll(step)
            self.straggler.record(self.comm.rank, dt)
            rec = {"step": step, "loss": float(loss), "time": dt,
                   "lr": float(stats["lr"])}
            self.metrics_log.append(rec)
            if on_step:
                on_step(step, rec)
            if tcfg.log_every and step % tcfg.log_every == 0:
                print(f"step {step:5d} loss {rec['loss']:.4f} "
                      f"({dt*1e3:.0f} ms)", flush=True)
            if self._ckpt and tcfg.ckpt_every and (step + 1) % tcfg.ckpt_every == 0:
                save = (self._ckpt.save_async if tcfg.ckpt_async
                        else self._ckpt.save)
                with span("train.save"):
                    save(step + 1, self._ckpt_tree(params, opt_state))
            if tcfg.mode == "offload" and self._oo_opt is not None \
                    and tcfg.ckpt_every and (step + 1) % tcfg.ckpt_every == 0:
                self._oo_opt.sync()

        if self._ckpt:
            self._ckpt.wait()
        return params, opt_state

    def close(self):
        if self._ckpt:
            self._ckpt.close()
        if self._oo_opt:
            self._oo_opt.free()
