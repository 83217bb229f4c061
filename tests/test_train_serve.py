"""Trainer (fused + offload + fault injection) and serving engine."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import SyntheticLM, make_batch_iter
from repro.models import init_cache_specs, init_params, param_specs
from repro.serve import Engine, SessionStore
from repro.core import Communicator
from repro.train import AdamWConfig, Trainer, TrainConfig


class FixedBatch:
    """Repeats one batch -> loss must fall (overfit sanity)."""

    def __init__(self, batch):
        self.batch = batch

    def __next__(self):
        return self.batch


def _fixed_batch(cfg, mb=1, B=4, S=24):
    ds = SyntheticLM(cfg, batch=B, seq=S, microbatches=mb, seed=7)
    return ds.batch_at(0)


def test_trainer_overfits_fixed_batch(tmp_path):
    cfg = get_config("internlm2-1.8b", smoke=True)
    opt = AdamWConfig(lr=2e-3, warmup_steps=0, total_steps=100,
                      weight_decay=0.0)
    tc = TrainConfig(steps=25, microbatches=1, log_every=0)
    tr = Trainer(cfg, opt, tc)
    tr.run(FixedBatch(_fixed_batch(cfg)))
    losses = [m["loss"] for m in tr.metrics_log]
    assert losses[-1] < losses[0] - 1.0, losses[::6]
    tr.close()


def test_trainer_ckpt_restart_is_exact(tmp_path):
    """Kill after step k; restart continues to the same final params."""
    cfg = get_config("internlm2-1.8b", smoke=True)
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=100)
    ck = str(tmp_path / "ck")

    def data():
        ds = SyntheticLM(cfg, batch=2, seq=16, microbatches=1, seed=1)
        class It:
            step = 0
            def __next__(self):
                b = ds.batch_at(It.step)
                It.step += 1
                return b
        return It()

    # uninterrupted run (no checkpointing interference in math)
    tcA = TrainConfig(steps=8, microbatches=1, log_every=0)
    trA = Trainer(cfg, opt, tcA)
    pA, _ = trA.run(data())

    # interrupted run: ckpt every 2, stop at 4, restart
    tcB = TrainConfig(steps=8, microbatches=1, log_every=0,
                      ckpt_dir=ck, ckpt_every=2, ckpt_async=False)
    trB = Trainer(cfg, opt, tcB)
    trB.run(data(), stop_after=4)
    trB._ckpt.wait()
    trC = Trainer(cfg, opt, tcB)
    it = data()
    for _ in range(4):  # align the data stream with the restored step
        next(it)
    pC, _ = trC.run(it)
    for k in pA:
        np.testing.assert_allclose(np.asarray(pA[k], np.float32),
                                   np.asarray(pC[k], np.float32),
                                   atol=1e-5, rtol=1e-4)
    trA.close(); trB.close(); trC.close()


def test_trainer_offload_mode(tmp_path):
    cfg = get_config("internlm2-1.8b", smoke=True)
    opt = AdamWConfig(lr=2e-3, warmup_steps=0, total_steps=100,
                      weight_decay=0.0)
    tc = TrainConfig(steps=10, mode="offload", log_every=0,
                     ckpt_dir=str(tmp_path / "oo"), ckpt_every=5)
    tr = Trainer(cfg, opt, tc)
    tr.run(FixedBatch(_fixed_batch(cfg)))
    losses = [m["loss"] for m in tr.metrics_log]
    assert losses[-1] < losses[0]
    # optimizer state lives in window files on storage
    assert os.path.exists(tmp_path / "oo" / "optstate.bin")
    tr.close()


def test_trainer_offload_checkpoint_keeps_no_host_copy(tmp_path):
    """Offload-mode checkpoints keep no host snapshot and stream through
    a bounded page cache, and a fresh trainer restores the saved params
    bit for bit."""
    from repro.train.loop import OFFLOAD_CKPT_CACHE_BYTES
    cfg = get_config("internlm2-1.8b", smoke=True)
    opt = AdamWConfig(lr=2e-3, warmup_steps=0, total_steps=100)
    tc = TrainConfig(steps=4, mode="offload", log_every=0,
                     ckpt_dir=str(tmp_path / "oo"), ckpt_every=2)
    tr = Trainer(cfg, opt, tc)
    params, _ = tr.run(FixedBatch(_fixed_batch(cfg)))
    ck = tr._ckpt
    assert ck.saves == 2 and not ck._snapshots
    for wt in ck.windows.values():
        backing = wt.win.segments[0].backing
        assert not backing.compare_on_write
        assert backing.capacity * backing.page_size <= OFFLOAD_CKPT_CACHE_BYTES
    saved = {k: np.asarray(v) for k, v in params.items()}
    tr.close()
    tr = Trainer(cfg, opt, tc)
    restored, _ = tr.run(iter(()), stop_after=0)
    assert tr.restored_step == 4
    for k, v in saved.items():
        got = np.asarray(restored[k])
        assert got.dtype == v.dtype and got.tobytes() == v.tobytes(), k
    tr.close()


def test_trainer_compression_still_learns():
    cfg = get_config("internlm2-1.8b", smoke=True)
    opt = AdamWConfig(lr=2e-3, warmup_steps=0, total_steps=100,
                      weight_decay=0.0)
    tc = TrainConfig(steps=20, compression=True, log_every=0)
    tr = Trainer(cfg, opt, tc)
    tr.run(FixedBatch(_fixed_batch(cfg)))
    losses = [m["loss"] for m in tr.metrics_log]
    assert losses[-1] < losses[0] - 0.5
    tr.close()


def test_engine_greedy_generation_and_session(tmp_path):
    cfg = get_config("internlm2-1.8b", smoke=True)
    params = init_params(param_specs(cfg), jax.random.PRNGKey(0))
    B, prompt, steps, max_len = 2, 6, 5, 32
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, prompt), 0,
                              cfg.vocab).astype(jnp.int32)

    store = SessionStore(Communicator(1), str(tmp_path / "sess.bin"),
                         init_cache_specs(cfg, B, max_len), factor="0.5")
    eng = Engine(cfg, params, batch=B, max_len=max_len, session=store)
    out_full = eng.generate({"inputs": toks}, steps)
    assert out_full.shape == (B, steps)

    # resumable sessions: run 2 steps, persist, "kill", reopen, continue
    eng2 = Engine(cfg, params, batch=B, max_len=max_len, session=store)
    nxt = eng2.prefill({"inputs": toks})
    seq = [nxt]
    nxt = eng2.step(nxt)
    seq.append(nxt)
    eng2.generated = [seq[0], seq[1]]
    eng2.save_session()
    del eng2
    eng3 = Engine(cfg, params, batch=B, max_len=max_len, session=store)
    eng3.load_session()
    assert eng3.pos == prompt + 1
    cont = seq[1]
    for _ in range(steps - 2):
        cont = eng3.step(cont)
        seq.append(cont)
    got = np.stack(seq, axis=1)
    np.testing.assert_array_equal(got, out_full)
    store.free()


def test_data_pipeline_determinism_and_prefetch():
    cfg = get_config("internlm2-1.8b", smoke=True)
    ds = SyntheticLM(cfg, batch=2, seq=16, seed=9)
    a = ds.batch_at(3)
    b = ds.batch_at(3)
    np.testing.assert_array_equal(a["inputs"], b["inputs"])
    it = make_batch_iter(iter(ds), prefetch=2)
    first = next(it)
    np.testing.assert_array_equal(first["inputs"], ds.batch_at(0)["inputs"])


def test_window_backed_dataset(tmp_path):
    from repro.data import WindowBackedDataset
    comm = Communicator(2)
    ds = WindowBackedDataset(comm, str(tmp_path / "corpus.bin"),
                             tokens_per_rank=4096)
    rng = np.random.default_rng(0)
    corpora = [rng.integers(0, 1000, 4096).astype(np.int32) for _ in range(2)]
    for r in range(2):
        ds.write_corpus(r, corpora[r])
    b = ds.batch_at(0, step=0, batch=2, seq=64)
    assert b["inputs"].shape == (2, 64)
    np.testing.assert_array_equal(b["inputs"][0], corpora[0][:64])
    ds.free()
