"""The resume driver at a tiny size on the CPU: resumes restore the saved
state bit for bit and continue the job's loss, and faults planted in the
restore make the run incorrect."""

import contextlib
import json
import os

import pytest

from bench import harness


def tiny():
    with open(os.path.join(harness.BENCH, "configs",
                           "internlm2-1.8b-2l.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=256)
    with open(harness.traffic_path("train_resume")) as f:
        traffic = json.load(f)
    traffic.update(batch=4, seq=32, limits={"loss_gap": 5e-3})
    return cfg, traffic


def run_cell(tmp_path, after_setup=None):
    drv = harness.load_module(harness.driver_path("train_resume"), "res_drv")
    cfg, traffic = tiny()
    c = drv.Cell(cfg, traffic, 2**33 + 13, str(tmp_path))
    try:
        c.setup()
        if after_setup:
            after_setup(c)
        c.window(0.5, lambda _n: contextlib.nullcontext())
        out = c.end_to_end(), c.counters()
        c.release()
        checks = {ch["name"]: ch for ch in c.check()}
    finally:
        c.close()
    return out, checks


def test_sound_run_is_correct(tmp_path):
    (e2e, counters), checks = run_cell(tmp_path)
    assert all(ch["ok"] for ch in checks.values()), checks
    assert counters["resumes"] >= 1 and e2e["resume_s"] > 0


def _flip(c):
    from bench.drivers.train_ckpt import _flip_byte
    _flip_byte(c.ckpt_dir)


def _altered_restore(monkeypatch):
    from repro.ckpt import manager

    real = manager.CheckpointManager._try_restore

    def restore(self, path):
        res = real(self, path)
        if res is not None:
            k = sorted(res.tree)[0]
            res.tree[k] = res.tree[k].copy()
            res.tree[k].reshape(-1)[0] += 1
        return res

    monkeypatch.setattr(manager.CheckpointManager, "_try_restore", restore)


@pytest.mark.parametrize("fault", ["file_byte", "altered_restore"])
def test_faults_make_it_incorrect(tmp_path, monkeypatch, fault):
    if fault == "file_byte":
        _, checks = run_cell(tmp_path, _flip)
    else:
        _, checks = run_cell(
            tmp_path, lambda c: _altered_restore(monkeypatch))
    assert not all(ch["ok"] for ch in checks.values()), checks


def test_control_is_caught(tmp_path, monkeypatch):
    from repro.ckpt import manager
    cal = harness.load_module(os.path.join(harness.BENCH, "tools",
                                           "calibrate.py"), "cal")
    monkeypatch.setattr(manager.CheckpointManager, "_try_restore",
                        manager.CheckpointManager._try_restore)
    _, checks = run_cell(tmp_path, cal.resume_control)
    assert not checks["resumes_state_differs"]["ok"], checks
