"""The training driver at a tiny size on the CPU: a run's compared numbers
sit under limits against the plain reference, its checkpoint restores bit
for bit, and each planted fault makes it incorrect."""

import contextlib
import json
import os

import pytest

from bench import harness

LIMITS = {"loss_gap": 5e-3, "grad_norm_gap": 2e-2, "change_norm_gap": 2e-2}


def tiny():
    with open(os.path.join(harness.BENCH, "configs",
                           "internlm2-1.8b-2l.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=256)
    with open(harness.traffic_path("train_ckpt")) as f:
        traffic = json.load(f)
    traffic.update(batch=4, seq=32, ckpt_every=2, limits=LIMITS)
    return cfg, traffic


def run_cell(tmp_path, fault=None):
    drv = harness.load_module(harness.driver_path("train_ckpt"), "train_drv")
    cfg, traffic = tiny()
    c = drv.Cell(cfg, traffic, 2**33 + 11, str(tmp_path), fault=fault)
    try:
        c.setup()
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
    assert counters["saves"] >= 1 and counters["failed"] == 0
    assert counters["steps"] == counters["saves"] * 2
    assert e2e["train_tokens_per_s"] > 0


@pytest.mark.parametrize("fault,caught", [
    ("unchanged", "change_norm_gap"),
    ("half_batch", "grad_norm_gap"),
    ("ckpt_byte", "ckpt_vs_device_bytes"),
])
def test_faults_make_it_incorrect(tmp_path, fault, caught):
    _, checks = run_cell(tmp_path, fault)
    assert not checks[caught]["ok"], checks


def test_control_is_caught():
    drv = harness.load_module(harness.driver_path("train_ckpt"), "train_drv")
    from bench.reference.internlm2 import fp8_cast
    cfg, traffic = tiny()
    want = drv.reference_readings(cfg, traffic, 5)
    got = drv.reference_readings(cfg, traffic, 5, cast=fp8_cast)
    gaps = drv.gaps(got, want)
    assert any(gaps[k] > LIMITS[k] for k in LIMITS), gaps
    assert drv.gaps(want, want) == {k: 0.0 for k in LIMITS}
