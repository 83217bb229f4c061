"""The HACC I/O driver at a tiny size on the CPU, the device-sync kernels
in interpret mode: a run is correct against its numpy replay, and each
fault planted under the timed path makes it incorrect."""

import contextlib
import json
import os

import numpy as np
import pytest

from bench import harness
from bench.gen import hacc as gen
from bench.reference import hacc as ref

TRAFFIC = {"driver": "hacc_ckpt", "ranges": 4, "dirty_share": 0.2}


def small_config():
    with open(os.path.join(harness.BENCH, "configs", "hacc-io-100m.json")) as f:
        cfg = json.load(f)
    cfg["particles"] = 2048 * 32
    return cfg


def run_cell(tmp_path, seed=2**33 + 7, traffic=TRAFFIC, seconds=0.5):
    drv = harness.load_module(harness.driver_path("hacc_ckpt"), "hacc_drv")
    c = drv.Cell(small_config(), traffic, seed, str(tmp_path),
                 impl="interpret")
    try:
        c.setup()
        c.window(seconds, lambda _n: contextlib.nullcontext())
        out = c.end_to_end(), c.counters()
        c.release()
        checks = c.check()
    finally:
        c.close()
    return out, checks


def test_sound_run_is_correct(tmp_path):
    (e2e, counters), checks = run_cell(tmp_path)
    assert all(ch["ok"] for ch in checks), checks
    assert counters["checkpoints"] >= 1 and counters["failed"] == 0
    assert e2e["ckpt_GBps"] > 0


def test_same_sizes_for_every_seed():
    cfg = small_config()
    a, b = gen.Plan(cfg, TRAFFIC, 1), gen.Plan(cfg, TRAFFIC, 2**40 + 3)
    # every cycle of every seed dirties the same number of pages
    for plan in (a, b):
        for i in range(8):
            c = plan.cycle(i)
            assert int(c["lens"].sum()) == plan.dirty_pages == 13
            assert c["lens"].min() >= 1 and c["lens"].max() <= plan.seg
            ends = c["starts"] + c["lens"]
            assert (c["starts"] >= np.arange(4) * plan.seg).all()
            assert (ends <= (np.arange(4) + 1) * plan.seg).all()
    # how the count splits and where the ranges lie: from seed and cycle
    assert not np.array_equal(a.cycle(5)["starts"], b.cycle(5)["starts"])
    assert len({tuple(a.cycle(i)["lens"]) for i in range(8)}) > 1


def test_replay_is_exact_float32():
    cfg = small_config()
    plan = gen.Plan(cfg, TRAFFIC, 3)
    pages = np.arange(plan.pages)
    rng = np.random.default_rng(0)
    start = {k: rng.standard_normal((plan.pages, gen.PAGE_PARTICLES))
             .astype(np.float32) for k in gen.FLOATS}
    out = ref.replay(plan, 4, start, pages)
    assert all(v.dtype == np.float32 for v in out.values())
    changed = out["vx"] != start["vx"]
    assert 0 < changed.mean() < 1


def _unchanged(self, rank, shards, **kw):
    return 0


def _half(real):
    def sync(self, rank, shards, **kw):
        shards = list(shards)
        return real(self, rank, shards[: len(shards) // 2], **kw)
    return sync


def _altered(real):
    def spans(self, *a, **kw):
        out, mask = real(self, *a, **kw)
        if out:
            off, data = out[0]
            data = data.copy()
            data[0] ^= 0xFF
            out = [(off, data)] + out[1:]
        return out, mask
    return spans


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_faults_make_it_incorrect(tmp_path, monkeypatch, fault):
    from repro.core.window import Window
    if fault == "unchanged":
        monkeypatch.setattr(Window, "sync_shards_from_device", _unchanged)
    elif fault == "half":
        monkeypatch.setattr(Window, "sync_shards_from_device",
                            _half(Window.sync_shards_from_device))
    else:
        monkeypatch.setattr(Window, "_packed_device_spans",
                            _altered(Window._packed_device_spans))
    _, checks = run_cell(tmp_path)
    assert not all(ch["ok"] for ch in checks), checks


def test_control_is_caught(tmp_path):
    cal = harness.load_module(os.path.join(harness.BENCH, "tools",
                                           "calibrate.py"), "cal")
    drv = harness.load_module(harness.driver_path("hacc_ckpt"), "hacc_drv")
    c = drv.Cell(small_config(), TRAFFIC, 9, str(tmp_path), impl="interpret")
    try:
        c.setup()
        cal.hacc_control(c)
        c.window(0.3, lambda _n: contextlib.nullcontext())
        c.release()
        checks = {ch["name"]: ch for ch in c.check()}
    finally:
        c.close()
    assert not checks["file_vs_device_bytes"]["ok"], checks
    assert not checks["file_vs_replay_particles"]["ok"], checks
