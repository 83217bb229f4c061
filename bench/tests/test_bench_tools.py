"""The pieces of a run that need no model: the quartile spread the bounds
are set from, the memory peak the result line reports, and where the
resume window closes."""

import contextlib
import json
import os
import statistics

import pytest

from bench import harness

SERIES = harness.load_module(
    os.path.join(harness.BENCH, "tools", "series.py"), "series")


def test_spread_is_iqr_over_median():
    v = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, med, q3 = statistics.quantiles(v, n=4)
    assert SERIES.spread(v) == pytest.approx((q3 - q1) / statistics.median(v))
    assert SERIES.spread([1.0]) is None


def test_counters_of_reads_the_last_counters_line():
    lines = ['{"at": "1", "what": "setup done", "setup_s": 3}',
             '{"at": "2", "what": "counters", "attempted": 4}',
             "check loss_gap: 1e-05 limit 0.0001 (ok)"]
    assert SERIES.counters_of("\n".join(lines))["attempted"] == 4
    assert SERIES.counters_of("no log") == {}


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("stats,want", [
    # a program's scratch reserved beside the arrays
    ({"peak_bytes_in_use": 2, "bytes_in_use": 1, "bytes_reserved": 13}, 14),
    # arrays that peaked above what is held when the window closes
    ({"peak_bytes_in_use": 12, "bytes_in_use": 4, "bytes_reserved": 1}, 12),
    ({}, 0),
])
def test_memory_peak_counts_reserved_scratch(monkeypatch, stats, want):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [_Dev(stats)])
    assert harness.memory_peak(1) == want


@pytest.mark.parametrize("each,seconds,resumes", [
    (10.1, 30, 3),  # just past 3 resumes: no fourth
    (9.9, 30, 3),   # just short of 3 resumes: no fourth either
    (8.0, 30, 4),
    (40.0, 30, 1),  # the first resume always runs
])
def test_resume_window_ends_at_nearest_boundary(monkeypatch, tmp_path, each,
                                                seconds, resumes):
    drv = harness.load_module(harness.driver_path("train_resume"), "res")
    with open(harness.traffic_path("train_resume")) as f:
        traffic = json.load(f)
    c = drv.Cell({}, traffic, 1, str(tmp_path))
    clock = [0.0]
    monkeypatch.setattr(drv.time, "monotonic", lambda: clock[0])

    def resume():
        clock[0] += each
        c.resumes.append({})

    c.resume = resume
    c.window(seconds, lambda _n: contextlib.nullcontext())
    assert len(c.resumes) == c.attempted == resumes
    assert c.window_s == pytest.approx(each * resumes)
