"""The reduction from trace events to metrics, on a small trace recorded on
a TPU v5e (three fused diff+pack passes, each in a ``bench.sync`` host
span, then a ``bench.host`` span of host work) and on made-up events."""

import json
import os

import pytest

from bench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return json.load(f)


def test_recorded_window_and_busy(recorded):
    red = tracing.reduce(recorded)
    (win,) = [e for e in recorded["host"] if e[0] == tracing.WINDOW]
    assert red["window_s"] == pytest.approx(win[2] / 1e9)
    evs = sorted(recorded["devices"]["/device:TPU:0"], key=lambda e: e[1])
    # no two ops of this trace overlap, so busy is the sum of durations
    assert all(a[1] + a[2] <= b[1] for a, b in zip(evs, evs[1:]))
    assert red["busy_s"] == pytest.approx(sum(e[2] for e in evs) / 1e9)
    assert red["busy_s"] == pytest.approx(0.000682245)
    idle = sum(red["idle_gaps"].values())
    assert idle + red["busy_s"] == pytest.approx(red["window_s"])
    assert red["idle_gaps"]["sync"] == pytest.approx(0.112016761)
    assert red["idle_gaps"]["host"] == pytest.approx(0.126454508)


def test_recorded_kernels(recorded):
    red = tracing.reduce(recorded)
    assert tracing.kernel_s(red, "pack_rows") == pytest.approx(0.000262528)
    assert tracing.kernel_s(red, "dirty_diff") == pytest.approx(3.1857e-05)
    bd = tracing.breakdown(red)
    assert len(bd["device_ops"]) == 10
    times = [v for _, v in bd["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert bd["device_ops"][0][0] == "pack_rows.1"


def test_union_and_nested_spans():
    ev = {"host": [["bench.window", 0, 100],
                   ["bench.outer", 10, 60],
                   ["bench.inner", 20, 10]],
          "devices": {"/device:TPU:0": [["a", 0, 10], ["b", 5, 10],
                                        ["c", 90, 20]]}}
    red = tracing.reduce(ev)
    # busy: [0, 15) and [90, 100) inside the window
    assert red["busy_s"] == pytest.approx(25e-9)
    assert red["op_s"]["c"] == pytest.approx(10e-9)
    gaps = red["idle_gaps"]
    assert gaps["inner"] == pytest.approx(10e-9)
    assert gaps["outer"] == pytest.approx(55e-9 - 10e-9)
    assert gaps["(host)"] == pytest.approx(20e-9)
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(100e-9)


def test_nothing_to_read():
    assert tracing.reduce({"host": [], "devices": {}}) is None
    assert tracing.reduce({"host": [["bench.window", 0, 5]],
                           "devices": {}}) is None


def test_op_name():
    assert tracing.op_name("%pack_rows.1 = (u32[4096,8,128]) custom-call("
                           "s32[4096] %b)") == "pack_rows.1"
    assert tracing.op_name("fusion.3") == "fusion.3"
