"""The program's spans in the reduction (``bench.spans``) and the per-layer
metrics that read them, on made-up events and on the recorded small trace
(``bench/tests/data/trace_small.json``, which holds no thread or stats)."""

import json
import os
import sys
import types

import pytest

from bench import harness, spans, tracing

HERE = os.path.dirname(os.path.abspath(__file__))

TRAIN = "train"
WB = "write-back"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return json.load(f)


def test_recorded_trace_reads_as_before(recorded):
    old, new = tracing.reduce(recorded), spans.reduce(recorded)
    for key in ("window_s", "busy_s", "devices", "op_s"):
        assert new[key] == old[key]
    assert new["idle_gaps"].keys() == old["idle_gaps"].keys()
    for k, v in old["idle_gaps"].items():
        assert new["idle_gaps"][k] == pytest.approx(v, rel=1e-12)
    assert new["spans"]["sync"]["n"] == 3
    assert tracing.breakdown(new)["device_ops"] == \
        tracing.breakdown(old)["device_ops"]


@pytest.mark.parametrize("events", [
    {"host": [["bench.window", 0, 100], ["bench.outer", 10, 60],
              ["bench.inner", 20, 10]],
     "devices": {"/device:TPU:0": [["a", 0, 10], ["b", 5, 10],
                                   ["c", 90, 20]]}},
    {"host": [["bench.window", 0, 100], ["bench.a", 0, 30],
              ["bench.b", 30, 30], ["bench.c", 70, 10]],
     "devices": {"/device:TPU:0": [["x", 20, 20]],
                 "/device:TPU:1": [["y", 50, 40]]}},
])
def test_events_without_threads_charge_as_before(events):
    old, new = tracing.reduce(events), spans.reduce(events)
    assert (new["window_s"], new["busy_s"], new["op_s"]) == \
        (old["window_s"], old["busy_s"], old["op_s"])
    assert new["idle_gaps"] == pytest.approx(old["idle_gaps"])


def two_threads():
    """A training thread whose save stages for 40 ns, beside a write-back
    thread flushing the previous save for 50 ns; the device runs a step
    at [0, 20) and [80, 100)."""
    return {
        "host": [["bench.window", 0, 100, TRAIN, {}],
                 ["bench.step+save", 0, 80, TRAIN, {}],
                 ["repro.train.save", 20, 50, TRAIN, {}],
                 ["repro.ckpt.stage", 25, 40, TRAIN, {}],
                 ["repro.ckpt.fetch", 25, 10, TRAIN, {"nbytes": 8}],
                 ["repro.ckpt.crc", 35, 30, TRAIN, {"nbytes": 8}],
                 ["repro.storage.task", 10, 60, WB, {"queued_s": 0.5}],
                 ["repro.storage.flush", 15, 50, WB, {"nbytes": 100}],
                 ["repro.storage.fsync", 40, 25, WB, {}],
                 ["bench.step", 80, 20, TRAIN, {}]],
        "devices": {"/device:TPU:0": [["step", 0, 20], ["step", 80, 20]]}}


def test_write_back_thread_takes_none_of_the_training_threads_idle():
    red = spans.reduce(two_threads())
    gaps = red["idle_gaps"]
    assert red["busy_s"] == pytest.approx(40e-9)
    # [20, 80): train.save until the stage's fetch and CRC, then the
    # step+save span the save returns to
    assert gaps == pytest.approx({"train.save": 10e-9, "ckpt.fetch": 10e-9,
                                  "ckpt.crc": 30e-9, "step+save": 10e-9})
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(100e-9)


def test_only_the_window_open_charges_any_thread():
    ev = {"host": [["bench.window", 0, 100, TRAIN, {}],
                   ["repro.storage.flush", 10, 30, WB, {"nbytes": 5}],
                   ["repro.storage.fsync", 20, 10, WB, {}]],
          "devices": {"/device:TPU:0": [["op", 60, 40]]}}
    gaps = spans.reduce(ev)["idle_gaps"]
    assert gaps == pytest.approx({"(host)": 30e-9, "storage.flush": 20e-9,
                                  "storage.fsync": 10e-9})


def test_spans_clip_to_the_window_and_rate_over_whole_ones():
    ev = {"host": [["bench.window", 100, 100, TRAIN, {}],
                   # begins before the window: clipped, not whole
                   ["repro.storage.flush", 50, 100, WB, {"nbytes": 1000}],
                   ["repro.storage.flush", 120, 40, WB, {"nbytes": 80}],
                   ["repro.storage.flush", 170, 10, WB, {"nbytes": 30}],
                   # ends after it
                   ["repro.storage.flush", 190, 50, WB, {"nbytes": 500}],
                   # outside it
                   ["repro.storage.flush", 0, 20, WB, {"nbytes": 7}],
                   ["repro.storage.flush", 250, 20, WB, {"nbytes": 7}]],
          "devices": {"/device:TPU:0": [["op", 100, 100]]}}
    fl = spans.reduce(ev)["spans"]["storage.flush"]
    assert fl["n"] == 4
    assert fl["s"] == pytest.approx((50 + 40 + 10 + 10) * 1e-9)
    assert fl["meta"] == {"nbytes": 1000 + 80 + 30 + 500}
    assert fl["whole_n"] == 2
    assert fl["whole_s"] == pytest.approx(50e-9)
    assert fl["whole_meta"] == {"nbytes": 110}


def test_metadata_sums():
    sp = spans.reduce(two_threads())["spans"]
    assert sp["ckpt.fetch"]["meta"] == {"nbytes": 8}
    assert sp["storage.task"]["meta"] == {"queued_s": 0.5}
    assert sp["ckpt.stage"]["meta"] == {}
    assert sp["step+save"]["s"] == pytest.approx(80e-9)


# -- the metric readers ------------------------------------------------------

def run_of(**counters):
    return types.SimpleNamespace(counters=counters)


def span_totals(**secs_and_meta):
    return {k.replace("__", "."): {"s": s, "n": 1, "meta": meta}
            for k, (s, meta) in secs_and_meta.items()}


PROGRAM = span_totals(ckpt__stage=(6.0, {}), ckpt__wait=(0.3, {}),
                      storage__flush=(2.0, {"nbytes": 3e9}),
                      window__device_sync=(2.5, {"shards": 9}),
                      window__fetch_bitmap=(1.0, {}),
                      window__fetch_payload=(0.5, {"nbytes": 1e8}),
                      ckpt__restore=(9.0, {}),
                      storage__read=(4.0, {"nbytes": 6e9}))

READINGS = [
    ("save_stage_s", {"saves": 3}, 2.0),
    ("save_wait_s", {"saves": 3}, 0.1),
    ("flush_GBps.train", {"saves": 3}, 1.5),
    ("flush_GBps.hacc", {"checkpoints": 2}, 1.5),
    ("sync_host_s", {"checkpoints": 2}, 0.5),
    ("restore_s", {"resumes": 3}, 3.0),
    ("restore_read_GBps", {"resumes": 3}, 1.5),
]


def metric(name):
    return harness.load_module(harness.metric_path(name), "bench_metric")


@pytest.mark.parametrize("name,counters,want", READINGS)
def test_metric_reads_program_spans(monkeypatch, name, counters, want):
    monkeypatch.setattr(spans, "program_spans", lambda: PROGRAM)
    assert metric(name).read(run_of(**counters)) == pytest.approx(want)


@pytest.mark.parametrize("name,counters,_want", READINGS)
def test_metric_without_its_spans_reads_none(monkeypatch, name, counters,
                                             _want):
    monkeypatch.setattr(spans, "program_spans", lambda: {})
    assert metric(name).read(run_of(**counters)) is None


@pytest.mark.parametrize("name,counters,_want", READINGS)
def test_metric_without_the_program_module_reads_none(monkeypatch, name,
                                                      counters, _want):
    """A program older than its spans (no ``repro.perf.trace``)."""
    import repro.perf
    monkeypatch.delattr(repro.perf, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.perf.trace", None)
    assert spans.program_spans() == {}
    assert metric(name).read(run_of(**counters)) is None


def test_metrics_are_declared():
    bench = harness.load_benchmark()
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name, _c, _w in READINGS:
        m = per_layer[name]
        assert m["source"] == "program_span"
        for cell in m["workloads"]:
            assert harness.resolve(bench, cell)["per_layer"].count(m) == 1
