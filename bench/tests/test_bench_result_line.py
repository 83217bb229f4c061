"""A whole run through the harness at a tiny size, with the look for a
chip skipped: the last line carries exactly the result line's keys, the
numbers compared come last, and a run with no TPU prints nothing."""

import json
import subprocess
import sys

import pytest

from bench import harness

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture
def tiny_hacc(monkeypatch):
    monkeypatch.setattr(harness, "device_info", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": chips})
    real = harness.resolve

    def resolve(bench, workload):
        spec = real(bench, workload)
        spec["config"]["particles"] = 2048 * 32
        spec["traffic"].update(ranges=4, dirty_share=0.2)
        return spec

    monkeypatch.setattr(harness, "resolve", resolve)


def test_last_line_keys(tiny_hacc, capsys):
    rc = harness.run(["--workload", "hacc_ckpt_sparse.hacc-io-100m",
                      "--seed", str(2**32 + 5), "--seconds", "0.5",
                      "--trace", "0"])
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line) == KEYS
    assert line["correct"] is True
    assert set(line["metrics"]) == {"ckpt_GBps", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(line["device"])
    assert all(set(v) == {"value", "limit"} for v in line["checks"].values())
    last = out.err.strip().splitlines()[-len(line["checks"]):]
    assert all(s.startswith("check ") for s in last)


def test_no_tpu_prints_nothing():
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "hacc_ckpt_sparse.hacc-io-100m", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=harness.ROOT, capture_output=True, text=True,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_traced_line_keys(tiny_hacc, monkeypatch, capsys):
    import contextlib
    import os

    from bench import tracing
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_small.json")) as f:
        recorded = json.load(f)

    @contextlib.contextmanager
    def capture(_dir):
        yield {"path": "recorded"}

    monkeypatch.setattr(tracing, "capture", capture)
    monkeypatch.setattr(tracing, "load", lambda _path: recorded)
    rc = harness.run(["--workload", "hacc_ckpt_sparse.hacc-io-100m",
                      "--seed", "3", "--seconds", "0.3", "--trace", "1"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line) == KEYS[:-1] + ["breakdown", "checks"]
    assert set(line["metrics"]) == {"dirty_diff_roofline",
                                    "pack_rows_roofline", "device_idle.hacc"}
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
