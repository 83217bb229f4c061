"""The program's spans (``repro.perf.trace``) in a profiler trace on the CPU:
their nesting, their threads, their byte counts, and their absence when
no profiler session is on."""

import collections
import contextlib
import dataclasses
import glob
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.ckpt import CheckpointManager  # noqa: E402
from repro.core import Communicator, Window  # noqa: E402
from repro.perf import trace  # noqa: E402

PAGE = 4096
PAGES = 16

Ev = collections.namedtuple("Ev", "name t0 t1 thread meta")


def _events(path: str) -> list:
    """The program's spans in the trace file, each with its host line."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(trace.PREFIX):
                    out.append(Ev(e.name[len(trace.PREFIX):], e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  (plane.name, i), dict(e.stats)))
    return out


@contextlib.contextmanager
def traced(tmp_path):
    """A profiler session around the block; the dict it yields gets the
    trace's ``events`` and the program's ``recorded`` totals."""
    d = str(tmp_path / "trace")
    trace.clear()
    got: dict = {}
    jax.profiler.start_trace(d)
    try:
        yield got
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                        recursive=True)
    got["events"] = _events(path)
    got["recorded"] = trace.recorded()


def parent(ev: Ev, events: list) -> str | None:
    """The innermost span on the same thread that holds ``ev``."""
    outer = [e for e in events if e is not ev and e.thread == ev.thread
             and e.t0 <= ev.t0 and ev.t1 <= e.t1
             and (e.t0, -e.t1) < (ev.t0, -ev.t1)]
    return max(outer, key=lambda e: (e.t0, -e.t1)).name if outer else None


def named(events: list, name: str) -> list:
    return [e for e in events if e.name == name]


def assert_nested(events: list, want: dict) -> None:
    for child, up in want.items():
        evs = named(events, child)
        assert evs, f"no {child} span"
        for ev in evs:
            assert parent(ev, events) == up, (child, parent(ev, events))


def _manager(tmp_path, comm):
    specs = {"b": ((PAGE // 4,), np.float32),
             "w": ((4 * PAGE // 4,), np.float32)}
    return CheckpointManager(str(tmp_path / "ck"), comm, specs), specs


def test_checkpoint_save_async_and_restore_spans(tmp_path):
    comm = Communicator(1)
    mgr, specs = _manager(tmp_path, comm)
    tree = {"b": np.ones(PAGE // 4, np.float32),
            "w": np.zeros(4 * PAGE // 4, np.float32)}
    mgr.save(1, tree)  # the first save to each window puts it whole
    mgr.save(2, tree)
    new = {"b": jnp.asarray(tree["b"]),
           "w": jnp.asarray(tree["w"]).at[PAGE // 4 + 3].set(5.0)}
    with traced(tmp_path) as got:
        req = mgr.save_async(3, new)
        mgr.wait()
        mgr.close()
        back = CheckpointManager.open_for_restore(str(tmp_path / "ck"),
                                                  comm, specs)
        res = back.restore()
    flushed = req.wait()
    back.close()
    comm.close()
    assert res.step == 3 and float(res.tree["w"][PAGE // 4 + 3]) == 5.0
    evs = got["events"]
    assert_nested(evs, {
        "ckpt.fetch": "ckpt.stage", "ckpt.snapshot": "ckpt.stage",
        "ckpt.diff": "ckpt.stage", "ckpt.read": "ckpt.restore",
        "storage.read": "ckpt.read", "storage.copy": "ckpt.read",
        "window.copy": "ckpt.read", "storage.apply": "storage.task",
        "storage.flush": "storage.task", "storage.write": "storage.flush",
        "storage.fsync": "storage.flush", "ckpt.commit": "storage.task"})
    assert {parent(e, evs) for e in named(evs, "ckpt.crc")} == {
        "ckpt.stage", "ckpt.restore"}
    for top in ("ckpt.stage", "ckpt.wait", "ckpt.restore", "ckpt.open"):
        assert named(evs, top) and all(parent(e, evs) is None
                                       for e in named(evs, top)), top
    # the flush and the commit run on the write-back thread, the rest on
    # the caller's
    caller = {e.thread for e in named(evs, "ckpt.stage")}
    assert len(caller) == 1
    for name in ("ckpt.commit", "storage.flush", "storage.apply",
                 "storage.task"):
        assert {e.thread for e in named(evs, name)}.isdisjoint(caller), name
    for name in ("ckpt.wait", "ckpt.fetch", "ckpt.crc", "ckpt.snapshot",
                 "ckpt.diff", "ckpt.restore", "ckpt.read", "storage.read",
                 "storage.copy", "window.copy", "ckpt.open"):
        assert {e.thread for e in named(evs, name)} == caller, name
    # what the spans carry
    assert [e.meta["pages"] for e in named(evs, "ckpt.diff")] == [0, 1]
    assert sum(e.meta["nbytes"] for e in named(evs, "ckpt.fetch")) == 5 * PAGE
    assert sum(e.meta["nbytes"] for e in named(evs, "storage.flush")) \
        == flushed == PAGE
    # the page cache reads w's four pages from the file in one run (b's
    # one page faults in alone)
    assert [e.meta["nbytes"] for e in named(evs, "storage.read")] == [
        4 * PAGE]
    (task,) = named(evs, "storage.task")
    assert task.meta["queued_s"] >= 0
    # the program's own record holds the same spans
    rec = got["recorded"]
    assert rec["storage.flush"]["meta"]["nbytes"] == flushed
    assert rec["ckpt.fetch"]["n"] == 2
    assert rec["ckpt.crc"]["n"] == 4
    assert rec["ckpt.stage"]["s"] >= rec["ckpt.diff"]["s"]


@pytest.mark.parametrize("impl", ["interpret", "ref"])
def test_device_sync_spans(tmp_path, impl):
    comm = Communicator(1)
    win = Window.allocate(comm, PAGES * PAGE, info={
        "alloc_type": "storage",
        "storage_alloc_filename": str(tmp_path / "w.bin")})
    a_snap = np.zeros(3 * PAGE // 4, np.float32)
    b_snap = np.ones(4 * PAGE // 4, np.float32)
    win.put(a_snap, 0, 0)
    win.put(b_snap, 0, 8 * PAGE)
    win.sync(0)
    a_cur = a_snap.copy()
    a_cur[PAGE // 4 + 1] = 5.0
    b_cur = b_snap.copy()
    b_cur[0] = 6.0
    b_cur[-1] = 7.0
    shards = [(jnp.asarray(a_cur), jnp.asarray(a_snap), 0),
              (jnp.asarray(b_cur), jnp.asarray(b_snap), 8 * PAGE)]
    win.sync_shards_from_device(0, shards, blocking=True, impl=impl)
    win.sync_shards_from_device(  # back, so the traced sync has work
        0, [(s, c, d) for c, s, d in shards], blocking=True, impl=impl)
    before = dict(win.device_sync_stats())
    with traced(tmp_path) as got:
        n = win.sync_shards_from_device(0, shards, blocking=True, impl=impl)
    stats = win.device_sync_stats()
    win.free()
    comm.close()
    assert n == 3 * PAGE
    evs = got["events"]
    want = {"window.fetch_bitmap": "window.device_sync",
            "window.fetch_payload": "window.device_sync",
            "window.spans": "window.device_sync",
            "storage.apply": "window.device_sync",
            "storage.flush": "window.device_sync",
            "storage.write": "storage.flush",
            "storage.fsync": "storage.flush"}
    if impl == "interpret":
        want["window.launch"] = "window.device_sync"
    assert_nested(evs, want)
    (sync,) = named(evs, "window.device_sync")
    assert sync.meta["shards"] == 2
    assert len({e.thread for e in evs}) == 1  # a blocking sync: one thread
    payload = sum(e.meta["nbytes"] for e in named(evs,
                                                  "window.fetch_payload"))
    if impl == "interpret":
        assert payload == stats["payload_bytes"] - before["payload_bytes"]
        assert len(named(evs, "window.fetch_payload")) == 1
    else:
        assert payload == stats["logical_bytes"] - before["logical_bytes"]
    (flush,) = named(evs, "storage.flush")
    assert flush.meta["nbytes"] == n
    assert got["recorded"]["storage.flush"]["meta"]["nbytes"] == n


def test_trainer_spans(tmp_path):
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.train import AdamWConfig, TrainConfig, Trainer
    cfg = get_config("internlm2-1.8b", smoke=True)
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=100)
    ds = SyntheticLM(cfg, batch=2, seq=16, microbatches=1, seed=1)
    tc = TrainConfig(steps=2, microbatches=1, log_every=0,
                     ckpt_dir=str(tmp_path / "ck"), ckpt_every=1)
    with traced(tmp_path) as got:
        tr = Trainer(cfg, opt, tc)
        tr.run(iter(ds.batch_at(i) for i in range(2)))
        tr.close()
        again = Trainer(cfg, opt, dataclasses.replace(tc, steps=3))
        again.run(iter([ds.batch_at(2)]))
        again.close()
    assert again.restored_step == 2
    evs = got["events"]
    assert_nested(evs, {"ckpt.stage": "train.save", "ckpt.restore": None,
                        "train.place": None})
    # a save waits for the one before; the run's end waits for the last
    assert {parent(e, evs) for e in named(evs, "ckpt.wait")} == {
        "train.save", None}
    assert len(named(evs, "train.build")) == 2
    assert len(named(evs, "train.step")) == 3
    assert len(named(evs, "train.save")) == 3  # after every step
    (place,) = named(evs, "train.place")
    assert place.meta["nbytes"] == sum(e.meta["nbytes"]
                                       for e in named(evs, "ckpt.read"))


def test_spans_record_nothing_without_a_profiler(tmp_path):
    trace.clear()
    a, b = trace.span("x", nbytes=1), trace.span("y")
    assert a is b  # the shared null span
    with a as s:
        s.set(nbytes=2)
    comm = Communicator(1)
    mgr, _ = _manager(tmp_path, comm)
    mgr.save(1, {"b": np.ones(PAGE // 4, np.float32),
                 "w": np.zeros(4 * PAGE // 4, np.float32)})
    mgr.restore()
    mgr.close()
    comm.close()
    assert trace.recorded() == {}


def test_spans_open_at_either_edge_are_not_recorded(tmp_path):
    d = str(tmp_path / "trace")
    trace.clear()
    before = trace.span("before")
    before.__enter__()
    jax.profiler.start_trace(d)
    try:
        before.__exit__(None, None, None)
        with trace.span("inside", nbytes=3):
            pass
        after = trace.span("after")
        after.__enter__()
    finally:
        jax.profiler.stop_trace()
    after.__exit__(None, None, None)
    rec = trace.recorded()
    assert list(rec) == ["inside"]
    assert (rec["inside"]["n"], rec["inside"]["meta"]) == (1, {"nbytes": 3})


def test_spans_from_many_threads_all_count(tmp_path):
    def work():
        for _ in range(200):
            with trace.span("stress", nbytes=1):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with traced(tmp_path) as got:
            threads = [threading.Thread(target=work) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    rec = got["recorded"]["stress"]
    assert (rec["n"], rec["meta"]) == (3200, {"nbytes": 3200})
    assert len(named(got["events"], "stress")) == 3200


def test_storage_imports_no_jax():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = ("import sys\n"
            "import repro.core.storage, repro.core.transport.multiproc\n"
            "from repro.perf.trace import span\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "assert span('a', nbytes=1) is span('b')\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True)
    assert p.returncode == 0, p.stderr
