"""Profiler capture of the measured window and its reduction to numbers.

A traced run wraps its window in ``jax.profiler`` and in a host span named
``bench.window``; the benchmark's own host spans (``bench.<what>``) mark
what the host was doing.  :func:`load` turns the profiler's ``.xplane.pb``
into plain events, and :func:`reduce` turns those into:

* ``window_s``  -- the length of the ``bench.window`` span;
* ``busy_s``    -- the union of the intervals in which an operation ran on
  a device, inside the window, averaged over the devices that ran any;
* ``op_s``      -- device seconds by operation name;
* ``idle_gaps`` -- the device's idle time inside the window, by the host
  span that was open during it (``(host)`` where none was).

The reduction reads only the plain events, so it is tested on a small
recorded trace (``bench/tests/data``).
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil

#: host spans the reduction reads: the window and the benchmark's own
HOST_PREFIX = "bench."
WINDOW = "bench.window"
#: the device line that holds one event per executed operation
OPS_LINE = "XLA Ops"


@contextlib.contextmanager
def capture(trace_dir: str):
    """Trace what runs inside; yields a dict that gets ``path``, the
    ``.xplane.pb`` written, once the block exits."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    out: dict = {}
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield out
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out["path"] = max(found, key=os.path.getmtime) if found else None


def span(name: str):
    """A host span of the benchmark's own, seen by the reduction."""
    import jax
    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)


def load(path: str) -> dict:
    """``.xplane.pb`` -> ``{"host": [[name, start_ns, dur_ns]...],
    "devices": {plane: [[name, start_ns, dur_ns]...]}}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend([op_name(e.name), e.start_ns, e.duration_ns]
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"host": host, "devices": {k: v for k, v in devices.items() if v}}


def op_name(hlo: str) -> str:
    """``%pack_rows.1 = (u32[...]) custom-call(...)`` -> ``pack_rows.1``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def reduce(events: dict) -> dict | None:
    """Plain events -> window, busy time, device time by op, idle gaps by
    host span.  None where the trace holds no window or no device op."""
    wins = [e for e in events["host"] if e[0] == WINDOW]
    if not wins or not events["devices"]:
        return None
    _, w0, wdur = wins[0]
    w1 = w0 + wdur
    busy_ns, op_ns, idle = [], {}, {}
    spans = sorted(([e[1], e[1] + e[2], e[0][len(HOST_PREFIX):]]
                    for e in events["host"] if e[0] != WINDOW),
                   key=lambda s: (s[0], -s[1]))
    for evs in events["devices"].values():
        clipped = []
        for name, s, d in evs:
            lo, hi = max(s, w0), min(s + d, w1)
            if hi > lo:
                clipped.append((lo, hi))
                op_ns[name] = op_ns.get(name, 0.0) + (hi - lo)
        busy = _union(clipped)
        busy_ns.append(sum(hi - lo for lo, hi in busy))
        # idle gaps of this device, charged to the innermost host span
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                _charge(idle, spans, g0, g1)
    ndev = len(busy_ns)
    return {"window_s": wdur / 1e9,
            "busy_s": sum(busy_ns) / ndev / 1e9,
            "devices": ndev,
            "op_s": {k: v / 1e9 / ndev for k, v in op_ns.items()},
            "idle_gaps": {k: v / 1e9 / ndev for k, v in idle.items()}}


def _charge(idle: dict, spans, g0: float, g1: float) -> None:
    """Split the gap ``[g0, g1)`` among the host spans open in it, the
    innermost (latest-starting) span taking the time it covers."""
    t = g0
    while t < g1:
        open_ = [s for s in spans if s[0] <= t < s[1]]
        if open_:
            s = max(open_, key=lambda s: s[0])
            # the innermost span holds until it ends or a child opens
            nxt = min([s[1], g1] + [c[0] for c in spans if t < c[0] < s[1]])
            label = s[2]
        else:
            nxt = min([g1] + [c[0] for c in spans if t < c[0] < g1])
            label = "(host)"
        idle[label] = idle.get(label, 0.0) + (nxt - t)
        t = nxt


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line."""
    def first(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return {"device_ops": first(red["op_s"]),
            "idle_gaps": first(red["idle_gaps"])}


def kernel_s(red: dict, kernel: str) -> float:
    """Device seconds of the ops whose name holds ``kernel``."""
    return sum(v for k, v in red["op_s"].items() if kernel in k)
