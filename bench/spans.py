"""The program's own spans in a traced run.

The program marks its phases with ``repro.perf.trace.span``: host spans
named ``repro.<layer>.<what>`` in the profiler's trace, whose metadata
(``nbytes``, ``pages``, ``queued_s``, ...) are the events' stats.  This
module reads them two ways.

* :func:`program_spans`: what the program recorded itself, in this
  process, of the spans that began and ended while the profiler was on (in
  a run of ``bench/run.py``, the traced window).  The per-layer metrics
  read these.  A program without ``repro.perf.trace`` gives ``{}``.
* :func:`load` and :func:`reduce`: the trace file.  :func:`load` keeps the
  ``bench.*`` and ``repro.*`` host events with their thread (their line in
  the host plane) and numeric stats; :func:`reduce` is
  :func:`bench.tracing.reduce` (the same ``window_s``, ``busy_s`` and
  ``op_s``) with the program's spans in ``idle_gaps`` and a ``spans`` key.

Labels drop the ``bench.`` and ``repro.`` prefixes: the benchmark's spans
have none of their own (``step``, ``checkpoint``, ``resume``), the
program's keep their layer (``ckpt.crc``), so they cannot collide.

An idle gap of the device goes to the innermost open span on the thread of
the innermost open ``bench.*`` span other than the window, so a flush on
the write-back thread never takes the training thread's idle.  Where only
the window is open, it goes to the innermost open span on any thread.
Events without a thread (the recorded ``trace_small.json``, made-up
events) read as one thread, as :func:`bench.tracing.reduce` reads them.
"""

from __future__ import annotations

from bench import tracing

PROGRAM_PREFIX = "repro."
PREFIXES = (tracing.HOST_PREFIX, PROGRAM_PREFIX)
HOST = "(host)"


def program_spans() -> dict:
    """``{label: {"s", "n", "meta": {stat: sum}}}`` as the program
    recorded them; ``{}`` where the program records none."""
    try:
        from repro.perf import trace
    except ImportError:
        return {}
    return trace.recorded()


def per(label: str, counter: str, run):
    """Seconds of ``label`` per unit of the driver's ``counter`` (saves,
    checkpoints, resumes); None where either is missing."""
    sp = program_spans().get(label)
    n = run.counters.get(counter)
    return sp["s"] / n if sp and n else None


def rate_GBps(label: str):
    """``nbytes`` over seconds of the ``label`` spans, in GB/s; None where
    there are none or they moved nothing."""
    sp = program_spans().get(label)
    if not sp or sp["s"] <= 0 or not sp["meta"].get("nbytes"):
        return None
    return sp["meta"]["nbytes"] / sp["s"] / 1e9


def _label(name: str) -> str:
    for p in PREFIXES:
        if name.startswith(p):
            return name[len(p):]
    return name


def load(path: str) -> dict:
    """``.xplane.pb`` -> ``{"host": [[name, start_ns, dur_ns, thread,
    {stat: value}]...], "devices": {plane: [[name, start_ns, dur_ns]...]}}``
    where ``thread`` is the event's line in its host plane."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    evs.extend([tracing.op_name(e.name), e.start_ns,
                                e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        stats = {k: v for k, v in e.stats
                                 if isinstance(v, (int, float))}
                        host.append([e.name, e.start_ns, e.duration_ns,
                                     f"{plane.name}/{i}", stats])
    return {"host": host, "devices": {k: v for k, v in devices.items() if v}}


def reduce(events: dict) -> dict | None:
    """:func:`bench.tracing.reduce` of the same events, with ``idle_gaps``
    charged among the benchmark's and the program's spans and a ``spans``
    key: ``{label: {"s", "n", "meta", "whole_s", "whole_n",
    "whole_meta"}}``, seconds clipped to the window (``s``, ``n`` and
    ``meta`` over the spans that overlap it) and, for rates, the spans that
    begin and end inside it (``whole_*``)."""
    bench_only = [e[:3] for e in events["host"]
                  if e[0].startswith(tracing.HOST_PREFIX)]
    red = tracing.reduce({"host": bench_only, "devices": events["devices"]})
    if red is None:
        return None
    (w0, wdur) = next(e[1:3] for e in events["host"]
                      if e[0] == tracing.WINDOW)
    w1 = w0 + wdur
    spans = [(e[1], e[1] + e[2], _label(e[0]),
              e[3] if len(e) > 3 else None,
              e[0].startswith(tracing.HOST_PREFIX),
              e[4] if len(e) > 4 else {})
             for e in events["host"] if e[0] != tracing.WINDOW]
    red["spans"] = _totals(spans, w0, w1)
    segments = _segments(spans)
    idle: dict = {}
    for evs in events["devices"].values():
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in evs]
        busy = tracing._union([iv for iv in clipped if iv[1] > iv[0]])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        _charge(idle, gaps, segments)
    ndev = red["devices"]
    red["idle_gaps"] = {k: v / 1e9 / ndev for k, v in idle.items()}
    return red


def _totals(spans, w0: float, w1: float) -> dict:
    out: dict = {}
    for s0, s1, label, _thread, _bench, meta in spans:
        lo, hi = max(s0, w0), min(s1, w1)
        if not (lo < hi or w0 <= s0 == s1 <= w1):
            continue  # outside the window
        t = out.setdefault(label, {"s": 0.0, "n": 0, "meta": {},
                                   "whole_s": 0.0, "whole_n": 0,
                                   "whole_meta": {}})
        t["s"] += (hi - lo) / 1e9
        t["n"] += 1
        _add(t["meta"], meta)
        if w0 <= s0 and s1 <= w1:
            t["whole_s"] += (s1 - s0) / 1e9
            t["whole_n"] += 1
            _add(t["whole_meta"], meta)
    return out


def _add(sums: dict, meta: dict) -> None:
    for k, v in meta.items():
        sums[k] = sums.get(k, 0) + v


def _segments(spans) -> list:
    """``[(t0, t1, label)]``: the timeline cut where any span begins or
    ends, each piece with the span its idle goes to."""
    spans = [s for s in spans if s[1] > s[0]]  # an instant holds no idle
    cuts = sorted({t for s in spans for t in s[:2]})
    starts: dict = {}
    ends: dict = {}
    for i, s in enumerate(spans):
        starts.setdefault(s[0], []).append(i)
        ends.setdefault(s[1], []).append(i)
    open_: set = set()
    out = []
    for t0, t1 in zip(cuts, cuts[1:]):
        open_.difference_update(ends.get(t0, ()))
        open_.update(starts.get(t0, ()))
        out.append((t0, t1, _owner(spans, open_)))
    return out


def _owner(spans, open_: set) -> str:
    """The innermost open span (the latest begun; of two begun together,
    the one that ends first) on the thread of the innermost open
    benchmark span, or on any thread where none is open."""
    def inner(ids):
        return max(ids, key=lambda i: (spans[i][0], -spans[i][1]))

    if not open_:
        return HOST
    benches = [i for i in open_ if spans[i][4]]
    if benches:
        thread = spans[inner(benches)][3]
        open_ = [i for i in open_ if spans[i][3] == thread]
    return spans[inner(open_)][2]


def _charge(idle: dict, gaps, segments) -> None:
    """Add each gap's overlap with each piece of the timeline to that
    piece's span; time outside every span to ``(host)``."""
    j = 0
    for g0, g1 in gaps:
        t = g0
        while j < len(segments) and segments[j][1] <= t:
            j += 1
        k = j
        while t < g1:
            if k >= len(segments) or segments[k][0] >= g1:
                idle[HOST] = idle.get(HOST, 0.0) + (g1 - t)
                break
            s0, s1, label = segments[k]
            if s0 > t:
                idle[HOST] = idle.get(HOST, 0.0) + (s0 - t)
                t = s0
            hi = min(s1, g1)
            idle[label] = idle.get(label, 0.0) + (hi - t)
            t = hi
            k += 1
