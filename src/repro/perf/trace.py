"""Spans of the program, on the profiler's clock.

``span(name, **meta)`` marks one phase of the program's work: while a
``jax.profiler`` session is on, it is a ``jax.profiler.TraceAnnotation``
named ``repro.<name>`` whose keyword metadata (``nbytes``, ``pages``, ...)
become the event's stats in the trace, beside the device's operations and
on the same clock.  Otherwise it is one shared null context and costs one
check.  It never imports JAX: where JAX is not loaded (the storage workers
of the multiprocess transport) every span is the null one.

The names carry their layer (``ckpt.stage``, ``window.fetch_payload``,
``storage.flush``).  A span marks one array's phase at the finest, never a
page, block or byte span, so a save or a sync opens a bounded number.

While a session is on, every span that begins and ends inside it also
adds its seconds, its count and the sums of its numeric metadata to
:func:`recorded`, by name: what a reader inside the process can take
without the trace file.
"""

from __future__ import annotations

import sys
import threading
import time

__all__ = ["PREFIX", "clear", "recorded", "span"]

#: prefix of the program's spans in the trace
PREFIX = "repro."


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **meta) -> None:
        pass


_NULL = _Null()
_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded
_lock = threading.Lock()
_totals: dict[str, dict] = {}


def _active():
    """The annotation class while a profiler session is on, else None."""
    global _annotation
    ta = _annotation
    if ta is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        ta = getattr(profiler, "TraceAnnotation", None)
        if ta is None:
            return None
        _annotation = ta
    return ta if ta.is_enabled() else None


class _Span:
    __slots__ = ("name", "meta", "_ann", "_t0")

    def __init__(self, name: str, meta: dict, ta):
        self.name = name
        self.meta = meta
        self._ann = ta(PREFIX + name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **meta) -> None:
        """Metadata known only at the end, such as the bytes written."""
        self.meta.update(meta)
        self._ann.set_metadata(**meta)

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if _annotation.is_enabled():
            with _lock:
                tot = _totals.setdefault(self.name,
                                         {"s": 0.0, "n": 0, "meta": {}})
                tot["s"] += dt
                tot["n"] += 1
                sums = tot["meta"]
                for k, v in self.meta.items():
                    sums[k] = sums.get(k, 0) + v
        return False


def span(name: str, **meta):
    """A span named ``repro.<name>``; its numeric ``meta`` travels as the
    trace event's stats.  Use as ``with span(...) as s:``; ``s.set(...)``
    adds metadata before it ends."""
    ta = _active()
    if ta is None:
        return _NULL
    return _Span(name, meta, ta)


def recorded() -> dict:
    """``{name: {"s", "n", "meta": {stat: sum}}}`` of the spans that began
    and ended while a profiler session was on, since :func:`clear`."""
    with _lock:
        return {k: {"s": v["s"], "n": v["n"], "meta": dict(v["meta"])}
                for k, v in _totals.items()}


def clear() -> None:
    with _lock:
        _totals.clear()
