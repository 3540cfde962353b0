"""Host spans of the program: ``jax.profiler.TraceAnnotation``s whose
names share the prefix ``trsm.``.

A span lands in the profiler's host plane, on the clock of the device
ops, so a gap on the device can be put down to what the host was doing
(PERF.md names each span with the metric that reads it).  With no
profiler active a span costs one ``TraceMe.is_enabled()`` check.

While a trace is being taken every span is also kept, as
``(name, start_ns, end_ns, cpu_ns)`` on ``time.perf_counter_ns``, in a
bounded in-process record that :func:`recorded` returns: a reader in
the same process gets the intervals without the trace file
(tests/test_tracing.py holds the record to the trace).  ``cpu_ns`` is
the thread's CPU time inside the span (``time.thread_time_ns``): it
leaves out the time the thread sleeps, on the device, a lock or the
GIL, which the span's length includes.  :func:`record` adds an interval that no
single thread spans, such as a request's wait in its queue; it has no
TraceAnnotation, since a TraceMe ends on the thread that began it.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

from jax.profiler import TraceAnnotation

PREFIX = "trsm."
KEEP = 1 << 18                  # newest intervals kept in the record

_active = TraceAnnotation.is_enabled
_clock = time.perf_counter_ns
_cpu = time.thread_time_ns
# process-wide, as the profiler session whose spans it keeps
_record: collections.deque = collections.deque(maxlen=KEEP)
_record_lock = threading.Lock()


class _Span:
    __slots__ = ("name", "_tm", "_t0", "_c0")

    def __init__(self, name: str):
        self.name = name
        self._tm = TraceAnnotation(name)

    def __enter__(self) -> "_Span":
        self._tm.__enter__()
        self._t0 = _clock()
        self._c0 = _cpu()
        return self

    def __exit__(self, *exc) -> None:
        c1 = _cpu()
        t1 = _clock()
        self._tm.__exit__(*exc)
        with _record_lock:
            _record.append((self.name, self._t0, t1, c1 - self._c0))


_OFF = contextlib.nullcontext()


def span(name: str):
    """``with span("submit"):`` -- the ``trsm.submit`` host span; a
    shared no-op when no trace is being taken."""
    return _Span(PREFIX + name) if _active() else _OFF


def record(name: str, seconds: float) -> None:
    """Keep ``trsm.<name>`` as an interval of ``seconds`` ending now,
    with no CPU time, if a trace is being taken."""
    if _active():
        t1 = _clock()
        with _record_lock:
            _record.append((PREFIX + name, t1 - int(seconds * 1e9), t1, 0))


def recorded() -> list:
    """The kept ``(name, start_ns, end_ns, cpu_ns)`` intervals, oldest
    first."""
    with _record_lock:
        return list(_record)


def clear() -> None:
    """Forget the kept intervals."""
    with _record_lock:
        _record.clear()
