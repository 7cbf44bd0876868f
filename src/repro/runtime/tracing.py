"""Program spans and counters, on the profiler's clock.

The device sweep and the perf-database build mark their own layers::

    with tracing.span("sweep.interval", interval=i):
        ...
    tracing.count("xfer.d2h_bytes", host.nbytes)

A span or counter is *active* only while a profiler session records
(``jax.profiler.start_trace``) or inside :func:`recording`. Then a span
writes a ``jax.profiler.TraceAnnotation`` of its name (the ids become the
event's arguments), so it lands in the profiler's trace on the same clock
as the device ops, and adds its duration, self time (duration less the
time of the spans opened inside it) and call count to an in-memory table
that :func:`snapshot` returns. Inactive, :func:`span` returns a shared
no-op context after one flag check, and :func:`count` does nothing.

The table holds host timings only: nothing it returns may feed a
simulated value, and no span may sit inside a jitted function. Spans nest
on one thread per process. Importing this module does not import JAX.
"""

from __future__ import annotations

import contextlib
import functools
import sys
import time
from collections import defaultdict

_NOOP = contextlib.nullcontext()
_recording = 0  # depth of open recording() blocks
_profiler_is_enabled = None  # TraceAnnotation.is_enabled, once JAX is loaded
_open: list = []  # spans entered and not yet left, innermost last
_spans: dict = defaultdict(lambda: [0.0, 0.0, 0])  # name -> [s, self s, calls]
_counters: dict = defaultdict(int)


def profiling() -> bool:
    """Whether a profiler session records in this process (jaxlib's
    ``TraceMe.is_enabled()``; False while JAX is not even loaded)."""
    global _profiler_is_enabled
    if _profiler_is_enabled is None:
        if "jax" not in sys.modules:
            return False
        import jax.profiler

        _profiler_is_enabled = jax.profiler.TraceAnnotation.is_enabled
    return _profiler_is_enabled()


def active() -> bool:
    """Whether spans and counters record now."""
    return bool(_recording) or profiling()


class _Span:
    __slots__ = ("name", "annotation", "child_s", "t0")

    def __init__(self, name: str, ids: dict) -> None:
        self.name = name
        self.annotation = None
        if profiling():
            import jax.profiler

            self.annotation = jax.profiler.TraceAnnotation(name, **ids)

    def __enter__(self) -> "_Span":
        if self.annotation is not None:
            self.annotation.__enter__()
        self.child_s = 0.0
        _open.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self.t0
        _open.pop()
        if _open:
            _open[-1].child_s += dt
        row = _spans[self.name]
        row[0] += dt
        row[1] += dt - self.child_s
        row[2] += 1
        if self.annotation is not None:
            self.annotation.__exit__(*exc)


def span(name: str, **ids):
    """A context manager timing the block as ``name``; ``ids`` (say
    ``interval=i``) tie one block's events together in the trace."""
    if not active():
        return _NOOP
    return _Span(name, ids)


def traced(name: str):
    """Decorator: the whole call of the function is the span ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return call

    return wrap


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while active."""
    if active():
        _counters[name] += int(n)


@contextlib.contextmanager
def recording():
    """Record spans and counters without a profiler session."""
    global _recording
    _recording += 1
    try:
        yield
    finally:
        _recording -= 1


def snapshot() -> dict:
    """``{"spans": {name: {"seconds", "self_seconds", "calls"}},
    "counters": {name: int}}`` of everything recorded since :func:`reset`."""
    return {
        "spans": {k: {"seconds": s, "self_seconds": own, "calls": n}
                  for k, (s, own, n) in _spans.items()},
        "counters": dict(_counters),
    }


def reset() -> None:
    """Forget every recorded span and counter."""
    _spans.clear()
    _counters.clear()
