"""Host spans around the program's module-level functions, for traced runs.

The benchmark does not edit the program: it replaces a module attribute
(``repro.sim.jax_engine._resolve_step_victims``, say) with a wrapper that
times each call on the host clock and writes it into the profiler trace as
a ``jax.profiler.TraceAnnotation`` of the span's name, so that idle gaps
on the device can be attributed to what the host was doing. The program
looks these names up at call time, so the wrapper takes effect at once;
:meth:`Spans.remove` puts the originals back.
"""

from __future__ import annotations

import importlib
import time
from collections import Counter


class Spans:
    """Wraps ``(module, attribute, span)`` triples while installed."""

    def __init__(self, wraps) -> None:
        self.wraps = list(wraps)
        self.seconds: Counter = Counter()
        self.calls: Counter = Counter()
        self._saved: list = []

    def _wrap(self, fn, span: str):
        import jax

        annotate = jax.profiler.TraceAnnotation
        seconds, calls = self.seconds, self.calls

        def wrapped(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with annotate(span):
                    return fn(*args, **kwargs)
            finally:
                seconds[span] += time.perf_counter() - t0
                calls[span] += 1

        wrapped.__wrapped__ = fn
        return wrapped

    def install(self) -> "Spans":
        for module, attr, span in self.wraps:
            mod = importlib.import_module(module)
            orig = getattr(mod, attr)
            self._saved.append((mod, attr, orig))
            setattr(mod, attr, self._wrap(orig, span))
        return self

    def remove(self) -> None:
        while self._saved:
            mod, attr, orig = self._saved.pop()
            setattr(mod, attr, orig)
