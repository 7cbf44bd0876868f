"""Interfering sizes per simulated interval: the resolver calls.

The program's ``sweep.interfering_sizes`` counter over its
``sweep.intervals`` counter (``repro.runtime.tracing``); the base of
``fixup_ms.sweep``. Nothing where the program has no such counters."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own counters
        return None
    counters = tracing.snapshot()["counters"]
    n = counters.get("sweep.intervals")
    sizes = counters.get("sweep.interfering_sizes")
    return sizes / n if n and sizes is not None else None
