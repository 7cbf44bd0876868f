"""Device calls per simulated interval.

The program's ``device.dispatches`` counter (calls of the schedule step,
the commit step, the row read and the fix-up patch) over its
``sweep.intervals`` counter (``repro.runtime.tracing``). Nothing where
the program has no such counters."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own counters
        return None
    counters = tracing.snapshot()["counters"]
    n = counters.get("sweep.intervals")
    calls = counters.get("device.dispatches")
    return calls / n if n and calls is not None else None
