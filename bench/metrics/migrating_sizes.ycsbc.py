"""Sizes that promote or demote per simulated interval.

The program's ``sweep.migrating_sizes`` counter (sizes with
``pm_pr + pm_de > 0`` in the interval's schedule) over its
``sweep.intervals`` counter (``repro.runtime.tracing``). Nothing where
the program has no such counter."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own counters
        return None
    counters = tracing.snapshot()["counters"]
    n = counters.get("sweep.intervals")
    sizes = counters.get("sweep.migrating_sizes")
    return sizes / n if n and sizes is not None else None
