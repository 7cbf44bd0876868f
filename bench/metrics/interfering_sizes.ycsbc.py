"""Interfering sizes per simulated interval: the resolver calls.

The program's ``sweep.interfering_sizes`` counter over its
``sweep.intervals`` counter (``repro.runtime.tracing``); 0 where no size
interfered, which a YCSB cell's hot set, small beside every size, should
keep it at. Nothing where the program has no such counters."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own counters
        return None
    counters = tracing.snapshot()["counters"]
    n = counters.get("sweep.intervals")
    return counters.get("sweep.interfering_sizes", 0) / n if n else None
