"""Host milliseconds of the demotion ranking per simulated interval.

The program's own ``interval.rank`` span (``GlobalDemoteRank``, the tie
groups and the rank arrays handed to the commit step), over its
``sweep.intervals`` counter (``repro.runtime.tracing``). Nothing where
the program has no such span."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own spans
        return None
    snap = tracing.snapshot()
    n = snap["counters"].get("sweep.intervals")
    span = snap["spans"].get("interval.rank")
    return 1e3 * span["seconds"] / n if n and span else None
