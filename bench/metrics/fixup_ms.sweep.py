"""Host milliseconds of the thrash fix-up per simulated interval.

The program's own ``interval.fixup`` span (``repro.runtime.tracing``):
for every interfering size, the two row pulls, the resolver merge and the
tier patch; over the program's ``sweep.intervals`` counter. Nothing where
the program has no such span."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own spans
        return None
    snap = tracing.snapshot()
    n = snap["counters"].get("sweep.intervals")
    span = snap["spans"].get("interval.fixup")
    return 1e3 * span["seconds"] / n if n and span else None
