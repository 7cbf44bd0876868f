"""Host milliseconds of per-size accounting per simulated interval.

The self time of the program's ``interval.account`` span (the counter
commit to the slice pools, the profilers and the cost model of every
size; the pull of the sums nested in it is ``device_wait_ms``), over its
``sweep.intervals`` counter (``repro.runtime.tracing``). Nothing where
the program has no such span."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own spans
        return None
    snap = tracing.snapshot()
    n = snap["counters"].get("sweep.intervals")
    span = snap["spans"].get("interval.account")
    return 1e3 * span["self_seconds"] / n if n and span else None
