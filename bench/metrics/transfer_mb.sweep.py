"""Megabytes (10^6 bytes) between host and device per simulated interval.

The program's ``xfer.h2d_bytes`` (host arrays handed to the device steps
and the fix-up) and ``xfer.d2h_bytes`` (device arrays pulled) counters,
once-per-sweep tier copies included, over its ``sweep.intervals`` counter
(``repro.runtime.tracing``). Nothing where the program has no such
counters."""

COUNTERS = ("xfer.h2d_bytes", "xfer.d2h_bytes")


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own counters
        return None
    counters = tracing.snapshot()["counters"]
    n = counters.get("sweep.intervals")
    found = [counters[k] for k in COUNTERS if k in counters]
    return 1e-6 * sum(found) / n if n and found else None
