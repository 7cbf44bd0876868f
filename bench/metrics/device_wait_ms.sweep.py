"""Host milliseconds blocked on device-to-host pulls per simulated interval.

The program's ``interval.pull`` spans (the schedule's counters, the
interference flags, the per-size sums) plus its ``fixup.pull`` spans (two
rows per interfering size), over its ``sweep.intervals`` counter
(``repro.runtime.tracing``). A pull waits for the device work before it,
so this is device time as the host sees it plus the copy. Nothing where
the program has no such spans."""

SPANS = ("interval.pull", "fixup.pull")


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own spans
        return None
    snap = tracing.snapshot()
    n = snap["counters"].get("sweep.intervals")
    found = [snap["spans"][k]["seconds"] for k in SPANS if k in snap["spans"]]
    return 1e3 * sum(found) / n if n and found else None
