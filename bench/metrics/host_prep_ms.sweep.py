"""Host milliseconds preparing each simulated interval's schedule step.

The program's ``interval.prep`` span (allocation bookkeeping, the
interval's hot order and admission, the watermark arrays, padding), over
its ``sweep.intervals`` counter (``repro.runtime.tracing``). Nothing
where the program has no such span."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own spans
        return None
    snap = tracing.snapshot()
    n = snap["counters"].get("sweep.intervals")
    span = snap["spans"].get("interval.prep")
    return 1e3 * span["seconds"] / n if n and span else None
