"""Host milliseconds of per-interval preparation and accounting.

The program's ``interval.prep`` span plus the self time of its
``interval.account`` span (the pull of the sums nested in it is
``device_wait_ms``), over its ``sweep.intervals`` counter: the per-size
Python loops that set the pace where the arrays are small
(``repro.runtime.tracing``). Nothing where the program has no such
spans."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own spans
        return None
    snap = tracing.snapshot()
    n = snap["counters"].get("sweep.intervals")
    prep = snap["spans"].get("interval.prep")
    account = snap["spans"].get("interval.account")
    if not n or prep is None or account is None:
        return None
    return 1e3 * (prep["seconds"] + account["self_seconds"]) / n
