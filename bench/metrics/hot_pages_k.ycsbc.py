"""Thousands of hot pages (touched at least ``hot_thr`` times) per
simulated interval: the promotion candidates the schedule step filters.

The program's ``interval.hot_pages`` counter over its ``sweep.intervals``
counter (``repro.runtime.tracing``). Nothing where the program has no
such counter."""


def read(ctx):
    try:
        from repro.runtime import tracing
    except ImportError:  # a program without its own counters
        return None
    counters = tracing.snapshot()["counters"]
    n = counters.get("sweep.intervals")
    hot = counters.get("interval.hot_pages")
    return 1e-3 * hot / n if n and hot is not None else None
