"""The ``demote_rank`` kernel's share of its roofline, in percent.

As ``demote_rank_roofline.sweep``: the least time of all its calls in the
window (bytes and operations from ``(n_sizes, rss_pages)`` alone,
:mod:`bench.roofline`, over the device's peaks) divided by the kernel's
device time in the trace, the op named after its ``pallas_call`` wrapper
or its body. In a YCSB cell the kernel selects real victims: a ranking
with heat differences and a large tie group of untouched pages. Nothing
is returned where the trace holds no kernel event; where the commit step
ran but no kernel event was found, that is said on standard error."""

import sys

from bench.roofline import demote_rank_least_s

KEYS = ("_victim_partition_pallas", "_victim_partition_kernel")


def read(ctx):
    if ctx.trace is None:
        return None
    us, n = ctx.trace.time_of(KEYS, table="ops")
    if not n or us <= 0:
        if ctx.trace.time_of("commit_step")[1]:
            print(f"demote_rank_roofline.ycsbc: the commit step ran but no op named "
                  f"{' or '.join(KEYS)} is in the trace", file=sys.stderr, flush=True)
        return None
    w = ctx.window["work"]
    least, _ = demote_rank_least_s(w["n_sizes"], w["rss_pages"], ctx.peaks)
    return 100.0 * n * least / (us * 1e-6)
