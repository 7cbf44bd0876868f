"""Operations and bytes a kernel's problem needs, from its shapes alone.

``demote_rank`` selects, for every fast-memory size and every page in the
shared demotion ranking, whether the page is among that size's victims:
per (size, page) entry the problem reads one byte of tier state and
writes one bit of victim selection, and its running count is one compare
and one add. The count is the problem's, not an implementation's, so no
kernel can read above 100% under it and none can make it stale.
"""

from __future__ import annotations

BYTES_PER_ENTRY = 1 + 1 / 8  # one byte of tier in, one bit of selection out
OPS_PER_ENTRY = 2  # one compare, one add of the running count


def demote_rank_bytes(n_sizes: int, rss_pages: int) -> float:
    return n_sizes * rss_pages * BYTES_PER_ENTRY


def demote_rank_ops(n_sizes: int, rss_pages: int) -> float:
    return n_sizes * rss_pages * OPS_PER_ENTRY


def demote_rank_least_s(n_sizes: int, rss_pages: int, peaks: dict) -> tuple[float, str]:
    """The least time one call can take on the device, and what bounds it."""
    t_mem = demote_rank_bytes(n_sizes, rss_pages) / peaks["hbm_bytes_per_s"]
    t_ops = demote_rank_ops(n_sizes, rss_pages) / peaks["int8_ops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "int8_ops")
