"""Plain reference of one perf-database record (paper Sections 3.2-3.3).

A record is the micro-benchmark that reproduces a configuration vector,
run at every size of the database's fast-memory vector; its curve is the
total simulated time at each size. The traffic gives vectors at the
micro-benchmark size, which the build admits unscaled; a vector over
``max_rss_pages`` is refused. The micro-benchmark inverts the paper's
Eqs. 1-4 into a page layout: a hot set that stays fast, a warm set just
under the promotion threshold in the slow tier, a churn set promoted each
interval and demoted the next, and a graded warm tail rotating through
the cold filler. At full size the record runs the variant with nothing
bound to the slow tier.

Nothing here comes from the program: the generator is written out from
the paper's equations and the layout above, and the simulation is
:mod:`bench.reference.tiering`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from bench.reference import tiering


@dataclass
class Interval:
    pages: np.ndarray
    counts: np.ndarray
    ops: float
    rand_frac: float = 1.0
    touches: np.ndarray | None = None

    def __post_init__(self) -> None:
        if self.touches is None:
            self.touches = self.counts


@dataclass
class Trace:
    rss_pages: int
    num_threads: int
    slow_pages: np.ndarray | None
    intervals: list = field(default_factory=list)

    def __iter__(self):
        return iter(self.intervals)


def microbench(cv: dict, n_intervals: int, warmup: int = 2) -> Trace:
    """The micro-benchmark trace of a configuration vector."""
    hot_thr = max(2, int(round(cv["hot_thr"])))
    pm_pr = max(0, int(round(cv["pm_pr"])))
    pm_de = max(0, int(round(cv["pm_de"])))
    tail_pages = max(0, int(round(cv.get("warm_pages", 0.0))))
    tail_total = max(0.0, float(cv.get("warm_touches", 0.0)))
    tail_touches = max(1, int(round(tail_total / tail_pages))) if tail_pages else 1
    tail_touches = min(tail_touches, hot_thr - 1)
    pacc_f = max(0.0, cv["pacc_f"] - pm_de * 1 - tail_total)  # Eq. 1
    pacc_s = max(0.0, cv["pacc_s"] - pm_pr * hot_thr)  # Eq. 2
    np_fast = int(pacc_f // hot_thr)  # Eq. 3
    np_slow = int(pacc_s // (hot_thr - 1))  # Eq. 4
    rss = max(int(round(cv["rss_pages"])), np_fast + tail_pages + np_slow + 4 * max(pm_pr, pm_de, 1))
    ai = float(cv["ai"])
    intensity = float(cv.get("intensity", 1.0))

    # page ids: [hot | warm | churn | cold filler with the rotating tail]
    hot = np.arange(0, np_fast, dtype=np.int64)
    warm = np.arange(np_fast, np_fast + np_slow, dtype=np.int64)
    churn_lo = np_fast + np_slow
    churn_want = max(pm_pr * (n_intervals + 1), pm_pr + pm_de, 1)
    churn_len = int(np.clip(churn_want, 1, max(1, (rss - churn_lo) // 2)))
    filler_lo = min(rss, churn_lo + churn_len)
    tail_len = max(1, rss - filler_lo)
    trace = Trace(rss, max(1, int(round(cv["num_threads"]))),
                  np.arange(np_fast, filler_lo, dtype=np.int64))
    # initialization: every page touched once, over the warm-up intervals
    per = math.ceil(rss / max(warmup, 1))
    for w in range(warmup):
        chunk = np.arange(w * per, min(rss, (w + 1) * per), dtype=np.int64)
        if chunk.size:
            trace.intervals.append(Interval(chunk, np.ones_like(chunk), ai * chunk.size))
    cursor = tail_cursor = 0
    prev = np.empty(0, dtype=np.int64)
    for _ in range(n_intervals):
        pages, touches = [], []
        if hot.size:
            pages.append(hot)
            touches.append(np.full(hot.size, hot_thr, dtype=np.int64))
        if tail_pages > 0:
            t = (tail_cursor + np.arange(min(tail_pages, tail_len))) % tail_len
            tail_cursor = (tail_cursor + tail_pages) % tail_len
            pages.append(filler_lo + t)
            touches.append(np.full(t.size, tail_touches, dtype=np.int64))
        if warm.size:
            pages.append(warm)
            touches.append(np.full(warm.size, hot_thr - 1, dtype=np.int64))
        if pm_pr > 0:
            promo = churn_lo + (cursor + np.arange(pm_pr)) % churn_len
            cursor = (cursor + pm_pr) % churn_len
            pages.append(promo)
            touches.append(np.full(promo.size, hot_thr, dtype=np.int64))
        else:
            promo = np.empty(0, dtype=np.int64)
        if prev.size:
            pages.append(prev)
            touches.append(np.ones(prev.size, dtype=np.int64))
        prev = promo
        p = np.concatenate(pages) if pages else np.empty(0, np.int64)
        t = np.concatenate(touches) if touches else np.empty(0, np.int64)
        c = np.maximum(1, np.rint(t * intensity)).astype(np.int64)
        trace.intervals.append(Interval(p, c, ai * t.sum(), touches=t))
    return trace


def record_curve(cv: dict, fm_fracs, hw, hot_thr: int, n_intervals: int,
                 max_rss_pages: int, dtype=np.float64) -> np.ndarray:
    """Total simulated time of the record at every size of ``fm_fracs``,
    under TPP with promotion threshold ``hot_thr``."""
    if cv["rss_pages"] > max_rss_pages:
        raise ValueError(f"vector of {cv['rss_pages']} pages over the build's "
                         f"max_rss_pages {max_rss_pages}: the traffic must give "
                         "vectors at the micro-benchmark size")
    tr = microbench(cv, n_intervals)
    fr = np.asarray(fm_fracs, dtype=np.float64)
    full = fr >= 1.0 - 1e-9
    out = np.empty(fr.size, dtype=np.float64)
    if full.any():
        fast_only = Trace(tr.rss_pages, tr.num_threads, None, tr.intervals)
        r = tiering.simulate(fast_only, fr[full], hw, hot_thr, dtype=dtype)
        out[full] = [np.sum(row) for row in r["times"]]
    if (~full).any():
        r = tiering.simulate(tr, fr[~full], hw, hot_thr, dtype=dtype)
        out[~full] = [np.sum(row) for row in r["times"]]
    return out
