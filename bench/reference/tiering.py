"""Plain reference of the simulated tiering system: TPP on a two-tier pool.

What a cell's timed path computes, written out straight from the
semantics, with nothing taken from the program: per fast-memory size a
pool of pages (unallocated, fast or slow) under watermarks; first-touch
allocation into the fast tier down to the low watermark; TPP promotion of
slow pages touched ``hot_thr`` times in the interval, hottest first,
interleaved with watermark reclaim that demotes the coldest fast pages by
effective heat (decayed history plus this interval's touches, ties by page
id); and the interval cost model. Every size runs the same sequence of
reclaim calls as the seed's dense-scan pool, one call at a time, so a
page promoted early in an interval can be demoted later in it.

Heat does not depend on the size, so the sizes of one run share the heat
array and the interval's demotion ranking (one stable argsort per
interval), which keeps the reference affordable at millions of pages.

``dtype`` is the precision of the heat and of the cost arithmetic:
``float64`` is what the configurations state; ``float32`` is the control,
the same reference one precision lower, which the comparison must reject.
"""

from __future__ import annotations

import numpy as np

UNALLOC, FAST, SLOW = -1, 0, 1
STATS = (
    "pgpromote_success", "pgpromote_fail", "pgdemote_kswapd",
    "pgdemote_direct", "direct_reclaim_events", "alloc_fast", "alloc_slow",
)


class Hardware:
    """The interval cost model's parameters (a configuration's ``hw``)."""

    def __init__(self, **kw) -> None:
        self.__dict__.update(kw)


def absorb_cache(counts: np.ndarray, llc_pages: int, cl_per_page: int = 64) -> np.ndarray:
    """The hottest ``llc_pages`` pages cost at most one fetch per line."""
    if llc_pages <= 0 or counts.size <= llc_pages:
        return np.minimum(counts, cl_per_page) if llc_pages > 0 else counts
    kth = np.partition(counts, counts.size - llc_pages)[counts.size - llc_pages]
    out = counts.copy()
    hot = counts >= kth
    out[hot] = np.minimum(counts[hot], cl_per_page)
    return out


def effective_mlp(counts: np.ndarray, hw_mlp: float, threads: int, F=float):
    """Memory-level parallelism bounded by the participation ratio."""
    if counts.size == 0:
        return F(hw_mlp) * F(threads)
    s1 = F(counts.sum())
    s2 = F(np.square(counts, dtype=np.float64 if F is float else F).sum())
    pr = (s1 * s1) / s2 if s2 > 0 else F(1.0)
    return min(F(hw_mlp) * F(threads), max(F(1.0), pr))


def interval_time(hw: Hardware, pacc_f, pacc_s, ops, pm_pr, pm_de, pm_fail,
                  direct, mlp_eff, threads, rand_frac, F=float):
    """Seconds one interval costs: the roofline max of compute and the two
    tiers' memory time, plus migration overhead and blocking stalls."""
    threads = max(1, threads)
    t_compute = F(ops) / (F(hw.ops_per_s) * F(threads))
    mig_bytes = F((pm_pr + pm_de) * hw.page_bytes)
    bytes_fast = F(pacc_f) * F(hw.access_bytes) + mig_bytes
    bytes_slow = F(pacc_s) * F(hw.access_bytes) + mig_bytes
    t_fast = max(bytes_fast / F(hw.bw_fast),
                 F(pacc_f) * F(rand_frac) * F(hw.lat_fast) / mlp_eff)
    t_slow = max(bytes_slow / F(hw.bw_slow),
                 F(pacc_s) * F(rand_frac) * F(hw.lat_slow) / mlp_eff)
    t_migrate = F(pm_pr + pm_de) * F(hw.migrate_page_overhead) / F(threads)
    t_stall = (F(direct) * F(hw.direct_reclaim_stall)
               + F(pm_fail) * F(hw.promote_fail_penalty))
    serial = F(hw.cross_tier_serial)
    t_mem = max(t_fast, t_slow) + serial * min(t_fast, t_slow)
    return max(t_compute, t_mem) + t_migrate + t_stall


class Pool:
    """One fast-memory size: tiers, watermarks and counters."""

    def __init__(self, num_pages: int, cap: int, fm_pages: int) -> None:
        self.cap = cap
        self.tier = np.full(num_pages, UNALLOC, dtype=np.int8)
        self.fast = 0
        self.kswapd = max(128, cap // 64)
        fm = int(max(1, min(cap, fm_pages)))
        self.low = cap - fm  # watermarks in free fast pages
        self.high = self.low
        self.min = int(0.8 * self.low)
        self.stats = dict.fromkeys(STATS, 0)

    @property
    def free(self) -> int:
        return self.cap - self.fast

    def demote(self, n: int, ranking, direct: bool = False) -> int:
        """Demote the ``n`` coldest fast pages in the interval's ranking."""
        if n <= 0 or self.fast == 0:
            return 0
        n = min(n, self.fast)
        order = ranking()
        victims = order[self.tier[order] == FAST][:n]
        self.tier[victims] = SLOW
        self.fast -= n
        self.stats["pgdemote_direct" if direct else "pgdemote_kswapd"] += n
        return n

    def reclaim(self, ranking, allow_direct: bool = False) -> tuple[int, int]:
        """kswapd toward the high watermark (rate limited); direct reclaim
        to the min watermark only on the promotion path."""
        bg = direct = 0
        if allow_direct and self.free < self.min:
            direct = self.demote(self.min - self.free, ranking, direct=True)
            self.stats["direct_reclaim_events"] += 1
        if self.free < self.low:
            bg = self.demote(min(self.high - self.free, self.kswapd), ranking)
        return bg, direct

    def tpp_step(self, cand: np.ndarray, ranking) -> tuple[int, int, int, int]:
        """Promote ``cand`` (hottest first) into the headroom above the min
        watermark, reclaiming whenever it runs out; then one kswapd pass.
        Returns ``(promoted, demoted, failed, direct)``."""
        pr = de = fail = direct = 0
        done = 0
        while done < cand.size:
            headroom = max(0, self.free - self.min)
            if headroom == 0:
                bg, d = self.reclaim(ranking, allow_direct=True)
                de += bg + d
                direct += d
                headroom = max(0, self.free - self.min)
                if headroom == 0:
                    fail += cand.size - done
                    break
            chunk = cand[done:done + headroom]
            self.tier[chunk] = FAST
            self.fast += chunk.size
            self.stats["pgpromote_success"] += chunk.size
            pr += chunk.size
            done += chunk.size
        bg, d = self.reclaim(ranking)
        return pr, de + bg + d, fail, direct + d


def simulate(trace, fm_fracs, hw: Hardware, hot_thr: int = 4, halflife: float = 2.0,
             dtype=np.float64, n_intervals: int | None = None) -> dict:
    """Run ``trace`` at every size in ``fm_fracs`` (capacity = RSS).

    Returns, per size, the counters, the interval times and the final
    tier of every page, over the first ``n_intervals`` intervals (all by
    default).
    """
    F = float if dtype == np.float64 else dtype
    num = int(trace.rss_pages)
    cap = num
    pools = [Pool(num, cap, int(round(float(f) * cap))) for f in fm_fracs]
    if trace.slow_pages is not None:
        for p in pools:
            p.tier[trace.slow_pages] = SLOW
    heat = np.zeros(num, dtype=dtype)
    touch = np.zeros(num, dtype=np.int64)
    decay = dtype(0.5 ** (1.0 / halflife))
    intervals = list(trace)[:n_intervals]
    times = np.zeros((len(pools), len(intervals)), dtype=np.float64)
    for i, ia in enumerate(intervals):
        pages = np.asarray(ia.pages, dtype=np.int64)
        touches = np.asarray(ia.touches, dtype=np.int64)
        counts = absorb_cache(np.asarray(ia.counts, dtype=np.int64), hw.llc_pages)
        mlp = effective_mlp(counts, hw.mlp, trace.num_threads, F)
        pacc = []
        for p in pools:
            new = pages[p.tier[pages] == UNALLOC]
            if new.size:
                n_fast = min(max(0, p.free - p.low), new.size)
                p.tier[new[:n_fast]] = FAST
                p.tier[new[n_fast:]] = SLOW
                p.fast += n_fast
                p.stats["alloc_fast"] += n_fast
                p.stats["alloc_slow"] += new.size - n_fast
            t = p.tier[pages]
            pacc.append((int(counts[t == FAST].sum()), int(counts[t == SLOW].sum())))
        touch[pages] += touches
        cached = []

        def ranking():
            if not cached:
                eff = heat * decay + touch.astype(dtype)
                cached.append(np.argsort(eff, kind="stable"))
            return cached[0]

        acc_now = touch[pages]
        for s, p in enumerate(pools):
            m = (p.tier[pages] == SLOW) & (acc_now >= hot_thr)
            cand = pages[m][np.argsort(-acc_now[m], kind="stable")]
            pr, de, fail, direct = p.tpp_step(cand, ranking)
            times[s, i] = interval_time(
                hw, pacc[s][0], pacc[s][1], ia.ops, pr, de, fail, direct, mlp,
                trace.num_threads, ia.rand_frac, F,
            )
        heat = heat * decay + touch.astype(dtype)
        touch[:] = 0
    return {
        "stats": [p.stats for p in pools],
        "times": times,
        "tiers": [p.tier for p in pools],
    }
