"""Check the recorded operating points against an independent harvest.

    python3 bench/harvest_check.py

``bench/traffic/perfdb_points.json`` holds operating points that the
program's numpy sweep harvested from the paper's workloads at their
default sizes. This command harvests them again without the program's
simulator or profiler: it runs each workload's trace (the repository's
workload generators define the workloads) through the plain reference
:mod:`bench.reference.tiering` at the probe sizes, profiles each interval
from the configuration vector's definitions (touches capped at the
threshold on each tier after allocation and before migration; the
interval's migrations; operations per capped touch; cache lines per
capped touch; fast pages touched below the threshold and their touches;
allocated pages), keeps the steady intervals (the first 3 and those under
500 capped touches dropped), and reports how many recorded vectors it
finds among them. Runs on the host alone, in about a minute; exits
non-zero where a recorded vector is not found.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import generate  # noqa: E402
from bench.reference import tiering as ref  # noqa: E402

KEYS = ("pacc_f", "pacc_s", "pm_de", "pm_pr", "ai", "rss_pages", "hot_thr",
        "num_threads", "intensity", "warm_pages", "warm_touches")
SKIP, MIN_ACCESSES = 3, 500


def harvest(trace, fracs, hw, hot_thr: int = 4, halflife: float = 2.0) -> list:
    """Per size, the configuration vector of every interval."""
    num = int(trace.rss_pages)
    pools = [ref.Pool(num, num, int(round(f * num))) for f in fracs]
    heat = np.zeros(num)
    touch = np.zeros(num, dtype=np.int64)
    decay = 0.5 ** (1.0 / halflife)
    rss = [0] * len(pools)
    out = [[] for _ in pools]
    for ia in trace:
        pages = np.asarray(ia.pages, dtype=np.int64)
        touches = np.asarray(ia.touches, dtype=np.int64)
        lines = int(ref.absorb_cache(np.asarray(ia.counts, dtype=np.int64), hw.llc_pages).sum())
        rep = np.minimum(touches, hot_thr)
        seen = []
        for s, p in enumerate(pools):
            new = pages[p.tier[pages] == ref.UNALLOC]
            if new.size:
                n_fast = min(max(0, p.free - p.low), new.size)
                p.tier[new[:n_fast]] = ref.FAST
                p.tier[new[n_fast:]] = ref.SLOW
                p.fast += n_fast
                rss[s] += new.size
            fast = p.tier[pages] == ref.FAST
            warm = fast & (rep < hot_thr)
            seen.append((int(rep[fast].sum()), int(rep[~fast].sum()),
                         int(warm.sum()), int(rep[warm].sum())))
        touch[pages] += touches
        cached = []

        def ranking():
            if not cached:
                cached.append(np.argsort(heat * decay + touch, kind="stable"))
            return cached[0]

        acc = touch[pages]
        for s, p in enumerate(pools):
            m = (p.tier[pages] == ref.SLOW) & (acc >= hot_thr)
            pr, de, _, _ = p.tpp_step(pages[m][np.argsort(-acc[m], kind="stable")], ranking)
            pf, ps, wp, wt = seen[s]
            a = pf + ps
            out[s].append({
                "pacc_f": pf, "pacc_s": ps, "pm_de": de, "pm_pr": pr,
                "ai": ia.ops / a if a else 0.0, "rss_pages": rss[s], "hot_thr": hot_thr,
                "num_threads": trace.num_threads, "intensity": max(1.0, lines / max(a, 1)),
                "warm_pages": wp, "warm_touches": wt,
            })
        heat = heat * decay + touch
        touch[:] = 0
    return out


def same(a: dict, b: dict) -> bool:
    return all(abs(a[k] - b[k]) <= 1e-9 * max(1.0, abs(b[k])) for k in KEYS)


def main() -> int:
    from repro.sim.costmodel import OPTANE_LIKE
    from repro.sim.workloads import WORKLOADS

    hw = ref.Hardware(**dataclasses.asdict(OPTANE_LIKE))
    points = generate.load("traffic", "perfdb_points")
    missing = 0
    for name in generate.load("traffic", "build")["workloads"]:
        fracs = [float(f) for f in points[name]["pool"]]
        per_size = harvest(WORKLOADS[name](), fracs, hw)
        found = total = 0
        for f, vecs in zip(fracs, per_size):
            steady = [v for v in vecs[SKIP:] if v["pacc_f"] + v["pacc_s"] >= MIN_ACCESSES]
            for rec in points[name]["pool"][str(f)]:
                total += 1
                found += any(same(v, rec) for v in steady)
        missing += total - found
        print(json.dumps({"workload": name, "recorded": total, "found": found}), flush=True)
    return 1 if missing else 0


if __name__ == "__main__":
    sys.exit(main())
