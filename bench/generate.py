"""The benchmark's traffic generator: one reader for every traffic mix.

A cell's inputs come from two data files: the configuration
(``bench/configs/<config>.json``: what is simulated) and the traffic mix
(``bench/traffic/<mix>.json``: which sizes, how long a trace, which
records). This module turns them, with ``--seed``, into what the program
is given: a page-access trace for a sweep, or the configuration vectors
of a perf-database build.

The GUPS generator follows the HPC Challenge RandomAccess rules (a table
of 2^n words, 4 x 2^n updates at uniformly random words), drawn per
interval as page counts. The perf-database vectors are operating points
the paper's workloads reach (recorded once in
``bench/traffic/perfdb_points.json``) with ``build_bench_db``'s
multiplicative jitter, scaled to the configuration's micro-benchmark
size; ``--seed`` draws their arithmetic-intensity jitter.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ELEM_BYTES = 8


def load(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``; the harness finds every file by name."""
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def rng_for(seed: int, purpose: int) -> np.random.Generator:
    """An independent stream per purpose, from any whole-number seed."""
    return np.random.default_rng([int(seed) % 2**64, purpose])


def fm_fracs(traffic: dict) -> tuple:
    """The traffic's fast-memory size vector, largest first."""
    hi, lo, step = traffic["fm_from"], traffic["fm_to"], traffic["fm_step"]
    n = int(round((hi - lo) / step)) + 1
    return tuple(float(round(hi - i * step, 3)) for i in range(n))


def gups_trace(cfg: dict, n_intervals: int, seed: int):
    """The configuration's HPCC RandomAccess (GUPS) trace: the table's
    initialisation pass, then ``n_intervals`` intervals of the update loop.

    The table holds ``table_words`` 8-byte words; the run makes
    ``updates_per_word`` x ``table_words`` updates, each a read-modify-write
    of one word drawn uniformly at random, split evenly over
    ``intervals_per_run`` intervals. Per interval the count of updates that
    land in each page is drawn at once (a multinomial over the pages, which
    is how uniform random words fall into equal pages); each update is one
    cache-line access and one touch, and ``ops_per_update`` integer
    operations.
    """
    from repro.core.trace import IntervalAccess, Trace

    words = int(cfg["table_words"])
    page_bytes = int(cfg["page_bytes"])
    rss = words * ELEM_BYTES // page_bytes
    updates = words * int(cfg["updates_per_word"]) // int(cfg["intervals_per_run"])
    rng = np.random.default_rng(int(seed) % 2**64)
    trace = Trace(name="gups", rss_pages=rss, num_threads=int(cfg["num_threads"]))
    # initialisation: Table[i] = i, a sequential scan, 64 cache lines and
    # one touch per page, one store per word
    trace.append(IntervalAccess(
        pages=np.arange(rss, dtype=np.int64),
        counts=np.full(rss, page_bytes // 64, dtype=np.int64),
        ops=float(words), rand_frac=0.0, touches=np.ones(rss, dtype=np.int64),
    ))
    uniform = np.full(rss, 1.0 / rss)
    for _ in range(n_intervals):
        counts = rng.multinomial(updates, uniform).astype(np.int64)
        pages = np.flatnonzero(counts)
        c = counts[pages]
        trace.append(IntervalAccess(
            pages=pages, counts=c, ops=float(cfg["ops_per_update"]) * updates,
            rand_frac=1.0, touches=c.copy(),
        ))
    return trace


def perfdb_vectors(cfg: dict, traffic: dict, seed: int) -> list:
    """The build's configuration vectors for ``seed``, in the cycle's order.

    The make-up and the order are the traffic's, the same for every seed,
    so that every run does the same work (a window holds about one cycle
    of records of unequal cost, so another order would change what it
    completes): for each workload one operating point from each stratum of
    probe sizes, with ``build_bench_db``'s jitter of the accesses and
    migrations, drawn by the traffic's ``set_seed``. The seed draws what
    does not change the work: each vector's arithmetic-intensity jitter
    (``build_bench_db``'s), which moves every interval's time and so every
    curve. Each vector is
    scaled uniformly to the configuration's micro-benchmark size
    ``rss_pages``: the extensive quantities (accesses, migrations, RSS,
    the warm tail) by the ratio of sizes, the intensive ones (arithmetic
    and stride intensity, threshold, threads) kept.
    """
    from repro.core.telemetry import ConfigVector

    points = load("traffic", traffic["points"])
    fixed = np.random.default_rng(int(traffic["set_seed"]))
    rng = rng_for(seed, 3)
    target = float(cfg["rss_pages"])
    lo, hi = traffic["jitter_counts"]
    ai_lo, ai_hi = traffic["jitter_ai"]
    vecs = []
    for name in traffic["workloads"]:
        for stratum in traffic["strata"]:
            pool = [v for f in stratum for v in points[name]["pool"][str(f)]]
            base = pool[int(fixed.integers(len(pool)))]
            lam = target / float(base["rss_pages"])
            v = np.array([base[k] for k in _INDEX], dtype=np.float64)
            v[:4] *= fixed.uniform(lo, hi, size=4) * lam  # pacc / pm, jittered
            v[4] *= rng.uniform(ai_lo, ai_hi)  # AI jitter, from the seed
            v[5] = target
            vecs.append(dataclasses.replace(
                ConfigVector(*v.tolist(), intensity=base["intensity"]),
                warm_pages=base["warm_pages"] * lam, warm_touches=base["warm_touches"] * lam,
            ))
    order = fixed.permutation(len(vecs))
    return [vecs[int(i)] for i in order]


_INDEX = ("pacc_f", "pacc_s", "pm_de", "pm_pr", "ai", "rss_pages", "hot_thr", "num_threads")
