"""Driver of a perf-database cell: records built back to back.

The window calls the program's own offline-modeling entry point,
``repro.core.tuner.build_database([vector], engine="jax")``, once per
record, cycling through the traffic's vectors in the traffic's order, and
stops at the first record boundary after ``--seconds``. A record is one
micro-benchmark's whole curve over the size vector (two device sweeps:
the full size on the variant with nothing bound to the slow tier, and
the other sizes together); each ends on the pull of the final tier state.

The seed draws each vector's arithmetic-intensity jitter
(:func:`bench.generate.perfdb_vectors`); the make-up is the traffic's.
Set-up builds a short record of each vector of the set (the traffic's
``warm_intervals`` instead of the configured interval count: the same
step shapes and the same migrating paths), so the window compiles
nothing. ``correct`` compares the whole curve of records drawn from the
seed against the plain reference.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import generate
from bench.reference import microbench
from bench.reference import tiering as ref

SPANS = (("repro.core.tuner", "_microbench_trace", "microbench_gen"),)


class Cell:
    """One perf-database cell: set-up, the measured window and the
    comparison."""

    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.fracs = generate.fm_fracs(cfg)
        self.spans = SPANS
        self.info: dict = {}

    def _record(self, cv, n_intervals=None):
        from repro.core.tuner import build_database

        db = build_database(
            [cv], fm_fracs=self.fracs, n_intervals=n_intervals or self.cfg["n_intervals"],
            max_rss_pages=self.cfg["max_rss_pages"], engine=self.cfg["engine"],
        )
        return np.asarray(db.records[0].times)

    def setup(self) -> None:
        t0 = time.perf_counter()
        self.vectors = generate.perfdb_vectors(self.cfg, self.traffic, self.seed)
        self.info["vectors"] = len(self.vectors)
        self.info["vector_generation_s"] = time.perf_counter() - t0
        # a short record of each vector: which step programs a record needs
        # (the commit step, the thrash fix-up) depends on its migrations,
        # not only on its shapes, so every vector of the set is warmed
        for cv in self.vectors:
            self._record(cv, int(self.traffic["warm_intervals"]))
        self.info["warm_records"] = len(self.vectors)

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        self.records = []
        while time.perf_counter() < deadline:
            cv = self.vectors[len(self.records) % len(self.vectors)]
            self.records.append((cv, self._record(cv)))
        self.elapsed = time.perf_counter() - t0
        n = len(self.records)
        self.info["records_completed"] = n
        return {
            "attempted": n,
            "metrics": {"db_records_per_s": n / self.elapsed},
            "work": {"records": n},
        }

    def release(self) -> None:
        pass

    def compared_records(self) -> list:
        rng = generate.rng_for(self.seed, 2)
        n = len(self.records)
        k = min(n, int(self.traffic["compare_records"]))
        return sorted(int(i) for i in rng.choice(n, size=k, replace=False))

    def reference(self, cv, dtype=np.float64) -> np.ndarray:
        return microbench.record_curve(
            dataclasses.asdict(cv), self.fracs, ref.Hardware(**self.cfg["hw"]),
            self.cfg["hot_thr"], self.cfg["n_intervals"], self.cfg["max_rss_pages"],
            dtype=dtype,
        )

    def check(self) -> dict:
        """Compared numbers, each ``(value, limit)``."""
        idx = self.compared_records()
        got = [self.records[i][1] for i in idx]
        want = [self.reference(self.records[i][0]) for i in idx]
        compared, self.failed = compare(got, want)
        self.info["compared_records"] = idx
        return compared


def compare(got: list, want: list) -> tuple[dict, int]:
    """Each compared record's whole curve, exactly; returns the compared
    numbers with their limits and the number of records that differ."""
    gap = 0.0
    bad_sizes = failed = 0
    for g_curve, w_curve in zip(got, want):
        g_curve = np.asarray(g_curve, dtype=np.float64)
        if g_curve.shape != w_curve.shape:
            g, b = 1.0, w_curve.size
        else:
            b = int(np.count_nonzero(g_curve != w_curve))
            g = float(np.max(np.abs(g_curve - w_curve) / w_curve))
        gap = max(gap, g)
        bad_sizes += b
        failed += bool(b or g)
    return {
        "curve_sizes_mismatched": (bad_sizes, 0),
        "curve_rel_gap": (gap, 0.0),
    }, failed
