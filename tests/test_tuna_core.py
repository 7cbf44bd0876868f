"""Tests for Tuna's core: micro-benchmark fidelity (Eqs. 1-4), the
performance database (HNSW recall, persistence), and the tuner loop."""

import numpy as np
import pytest

from repro.core import (
    ConfigVector,
    PerfDB,
    PerfRecord,
    TunaTuner,
    TunerConfig,
    WatermarkController,
    generate_microbench,
)
from repro.core.microbench import spec_from_config
from repro.core.tuner import build_database
from repro.sim.engine import _simulate, run_trace


def mk_cv(pacc_f=200000, pacc_s=4000, pm=60, ai=6.0, rss=60000, hot_thr=4, nt=1):
    return ConfigVector(
        pacc_f=pacc_f, pacc_s=pacc_s, pm_de=pm, pm_pr=pm, ai=ai,
        rss_pages=rss, hot_thr=hot_thr, num_threads=nt,
    )


class TestMicrobenchEquations:
    def test_eq_1_to_4_layout(self):
        cv = mk_cv()
        spec = spec_from_config(cv)
        # Eq.1/3: NP_fast = (pacc_f - pm_de*1) / hot_thr
        assert spec.np_fast == int((cv.pacc_f - cv.pm_de) // cv.hot_thr)
        # Eq.2/4: NP_slow = (pacc_s - pm_pr*hot_thr) / (hot_thr-1)
        assert spec.np_slow == int(
            (cv.pacc_s - cv.pm_pr * cv.hot_thr) // (cv.hot_thr - 1)
        )

    def test_generated_accesses_match(self):
        cv = mk_cv()
        spec = spec_from_config(cv)
        pf, ps = spec.accesses_per_interval()
        # hitting the requested pacc within rounding of Eqs. 3-4
        assert pf == pytest.approx(cv.pacc_f, rel=0.01)
        assert ps == pytest.approx(cv.pacc_s, rel=0.1)

    def test_steady_state_telemetry_reproduces_cv(self):
        """The heart of Section 3.2: running the generated micro-benchmark
        under TPP at the reference size reproduces pacc/pm/AI."""
        cv = mk_cv()
        trace = generate_microbench(cv, n_intervals=12)
        res = _simulate(trace, fm_frac=0.9)
        mid = res.configs[8]  # steady-state interval
        assert mid.pm_pr == pytest.approx(cv.pm_pr, rel=0.1)
        assert mid.pm_de == pytest.approx(cv.pm_de, rel=0.1)
        assert mid.pacc_f == pytest.approx(cv.pacc_f, rel=0.05)
        assert mid.pacc_s == pytest.approx(cv.pacc_s, rel=0.25)
        assert mid.ai == pytest.approx(cv.ai, rel=0.01)

    def test_fast_only_variant_has_no_slow_accesses(self):
        cv = mk_cv()
        trace = generate_microbench(cv, n_intervals=8)
        res = _simulate(trace.fast_only(), fm_frac=1.0)
        assert all(c.pacc_s == 0 for c in res.configs)
        assert res.migrations == 0

    def test_time_monotone_as_fm_shrinks(self):
        cv = mk_cv()
        trace = generate_microbench(cv, n_intervals=8)
        times = [run_trace(trace, f) for f in (0.95, 0.7, 0.45, 0.25)]
        assert times == sorted(times)


class TestPerfDB:
    def _db(self, n=60, seed=0):
        rng = np.random.default_rng(seed)
        db = PerfDB()
        grid = np.round(np.arange(1.0, 0.29, -0.1), 2)
        for _ in range(n):
            cv = mk_cv(
                pacc_f=float(rng.integers(10_000, 500_000)),
                pacc_s=float(rng.integers(100, 20_000)),
                pm=float(rng.integers(0, 500)),
                ai=float(rng.uniform(1, 50)),
                rss=float(rng.integers(10_000, 200_000)),
            )
            base = rng.uniform(0.01, 0.1)
            times = base * (1 + np.linspace(0, rng.uniform(0.1, 2.0), grid.size))
            db.add(PerfRecord(config=cv, fm_fracs=grid, times=times))
        db.build()
        return db

    def test_hnsw_recall_vs_brute(self):
        db = self._db(n=120)
        hits = 0
        rng = np.random.default_rng(1)
        for _ in range(30):
            q = mk_cv(
                pacc_f=float(rng.integers(10_000, 500_000)),
                pacc_s=float(rng.integers(100, 20_000)),
                pm=float(rng.integers(0, 500)),
                ai=float(rng.uniform(1, 50)),
                rss=float(rng.integers(10_000, 200_000)),
            )
            approx = db.query(q, k=3)
            exact = db.query_brute(q, k=3)
            hits += len({id(r) for r in approx} & {id(r) for r in exact})
        assert hits / 90 >= 0.8  # recall@3

    def test_exact_match_returns_itself(self):
        db = self._db(n=60)
        r = db.records[17]
        assert db.query(r.config, k=1)[0] is r

    def test_persistence_roundtrip(self, tmp_path):
        db = self._db(n=20)
        db.save(tmp_path / "perfdb")
        db2 = PerfDB.load(tmp_path / "perfdb")
        assert len(db2.records) == 20
        q = db.records[5].config
        assert np.allclose(
            db2.query(q, k=1)[0].times, db.query(q, k=1)[0].times
        )

    def test_min_fm_within(self):
        grid = np.array([1.0, 0.8, 0.6, 0.4])
        times = np.array([1.0, 1.02, 1.04, 1.5])
        rec = PerfRecord(config=mk_cv(), fm_fracs=grid, times=times)
        assert rec.min_fm_within(0.05) == pytest.approx(0.6)
        assert rec.min_fm_within(0.001) == pytest.approx(1.0)
        assert rec.min_fm_within(-1.0) is None


class TestTunerLoop:
    def test_build_and_tune(self):
        # small offline DB around one operating point
        cvs = [
            mk_cv(pacc_f=f, pacc_s=s, pm=pm, rss=40000)
            for f in (100_000, 150_000)
            for s in (1_000, 4_000)
            for pm in (30, 120)
        ]
        db = build_database(
            cvs, run_trace, fm_fracs=np.arange(1.0, 0.29, -0.1), n_intervals=6
        )
        assert len(db.records) == 8

        from repro.tiering.page_pool import TieredPagePool

        pool = TieredPagePool(num_pages=40000, hw_capacity=40000)
        ctl = WatermarkController(pool, max_step_frac=1.0)
        tuner = TunaTuner(
            db, ctl, TunerConfig(target_loss=0.05), peak_rss_pages=40000
        )
        cv = mk_cv(pacc_f=120_000, pacc_s=2_000, pm=60, rss=40000)
        d = tuner.step(cv, t=0.0)
        assert d.fm_pages <= 40000
        if d.fm_frac is not None:
            assert d.predicted_loss <= 0.05 + 1e-9
            # saved memory only if the DB says it is safe
            assert pool.effective_fm_size == d.fm_pages

    def test_grows_back_when_smaller_sizes_all_violate(self):
        """Paper Section 4 'increasing fast memory size': when every reduced
        size violates τ, the minimum qualifying size is the full size and the
        tuner grows the fast tier back."""
        grid = np.array([1.0, 0.8, 0.6])
        rec = PerfRecord(
            config=mk_cv(), fm_fracs=grid, times=np.array([1.0, 2.0, 3.0])
        )
        db = PerfDB()
        db.add(rec)
        db.build()
        from repro.tiering.page_pool import TieredPagePool

        pool = TieredPagePool(num_pages=1000, hw_capacity=1000)
        pool.set_fm_size(900)
        ctl = WatermarkController(pool, max_step_frac=1.0)
        tuner = TunaTuner(db, ctl, TunerConfig(target_loss=0.05))
        d = tuner.step(mk_cv())
        assert d.fm_frac == pytest.approx(1.0)
        assert pool.effective_fm_size == 1000

    def test_keeps_current_size_on_empty_records(self):
        db = PerfDB()
        db.add(
            PerfRecord(
                config=mk_cv(),
                fm_fracs=np.array([1.0]),
                times=np.array([1.0]),
            )
        )
        db.build()
        from repro.tiering.page_pool import TieredPagePool

        pool = TieredPagePool(num_pages=1000, hw_capacity=1000)
        pool.set_fm_size(700)
        ctl = WatermarkController(pool)
        tuner = TunaTuner(db, ctl, TunerConfig(target_loss=0.05))
        tuner.db.records = []  # degenerate: no records found
        d = tuner._choose([])
        assert d == (None, None)
        assert pool.effective_fm_size == 700


class TestWatermarkController:
    def test_rate_limit_and_deadband(self):
        from repro.tiering.page_pool import TieredPagePool

        pool = TieredPagePool(num_pages=1000, hw_capacity=1000)
        ctl = WatermarkController(pool, max_step_frac=0.1, deadband_frac=0.01)
        # big shrink is rate limited to 10%/call
        got = ctl.set_size(500)
        assert got == 900
        # tiny change inside deadband is ignored
        got2 = ctl.set_size(897)
        assert got2 == 900
