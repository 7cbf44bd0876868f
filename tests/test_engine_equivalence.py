"""Equivalence of the optimized engine against the seed implementation.

The incremental pool (O(1) occupancy counters, fast-tier index, lazy heat
decay, bulk policy steps) and the batched fm-size sweep engine are pure
performance work: same-seed simulations must reproduce the seed
implementation's migration counters (``pgpromote_*``, ``pgdemote_*``,
``alloc_*``) and interval times **exactly**, and the batched sweep must
match per-size ``simulate()`` on every fm fraction. The seed implementation
is kept verbatim as :class:`repro.tiering.reference_pool.ReferencePagePool`
for exactly this purpose.
"""

import functools
import heapq

import numpy as np
import pytest

from repro.core.microbench import generate_microbench
from repro.core.perfdb import PerfDB, PerfRecord
from repro.core.telemetry import ConfigVector
from repro.core.trace import IntervalAccess, Trace
from repro.core.tuner import TunaTuner, TunerConfig, build_database, scale_config
from repro.core.watermark import WatermarkController
from repro.runtime import tracing
from repro.sim.engine import run_trace, simulate
from repro.sim.sweep import TunedSlice, sweep_fm_fracs, sweep_tuned
from repro.tiering import policy as policy_mod
from repro.tiering.page_pool import (
    LazyHeat,
    TieredPagePool,
    _FastSet,
    _bulk_schedule,
    _bulk_schedule_batch,
    _resolve_step_victims,
)
from repro.tiering.reference_pool import ReferencePagePool


def microbench_trace(pm=60, rss=20_000, pacc_f=60_000, pacc_s=2_000,
                     n_intervals=10):
    cv = ConfigVector(
        pacc_f=pacc_f, pacc_s=pacc_s, pm_de=pm, pm_pr=pm, ai=6.0,
        rss_pages=rss, hot_thr=4, num_threads=1,
    )
    return generate_microbench(scale_config(cv, rss), n_intervals=n_intervals)


def random_trace(seed, rss=6_000, n_intervals=14):
    rng = np.random.default_rng(seed)
    tr = Trace(name=f"rand{seed}", rss_pages=rss)
    for _ in range(n_intervals):
        k = int(rng.integers(400, 2500))
        pages = rng.choice(rss, size=k, replace=False)
        tr.append(
            IntervalAccess(
                pages=pages,
                counts=rng.integers(1, 9, size=k),
                ops=1000.0,
            )
        )
    return tr


def assert_run_equal(res_a, res_b):
    assert res_a.stats == res_b.stats
    assert np.array_equal(res_a.interval_times, res_b.interval_times)


class TestIncrementalPoolEquivalence:
    """simulate() with the incremental pool == seed pool, bit for bit."""

    @pytest.mark.parametrize("frac", [1.0, 0.9, 0.6, 0.35, 0.15])
    def test_microbench_counters_and_times(self, frac):
        tr = microbench_trace()
        ref = simulate(tr, fm_frac=frac, pool_factory=ReferencePagePool)
        new = simulate(tr, fm_frac=frac)
        assert_run_equal(ref, new)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("frac", [0.8, 0.45, 0.2])
    def test_random_traces(self, seed, frac):
        tr = random_trace(seed)
        ref = simulate(tr, fm_frac=frac, pool_factory=ReferencePagePool)
        new = simulate(tr, fm_frac=frac)
        assert_run_equal(ref, new)

    def test_config_vectors_match(self):
        tr = microbench_trace(n_intervals=8)
        ref = simulate(tr, fm_frac=0.5, pool_factory=ReferencePagePool)
        new = simulate(tr, fm_frac=0.5)
        assert ref.configs == new.configs

    def test_fast_only_variant(self):
        tr = microbench_trace(n_intervals=6)
        ref = simulate(tr.fast_only(), fm_frac=1.0,
                       pool_factory=ReferencePagePool)
        new = simulate(tr.fast_only(), fm_frac=1.0)
        assert_run_equal(ref, new)


class TestSweepEquivalence:
    """Batched sweep == one simulate() per size (within 1e-9; in practice
    bit-exact, which is what these asserts require)."""

    def test_microbench_sweep_matches_per_size(self):
        tr = microbench_trace(n_intervals=8)
        fracs = np.round(np.arange(0.95, 0.14, -0.1), 3)
        res = sweep_fm_fracs(tr, fracs)
        for i, f in enumerate(fracs):
            per = simulate(tr, fm_frac=float(f))
            assert res.stats[i] == per.stats
            np.testing.assert_allclose(
                res.interval_times[i], per.interval_times,
                rtol=0.0, atol=1e-9,
            )
            assert abs(res.total_times[i] - per.total_time) <= 1e-9

    @pytest.mark.parametrize("seed", [3, 4])
    def test_random_sweep_matches_reference(self, seed):
        tr = random_trace(seed)
        fracs = np.array([0.85, 0.55, 0.3])
        res = sweep_fm_fracs(tr, fracs, collect_configs=True)
        for i, f in enumerate(fracs):
            ref = simulate(tr, fm_frac=float(f),
                           pool_factory=ReferencePagePool)
            assert res.stats[i] == ref.stats
            assert np.array_equal(res.interval_times[i], ref.interval_times)
            assert res.configs[i] == ref.configs

    def test_build_database_matches_seed_loop(self):
        cv = ConfigVector(
            pacc_f=30_000, pacc_s=1_500, pm_de=40, pm_pr=40, ai=8.0,
            rss_pages=10_000, hot_thr=4, num_threads=1,
        )
        fracs = np.round(np.arange(1.0, 0.29, -0.1), 3)
        db = build_database([cv], fm_fracs=fracs, n_intervals=8,
                            max_rss_pages=10_000)
        trace = generate_microbench(scale_config(cv, 10_000), n_intervals=8)
        for i, f in enumerate(fracs):
            t = trace.fast_only() if f >= 1.0 - 1e-9 else trace
            seed_t = simulate(
                t, fm_frac=min(float(f), 1.0),
                pool_factory=ReferencePagePool,
            ).total_time
            assert abs(db.records[0].times[i] - seed_t) <= 1e-9

    def test_legacy_backend_still_supported(self):
        cv = ConfigVector(
            pacc_f=20_000, pacc_s=1_000, pm_de=30, pm_pr=30, ai=8.0,
            rss_pages=8_000, hot_thr=4, num_threads=1,
        )
        fracs = np.array([1.0, 0.6, 0.3])
        db_fast = build_database([cv], fm_fracs=fracs, n_intervals=6)
        db_legacy = build_database(
            [cv],
            lambda trace, f: simulate(trace, fm_frac=f).total_time,
            fm_fracs=fracs,
            n_intervals=6,
        )
        # run_trace-equivalent custom backend produces the same records
        np.testing.assert_allclose(
            db_fast.records[0].times, db_legacy.records[0].times,
            rtol=0.0, atol=1e-9,
        )
        db_runtrace = build_database(
            [cv], run_trace, fm_fracs=fracs, n_intervals=6
        )
        assert np.array_equal(
            db_fast.records[0].times, db_runtrace.records[0].times
        )


def synthetic_db(rss=6_000, max_loss=0.4):
    """A one-record database whose loss curve grows linearly as fm
    shrinks, so every sane τ maps to a definite (mid-curve) target size
    and the tuner actually moves the watermarks."""
    grid = np.round(np.arange(1.0, 0.19, -0.05), 3)
    cv = ConfigVector(
        pacc_f=10_000, pacc_s=500, pm_de=20, pm_pr=20, ai=6.0,
        rss_pages=rss, hot_thr=4, num_threads=1,
    )
    times = 1.0 + np.linspace(0.0, max_loss, grid.size)
    db = PerfDB()
    db.add(PerfRecord(config=cv, fm_fracs=grid, times=times))
    db.build()
    return db


def make_tuner(db, tau, max_step_frac=0.08):
    """A tuner with an *unbound* controller (the sweep/engine binds it)."""
    return TunaTuner(
        db,
        WatermarkController(max_step_frac=max_step_frac),
        TunerConfig(target_loss=tau, cooldown_windows=3),
    )


def assert_tuned_equal(sim_res, sweep_res, sim_tuner, sweep_tuner):
    assert sim_res.stats == sweep_res.stats
    assert np.array_equal(sim_res.interval_times, sweep_res.interval_times)
    assert np.array_equal(sim_res.fm_sizes, sweep_res.fm_sizes)
    assert sim_res.configs == sweep_res.configs
    assert sim_res.total_time == sweep_res.total_time
    if sim_tuner is None:
        assert sweep_tuner is None
        return
    assert [d.__dict__ for d in sim_tuner.decisions] == [
        d.__dict__ for d in sweep_tuner.decisions
    ]
    assert [e.__dict__ for e in sim_tuner.controller.log] == [
        e.__dict__ for e in sweep_tuner.controller.log
    ]


class TestTunedSweepEquivalence:
    """sweep_tuned == one simulate(..., tuner=...) per slice, bit for bit:
    counters, interval times, config vectors, per-interval fm sizes, tuner
    decisions and watermark event logs."""

    SPECS = [(0.05, 3), (0.10, 2), (0.20, 4), (None, None)]

    def _run_pair(self, tr, db):
        per = []
        for tau, te in self.SPECS:
            tuner = make_tuner(db, tau) if tau is not None else None
            per.append(
                (
                    simulate(tr, fm_frac=1.0, tuner=tuner, tune_every=te),
                    tuner,
                )
            )
        tuners = [
            make_tuner(db, tau) if tau is not None else None
            for tau, _ in self.SPECS
        ]
        slices = [
            TunedSlice(1.0, tuner, te)
            for tuner, (_, te) in zip(tuners, self.SPECS)
        ]
        return per, list(zip(sweep_tuned(tr, slices), tuners))

    def test_random_trace_with_live_watermark_moves(self):
        tr = random_trace(3, n_intervals=30)
        db = synthetic_db()
        per, swept = self._run_pair(tr, db)
        moved = 0
        for (sim_res, sim_tuner), (sweep_res, sweep_tuner) in zip(per, swept):
            assert_tuned_equal(sim_res, sweep_res, sim_tuner, sweep_tuner)
            if sweep_tuner is not None:
                moved += len(sweep_tuner.controller.log)
        # the scenario must exercise actuation, not just idle along
        assert moved > 0
        assert any(res.fm_sizes.min() < tr.rss_pages for res, _ in swept[:3])

    def test_microbench_trace(self):
        tr = microbench_trace(rss=8_000, pacc_f=24_000, pacc_s=800,
                              n_intervals=12)
        db = synthetic_db(rss=8_000)
        per, swept = self._run_pair(tr, db)
        for (sim_res, sim_tuner), (sweep_res, sweep_tuner) in zip(per, swept):
            assert_tuned_equal(sim_res, sweep_res, sim_tuner, sweep_tuner)

    def test_reference_pool_anchor(self):
        """The frozen seed pool is the golden model for the tuned path too:
        simulate(tuner=...) over ReferencePagePool == the tuned sweep."""
        tr = random_trace(5, n_intervals=24)
        db = synthetic_db()
        ref_tuner = make_tuner(db, 0.10)
        ref = simulate(tr, fm_frac=1.0, tuner=ref_tuner, tune_every=2,
                       pool_factory=ReferencePagePool)
        sweep_tuner = make_tuner(db, 0.10)
        (res,) = sweep_tuned(tr, [TunedSlice(1.0, sweep_tuner, 2)])
        assert_tuned_equal(ref, res, ref_tuner, sweep_tuner)

    def test_plain_slice_matches_untuned_simulate(self):
        tr = random_trace(6)
        (res,) = sweep_tuned(tr, [TunedSlice(0.6)])
        per = simulate(tr, fm_frac=0.6)
        assert res.stats == per.stats
        assert np.array_equal(res.interval_times, per.interval_times)
        assert np.array_equal(res.fm_sizes, per.fm_sizes)

    def test_feedback_guard_equivalence(self):
        """Deep-shrink slices trip the closed-loop feedback guard (grow
        hard + cooldown); the sweep must replay that path exactly too."""
        tr = random_trace(7, n_intervals=30)
        db = synthetic_db(max_loss=0.02)  # db says everything is safe
        sim_tuner = make_tuner(db, 0.05, max_step_frac=0.2)
        sim_res = simulate(tr, fm_frac=1.0, tuner=sim_tuner, tune_every=2)
        sweep_tuner = make_tuner(db, 0.05, max_step_frac=0.2)
        (res,) = sweep_tuned(tr, [TunedSlice(1.0, sweep_tuner, 2)])
        assert_tuned_equal(sim_res, res, sim_tuner, sweep_tuner)


class _ChunkedOnlyPool(TieredPagePool):
    """Incremental pool with the bulk step disabled: forces the chunked
    promote/reclaim loop, the third lane of the thrash equivalence."""

    def _try_bulk_step(self, cand, _sched=None):
        return None


def pressure_trace(seed, rss=6_000, n_intervals=12):
    """Rotating hot window ~ most of the RSS: candidate counts far beyond
    any mid-curve headroom, so per-step reclaim demand digs into the same
    step's promotions (the bulk path's thrash regime)."""
    rng = np.random.default_rng(seed)
    tr = Trace(name=f"press{seed}", rss_pages=rss)
    hot_n = int(rss * rng.uniform(0.5, 0.8))
    stride = max(1, int(hot_n * rng.uniform(0.15, 0.45)))
    for i in range(n_intervals):
        hot = (np.arange(hot_n) + i * stride) % rss
        extra = rng.choice(rss, size=rss // 10, replace=False)
        pages = np.unique(np.concatenate([hot, extra]))
        counts = rng.integers(4, 9, size=pages.size)  # nearly all hot
        tr.append(IntervalAccess(pages=pages, counts=counts, ops=1000.0))
    return tr


class TestThrashEquivalence:
    """The thrash regime stays on the bulk path and stays bit-exact:
    bulk == forced-chunked == ReferencePagePool per lane, across
    near-capacity watermarks, candidate counts >> headroom, and starved
    kswapd budgets — and the sweeps never execute the chunked loop."""

    def _assert_three_lanes(self, tr, fracs, cap=None, kswapd=None):
        sweep_policy = policy_mod.TPPPolicy(hot_thr=4)
        res = sweep_fm_fracs(
            tr, fracs, hw_capacity_pages=cap, kswapd_batch=kswapd,
            collect_configs=True, policy=sweep_policy,
        )
        assert sweep_policy.chunked_steps == 0
        for i, f in enumerate(fracs):
            ref = simulate(
                tr, fm_frac=float(f), hw_capacity_pages=cap,
                pool_factory=functools.partial(
                    ReferencePagePool, kswapd_batch=kswapd
                ),
            )
            chunked = simulate(
                tr, fm_frac=float(f), hw_capacity_pages=cap,
                pool_factory=functools.partial(
                    _ChunkedOnlyPool, kswapd_batch=kswapd
                ),
            )
            for lane in (ref, chunked):
                assert res.stats[i] == lane.stats, f
                assert np.array_equal(
                    res.interval_times[i], lane.interval_times
                ), f
                assert res.configs[i] == lane.configs, f

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_pressure_sweeps_random(self, seed):
        self._assert_three_lanes(
            pressure_trace(seed), np.array([0.8, 0.45, 0.25, 0.1])
        )

    @pytest.mark.parametrize("kswapd", [1, 16, 96])
    def test_kswapd_starved(self, kswapd):
        self._assert_three_lanes(
            pressure_trace(7, rss=4_000, n_intervals=8),
            np.array([0.6, 0.3, 0.12]),
            kswapd=kswapd,
        )

    def test_watermarks_near_capacity(self):
        # hw capacity at half the RSS, fracs up to 1.0: low_free hits 0,
        # promotions fail with reclaim exhausted (the latent seed
        # stats-vs-outcome pm_fail divergence this regime exposed)
        self._assert_three_lanes(
            pressure_trace(11, rss=6_000, n_intervals=10),
            np.array([1.0, 0.97, 0.55, 0.2]),
            cap=3_000,
            kswapd=32,
        )

    def test_tuned_slices_thrash_on_watermark_moves(self):
        # aggressive tuner steps shrink mid-run: the moved watermarks make
        # reclaim demand reach same-interval promotions (direct reclaim
        # fires), and the tuned sweep must replay it all exactly
        tr = pressure_trace(5, rss=5_000, n_intervals=16)
        db = synthetic_db(rss=5_000)
        specs = [(0.25, 2), (0.10, 3), (None, None)]
        per = []
        for tau, te in specs:
            tuner = make_tuner(db, tau, max_step_frac=0.3) if tau else None
            per.append(
                (simulate(tr, fm_frac=0.9, tuner=tuner, tune_every=te), tuner)
            )
        tuners = [
            make_tuner(db, tau, max_step_frac=0.3) if tau else None
            for tau, _ in specs
        ]
        sweep_policy = policy_mod.TPPPolicy(hot_thr=4)
        swept = sweep_tuned(
            tr,
            [TunedSlice(0.9, t, te) for t, (_, te) in zip(tuners, specs)],
            policy=sweep_policy,
        )
        assert sweep_policy.chunked_steps == 0
        moved = direct = 0
        for (sim_res, sim_tuner), sweep_res, sweep_tuner in zip(
            per, swept, tuners
        ):
            assert_tuned_equal(sim_res, sweep_res, sim_tuner, sweep_tuner)
            direct += sweep_res.stats["pgdemote_direct"]
            if sweep_tuner is not None:
                moved += len(sweep_tuner.controller.log)
        assert moved > 0 and direct > 0  # the scenario must actually thrash

    @pytest.mark.parametrize("keys", ["float", "rank"])
    @pytest.mark.parametrize("seed", list(range(8)))
    def test_victim_resolver_matches_event_replay(self, seed, keys):
        """Property check of the merge itself: random key-sorted base
        streams, random candidate keys and random availability horizons
        must select exactly the pages a per-event heap replay demotes —
        keyed on float heat (the resolver sorts the candidates), or on
        integer ranks of one shared (heat, id) ranking with the
        candidates' key order handed in (the device sweep's form)."""
        rng = np.random.default_rng(seed)
        n_base = int(rng.integers(0, 60))
        n_cand = int(rng.integers(1, 60))
        # small integer keys force heavy cross-stream ties (broken by id)
        base_eff = np.sort(rng.integers(0, 6, size=n_base)).astype(np.float64)
        base_ids = np.arange(n_base, dtype=np.int64)
        order = np.lexsort((base_ids, base_eff))
        base_eff, base_ids = base_eff[order], base_ids[order]
        cand_eff = rng.integers(0, 6, size=n_cand).astype(np.float64)
        cand_ids = np.arange(1000, 1000 + n_cand, dtype=np.int64)
        # availability horizons grow monotonically; every event's demand
        # stays within the supply promoted-or-resident at that point
        events, p, demanded = [], 0, 0
        for _ in range(6):
            p = int(rng.integers(p, n_cand + 1))
            avail = n_base + p - demanded
            if avail > 0:
                d = int(rng.integers(1, avail + 1))
                events.append((p, d))
                demanded += d
        if not events:
            events = [(n_cand, max(1, (n_base + n_cand) // 2))]
        if keys == "float":
            args = (base_eff, base_ids, cand_eff, cand_ids, events)
        else:
            ranking = np.lexsort((np.concatenate([base_ids, cand_ids]),
                                  np.concatenate([base_eff, cand_eff])))
            rank = np.empty(ranking.size, dtype=np.int64)
            rank[ranking] = np.arange(ranking.size)
            # the candidates' key order is a filter of the shared ranking
            cand_order = ranking[ranking >= n_base] - n_base
            args = (rank[:n_base], base_ids, rank[n_base:], cand_ids, events,
                    cand_order)
        n_b, taken = _resolve_step_victims(*args)
        # naive replay: per event, pop the d smallest available (eff, id)
        heap, bi, p_prev = [], 0, 0
        got_base, got_cand = 0, set()
        for p_e, d in events:
            for j in range(p_prev, p_e):
                heapq.heappush(heap, (cand_eff[j], int(cand_ids[j]), j))
            p_prev = p_e
            for _ in range(d):
                take_base = bi < n_base and (
                    not heap
                    or (base_eff[bi], int(base_ids[bi])) < heap[0][:2]
                )
                if take_base:
                    got_base += 1
                    bi += 1
                elif heap:
                    got_cand.add(heapq.heappop(heap)[2])
        assert n_b == got_base, (seed, events)
        assert set(np.flatnonzero(taken)) == got_cand, (seed, events)


BACKEND_CASES = [
    ("admission", policy_mod.AdmissionTPPPolicy, {"admit_margin": 2.0}),
    ("thrash_guard", policy_mod.ThrashGuardPolicy,
     {"reuse_window": 2, "churn_frac": 0.25, "backoff_intervals": 2}),
]


class TestPluggableBackendEquivalence:
    """The admission-controlled and thrash-responsive backends are anchored
    exactly like PR 3 anchored TPP: bulk sweep == forced-chunked
    ``_ChunkedOnlyPool`` == ``ReferencePagePool`` per lane (counters,
    interval times, config vectors incl. the new ``pm_admit_fail`` extra),
    with the sweep's policy instance asserted chunked-loop-free — on both
    the fixed-size and the tuned sweep."""

    def _assert_three_lanes(self, make_policy, tr, fracs, kswapd=None):
        sweep_policy = make_policy()
        res = sweep_fm_fracs(
            tr, fracs, kswapd_batch=kswapd, collect_configs=True,
            policy=sweep_policy,
        )
        assert sweep_policy.chunked_steps == 0
        suppressed = 0
        for i, f in enumerate(fracs):
            suppressed += sum(c.pm_admit_fail for c in res.configs[i])
            for pf in (ReferencePagePool, _ChunkedOnlyPool):
                lane = simulate(
                    tr, fm_frac=float(f), policy=make_policy(),
                    pool_factory=functools.partial(pf, kswapd_batch=kswapd),
                )
                assert res.stats[i] == lane.stats, (f, pf)
                assert np.array_equal(
                    res.interval_times[i], lane.interval_times
                ), (f, pf)
                assert res.configs[i] == lane.configs, (f, pf)
        # the scenario must actually exercise the admission/guard stage
        assert suppressed > 0

    @pytest.mark.parametrize("kind,cls,params", BACKEND_CASES)
    @pytest.mark.parametrize("seed", [0, 1])
    def test_pressure_three_lanes(self, kind, cls, params, seed):
        self._assert_three_lanes(
            lambda: cls(**params),
            pressure_trace(seed, rss=4_000, n_intervals=8),
            np.array([0.6, 0.3, 0.12]),
            kswapd=16,
        )

    @pytest.mark.parametrize("kind,cls,params", BACKEND_CASES)
    def test_tuned_sweep_matches_per_size(self, kind, cls, params):
        tr = pressure_trace(5, rss=5_000, n_intervals=16)
        db = synthetic_db(rss=5_000)
        specs = [(0.25, 2), (None, None)]
        per = []
        for tau, te in specs:
            tuner = make_tuner(db, tau, max_step_frac=0.3) if tau else None
            per.append(
                (
                    simulate(
                        tr, fm_frac=0.9, policy=cls(**params),
                        tuner=tuner, tune_every=te,
                    ),
                    tuner,
                )
            )
        tuners = [
            make_tuner(db, tau, max_step_frac=0.3) if tau else None
            for tau, _ in specs
        ]
        sweep_policy = cls(**params)
        swept = sweep_tuned(
            tr,
            [TunedSlice(0.9, t, te) for t, (_, te) in zip(tuners, specs)],
            policy=sweep_policy,
        )
        assert sweep_policy.chunked_steps == 0
        for (sim_res, sim_tuner), sweep_res, sweep_tuner in zip(
            per, swept, tuners
        ):
            assert_tuned_equal(sim_res, sweep_res, sim_tuner, sweep_tuner)

    def test_admission_rejects_spikes_not_history(self):
        """One-interval spikes are rejected; pages with reuse history pass
        once their decayed mass clears the margin."""
        pool = TieredPagePool(num_pages=100, hw_capacity=100)
        pool.set_fm_size(50)
        pool.place(np.arange(100, dtype=np.int64), policy_mod.Tier.SLOW)
        pol = policy_mod.AdmissionTPPPolicy(hot_thr=4, admit_margin=2.0)
        pages = np.arange(10, dtype=np.int64)
        # intervals 1-2: pages touched at exactly hot_thr — the decayed
        # history mass (0 then 4*decay) keeps the effective heat under
        # margin * hot_thr == 8: every candidate is rejected
        for _ in range(2):
            pool.apply_accesses(pages, np.full(10, 4), touch_cap=4)
            out = pol.step(pool, pages)
            assert out.pm_pr == 0 and out.pm_admit_fail == 10
            pool.end_interval()
        # interval 3: two folds of history ((4*d + 4)*d ≈ 4.83) + 4
        # touches clears the margin: all admitted, none rejected
        pool.apply_accesses(pages, np.full(10, 4), touch_cap=4)
        out = pol.step(pool, pages)
        assert out.pm_admit_fail == 0 and out.pm_pr == 10

    @pytest.mark.parametrize("reuse_window", [1, 2])
    def test_thrash_guard_backs_off_pingpong(self, reuse_window):
        """A rotating set ~2x the fast tier ping-pongs under plain TPP;
        the guard must detect it and suppress re-promotions — including
        at the minimum window (reuse_window=1 covers exactly the
        immediately preceding step, where same-regime ping-pong lives)."""
        tr = pressure_trace(9, rss=3_000, n_intervals=8)
        guard = policy_mod.ThrashGuardPolicy(reuse_window=reuse_window)
        res = simulate(tr, fm_frac=0.3, policy=guard)
        tpp = simulate(tr, fm_frac=0.3)
        suppressed = sum(c.pm_admit_fail for c in res.configs)
        assert suppressed > 0
        assert res.migrations < tpp.migrations


class TestBatchPolicySchedule:
    """The cross-size vectorized TPP schedule == the scalar recurrence."""

    def test_matches_scalar_on_random_states(self):
        rng = np.random.default_rng(11)
        n = 500
        cap = rng.integers(100, 5_000, size=n)
        fm = np.maximum(1, (cap * rng.uniform(0.05, 1.0, size=n)).astype(np.int64))
        low = cap - fm
        min_free = (0.8 * low).astype(np.int64)
        fast_count = rng.integers(0, cap + 1)
        free = cap - fast_count
        kswapd = np.maximum(128, cap // 64)
        n_cand = rng.integers(0, 3_000, size=n)
        batch = _bulk_schedule_batch(
            free, fast_count, min_free, low, low, kswapd, n_cand
        )
        for s in range(n):
            scalar = _bulk_schedule(
                int(free[s]), int(fast_count[s]), int(min_free[s]),
                int(low[s]), int(low[s]), int(kswapd[s]), int(n_cand[s]),
            )
            assert tuple(int(col[s]) for col in batch) == scalar, s

    def test_step_batch_matches_serial_steps(self):
        from repro.tiering.policy import TPPPolicy

        tr = random_trace(9)
        fracs = np.array([0.9, 0.5, 0.25])
        res = sweep_fm_fracs(tr, fracs)  # drives step_batch internally
        for i, f in enumerate(fracs):
            per = simulate(tr, fm_frac=float(f),
                           policy=TPPPolicy(hot_thr=4))
            assert res.stats[i] == per.stats
            assert np.array_equal(res.interval_times[i], per.interval_times)


class TestIncrementalPrimitives:
    """Unit checks of the new pool data structures."""

    def test_lazy_heat_matches_dense_decay(self):
        rng = np.random.default_rng(5)
        n = 500
        heat = LazyHeat(n, 0.5 ** 0.5)
        dense = np.zeros(n)
        for _ in range(30):
            k = int(rng.integers(0, 120))
            pages = rng.choice(n, size=k, replace=False)
            touches = rng.integers(1, 6, size=k)
            it = np.zeros(n, dtype=np.int64)
            it[pages] = touches
            dense = dense * heat.decay + it
            heat.fold(pages, touches)
        got = heat.dense()
        assert np.array_equal(got, dense)

    def test_fast_set_add_remove(self):
        fs = _FastSet(100)
        fs.add(np.array([5, 7, 9, 11]))
        fs.remove(np.array([9, 5]))
        assert sorted(fs.members().tolist()) == [7, 11]
        fs.add(np.array([1, 2]))
        fs.remove(np.array([7, 11, 1, 2]))
        assert fs.n == 0

    def test_counters_track_reference(self):
        rng = np.random.default_rng(7)
        pool = TieredPagePool(num_pages=400, hw_capacity=200)
        ref = ReferencePagePool(num_pages=400, hw_capacity=200)
        pool.set_fm_size(120)
        ref.set_fm_size(120)
        for _ in range(12):
            pages = rng.choice(400, size=150, replace=False)
            counts = rng.integers(1, 6, size=150)
            assert pool.apply_accesses(pages, counts) == ref.apply_accesses(
                pages, counts
            )
            pool.promote(pages[:40])
            ref.promote(pages[:40])
            pool.run_reclaim(allow_direct=True)
            ref.run_reclaim(allow_direct=True)
            assert pool.fast_used == ref.fast_used
            assert pool.rss_pages == ref.rss_pages
            assert np.array_equal(pool.tier, ref.tier)
            pool.end_interval()
            ref.end_interval()
            assert np.array_equal(pool.heat, ref.heat)
        assert pool.stats.snapshot() == ref.stats.snapshot()

    def test_duplicate_page_ids_handled(self):
        pool = TieredPagePool(num_pages=50, hw_capacity=50)
        ref = ReferencePagePool(num_pages=50, hw_capacity=50)
        pool.set_fm_size(20)
        ref.set_fm_size(20)
        pages = np.array([3, 7, 3, 9, 7, 11])
        counts = np.array([2, 1, 3, 4, 1, 5])
        assert pool.apply_accesses(pages, counts) == ref.apply_accesses(
            pages, counts
        )
        assert pool.fast_used == ref.fast_used
        assert np.array_equal(pool.tier, ref.tier)


class TestJaxSweepEquivalence:
    """Three lanes for the accelerator-native backend: the jitted JAX
    sweep (:mod:`repro.sim.jax_engine`, Pallas victim-partition kernel in
    interpreter mode) == the numpy sweep == the frozen
    ``ReferencePagePool``, bit for bit — counters, interval times, config
    vectors — across the thrash, starved-kswapd, near-capacity and
    tuned-shrink regimes, with the sweep policies chunked-loop-free."""

    @pytest.fixture(autouse=True)
    def _interpret_mode(self, monkeypatch):
        # force the Pallas kernel through interpreter mode so these tests
        # cover the kernel code path on CPU, not just the jnp reference
        monkeypatch.setenv("REPRO_PALLAS", "interpret")

    def _assert_three_lanes(self, tr, fracs, cap=None, kswapd=None,
                            make_policy=None):
        pytest.importorskip("jax")
        from repro.sim.sweep import _sweep_fm_fracs

        if make_policy is None:
            def make_policy():
                return policy_mod.TPPPolicy(hot_thr=4)
        fracs = np.asarray(fracs, dtype=np.float64)
        jax_policy = make_policy()
        jx = _sweep_fm_fracs(
            tr, fracs, hw_capacity_pages=cap, kswapd_batch=kswapd,
            collect_configs=True, policy=jax_policy, engine="jax",
        )
        assert jax_policy.chunked_steps == 0
        np_policy = make_policy()
        base = _sweep_fm_fracs(
            tr, fracs, hw_capacity_pages=cap, kswapd_batch=kswapd,
            collect_configs=True, policy=np_policy, engine="numpy",
        )
        for i, f in enumerate(fracs):
            assert jx.stats[i] == base.stats[i], f
            assert np.array_equal(
                jx.interval_times[i], base.interval_times[i]
            ), f
            assert jx.configs[i] == base.configs[i], f
            ref = simulate(
                tr, fm_frac=float(f), hw_capacity_pages=cap,
                policy=make_policy(),
                pool_factory=functools.partial(
                    ReferencePagePool, kswapd_batch=kswapd
                ),
            )
            assert jx.stats[i] == ref.stats, f
            assert np.array_equal(jx.interval_times[i], ref.interval_times), f
            assert jx.configs[i] == ref.configs, f
        return jx

    @pytest.mark.parametrize("seed", [0, 2])
    def test_thrash_pressure(self, seed):
        self._assert_three_lanes(
            pressure_trace(seed, rss=3_000, n_intervals=8),
            [0.8, 0.45, 0.25, 0.1],
        )

    @pytest.mark.parametrize("seed", [0, 2])
    def test_thrash_resolver_key_orders(self, seed):
        """Each interfering size takes its winners' key order from the
        interval's shared ranking, or sorts their ranks itself where few
        win: the pressure trace's sizes reach both, every resolver call
        takes one, and the sweep stays bit-identical either way."""
        tracing.reset()
        try:
            with tracing.recording():
                self._assert_three_lanes(
                    pressure_trace(seed, rss=3_000, n_intervals=8),
                    [0.8, 0.45, 0.25, 0.1],
                )
            c = tracing.snapshot()["counters"]
        finally:
            tracing.reset()
        shared = c.get("fixup.shared_order", 0)
        own = c.get("fixup.own_order", 0)
        assert shared + own == c["sweep.interfering_sizes"]
        assert shared > 0 and own > 0

    @pytest.mark.parametrize("on_device", [True, False], ids=["device", "host"])
    @pytest.mark.parametrize("seed", [0, 2])
    def test_thrash_pressure_rank_paths(self, monkeypatch, seed, on_device):
        """The demotion ranking on the device (forced here on the CPU) and
        on the host give the same bit-exact sweep through the thrash
        fix-up; every demoting interval ranks once, on the forced path."""
        pytest.importorskip("jax")
        from repro.sim import jax_engine

        monkeypatch.setattr(jax_engine, "_rank_on_device", lambda: on_device)
        tracing.reset()
        try:
            with tracing.recording():
                jx = self._assert_three_lanes(
                    pressure_trace(seed, rss=3_000, n_intervals=8),
                    [0.8, 0.45, 0.25, 0.1],
                )
            c = tracing.snapshot()["counters"]
        finally:
            tracing.reset()
        demoting = np.any([[cv.pm_de > 0 for cv in cfgs] for cfgs in jx.configs], axis=0)
        taken, other = ("rank.device", "rank.host")[:: 1 if on_device else -1]
        assert c[taken] == int(demoting.sum()) > 0
        assert c.get(other, 0) == 0
        assert c["sweep.interfering_sizes"] > 0

    @pytest.mark.parametrize("kswapd", [1, 96])
    def test_kswapd_starved(self, kswapd):
        self._assert_three_lanes(
            pressure_trace(7, rss=3_000, n_intervals=6),
            [0.6, 0.3, 0.12],
            kswapd=kswapd,
        )

    def test_watermarks_near_capacity(self):
        self._assert_three_lanes(
            pressure_trace(11, rss=4_000, n_intervals=8),
            [1.0, 0.97, 0.55, 0.2],
            cap=2_000,
            kswapd=32,
        )

    def test_admission_backend(self):
        self._assert_three_lanes(
            pressure_trace(3, rss=3_000, n_intervals=6),
            [0.6, 0.25],
            make_policy=lambda: policy_mod.AdmissionTPPPolicy(
                hot_thr=4, admit_margin=0.5
            ),
        )

    def test_tuned_shrink_three_lanes(self):
        pytest.importorskip("jax")
        from repro.sim.sweep import _sweep_tuned

        tr = pressure_trace(5, rss=4_000, n_intervals=12)
        db = synthetic_db(rss=4_000)
        specs = [(0.25, 2), (None, None)]

        def mk():
            return [
                make_tuner(db, tau, max_step_frac=0.3) if tau else None
                for tau, _ in specs
            ]

        lanes, tuners = {}, {}
        for engine in ("numpy", "jax"):
            tn = mk()
            pol = policy_mod.TPPPolicy(hot_thr=4)
            lanes[engine] = _sweep_tuned(
                tr,
                [TunedSlice(0.9, t, te) for t, (_, te) in zip(tn, specs)],
                policy=pol, engine=engine,
            )
            assert pol.chunked_steps == 0
            tuners[engine] = tn
        ref_tuners = mk()
        refs = [
            simulate(tr, fm_frac=0.9, tuner=t, tune_every=te,
                     pool_factory=ReferencePagePool)
            for t, (_, te) in zip(ref_tuners, specs)
        ]
        moved = 0
        for i in range(len(specs)):
            assert_tuned_equal(lanes["numpy"][i], lanes["jax"][i],
                               tuners["numpy"][i], tuners["jax"][i])
            assert_tuned_equal(refs[i], lanes["jax"][i],
                               ref_tuners[i], tuners["jax"][i])
            if tuners["jax"][i] is not None:
                moved += len(tuners["jax"][i].controller.log)
        assert moved > 0  # the tuner must actually shrink the fast tier


class TestJaxEngineRouting:
    """``Scenario.engine`` planner routing and its fail-fast eligibility
    validation in :func:`repro.sim.api.run`."""

    def _tiny(self):
        return pressure_trace(0, rss=1_000, n_intervals=3)

    def test_jax_backend_labels_and_equality(self):
        pytest.importorskip("jax")
        from repro.sim.api import Experiment, Scenario
        from repro.sim.api import run as run_experiment

        tr = self._tiny()

        def _exp(engine):
            return run_experiment(
                Experiment(
                    name=f"route_{engine}",
                    scenarios=[Scenario(trace=tr, engine=engine)],
                    fm_fracs=(0.5, 0.25),
                    collect_configs=True,
                )
            )

        jx, base = _exp("jax"), _exp("numpy")
        assert [r.backend for r in jx.runs] == ["jax_sweep", "jax_sweep"]
        assert [r.backend for r in base.runs] == ["sweep", "sweep"]
        assert jx.chunked_step_count == 0
        for rj, rn in zip(jx.runs, base.runs):
            assert rj.result.stats == rn.result.stats
            assert np.array_equal(
                rj.result.interval_times, rn.result.interval_times
            )
            assert rj.result.configs == rn.result.configs

    def test_engine_validation_fails_fast(self):
        from repro.sim.api import Experiment, PolicySpec, Scenario
        from repro.sim.api import run as run_experiment

        tr = self._tiny()
        with pytest.raises(ValueError, match="engine"):
            run_experiment(
                Experiment(
                    name="bad_engine",
                    scenarios=[Scenario(trace=tr, engine="torch")],
                )
            )
        with pytest.raises(ValueError, match="pool_factory"):
            run_experiment(
                Experiment(
                    name="bad_pool",
                    scenarios=[
                        Scenario(
                            trace=tr, engine="jax",
                            pool_factory=ReferencePagePool,
                        )
                    ],
                )
            )
        with pytest.raises(ValueError, match="thrash_guard"):
            run_experiment(
                Experiment(
                    name="bad_policy",
                    scenarios=[Scenario(trace=tr, engine="jax")],
                    policies=[
                        PolicySpec(
                            kind="thrash_guard",
                            params={"reuse_window": 2},
                        )
                    ],
                )
            )


class TestVictimPartitionKernel:
    """The Pallas segment-scan re-partition == a per-row heap replay of
    the demotion walk, property-tested over random fast-tier layouts and
    demands (and always equal to the jnp reference, so mode selection can
    never perturb victim identities)."""

    @pytest.fixture(autouse=True)
    def _hyp(self):
        pytest.importorskip("hypothesis")
        pytest.importorskip("jax")

    @pytest.mark.parametrize("shape", [(1, 64), (3, 64), (2, 200)])
    def test_pallas_matches_heap_replay(self, shape):
        from hypothesis import given, settings
        from hypothesis import strategies as st
        import jax.numpy as jnp

        from repro.kernels.demote_rank import (
            _victim_partition_jnp,
            _victim_partition_pallas,
        )

        s, r = shape  # fixed shapes bound the per-example jit compiles

        @settings(max_examples=15, deadline=None)
        @given(
            seed=st.integers(0, 2**32 - 1),
            density=st.floats(0.0, 1.0),
            tight=st.booleans(),
        )
        def _property(seed, density, tight):
            rng = np.random.default_rng(seed)
            fast = (rng.random((s, r)) < density).astype(np.int32)
            # "tight" draws demand near the actual fast supply, where the
            # <=-boundary of the running count lives; loose draws roam
            # past it (over-demand must saturate, never over-select)
            hi = fast.sum(axis=1) + 1 if tight else np.full(s, r + 2)
            demand = rng.integers(0, hi + 1).astype(np.int64)
            got = np.asarray(
                _victim_partition_pallas(
                    jnp.asarray(fast), jnp.asarray(demand), interpret=True
                )
            )
            for row in range(s):
                # heap replay of GlobalDemoteRank.walk: pop the lowest
                # rank positions among fast entries, demand[row] times
                heap = list(np.flatnonzero(fast[row]))
                heapq.heapify(heap)
                want = set()
                for _ in range(int(demand[row])):
                    if not heap:
                        break
                    want.add(heapq.heappop(heap))
                assert set(np.flatnonzero(got[row])) == want, (row, demand)
            fallback = np.asarray(
                _victim_partition_jnp(jnp.asarray(fast), jnp.asarray(demand))
            )
            assert np.array_equal(got, fallback)

        _property()
