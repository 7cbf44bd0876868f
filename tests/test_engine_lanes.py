"""Every engine lane against its oracle, on workload-like traces.

Each case runs one path of the unified API (:func:`repro.sim.api.run`)
once and checks it bit for bit against an independent lane: the frozen
:class:`~repro.tiering.reference_pool.ReferencePagePool` run size by size
through :func:`repro.sim.engine._simulate`, or, for the device sweep and
the fleet layer, the numpy sweep they must reproduce. Each case also
checks that it exercised what it names (migrations, admission rejects,
arbiter events), so a lane that did nothing cannot pass.

The traces are an application stand-in (a skewed-reuse resident set plus
a migrating hot front, 16,000 pages over 48 intervals), the rotating
churn of :func:`repro.sim.workloads.thrash_trace` (8,000 pages over 16
intervals, hot set ~2x the mid-curve fast tier), and 150 tiny churn
scenarios for the planner's process fan-out. Speed is measured on the
chip by the ``bench/`` cells, not here.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

from benchmarks.common import DB_FM_FRACS, _representative_from, steady_from
from repro.core.microbench import generate_microbench
from repro.core.trace import IntervalAccess, Trace
from repro.core.tuner import TunaTuner, TunerConfig, build_database, scale_config
from repro.core.watermark import WatermarkController
from repro.fleet import ArbiterSpec, FleetScenario, TenantSpec
from repro.sim.api import Experiment, FaultSpec, PolicySpec, Scenario, TunerSpec, run
from repro.sim.engine import _simulate
from repro.sim.workloads import thrash_trace
from repro.tiering.policy import AdmissionTPPPolicy
from repro.tiering.reference_pool import ReferencePagePool

# what build_bench_db harvests: representative fracs + probe fracs
HARVEST_FRACS = (1.0, 0.95, 0.9, 0.8, 0.75, 0.6, 0.45, 0.3)
APP_RSS, APP_INTERVALS = 16_000, 48
DB_INTERVALS, DB_MAX_RSS, DB_MAX_CONFIGS = 8, 10_000, 6
TUNED_TARGETS, TUNE_EVERY = (0.02, 0.05, 0.10, 0.15, 0.25), 3
THRASH_RSS, THRASH_INTERVALS = 8_000, 16
THRASH_FRACS = (0.6, 0.45, 0.35, 0.25)
STRESS_SCENARIOS = 150


def _app_trace(rss: int, n_intervals: int, seed: int = 7) -> Trace:
    """A skewed-reuse resident set plus a hot front that migrates through
    the RSS (what makes pages churn)."""
    rng = np.random.default_rng(seed)
    tr = Trace(name="bench_app", rss_pages=rss, num_threads=4)
    hot = rng.permutation(rss)[: (2 * rss) // 3]
    front_n = rss // 10
    for i in range(n_intervals):
        front = (np.arange(front_n) + i * 997) % rss
        reuse = hot[rng.random(hot.size) < 0.85]
        pages = np.unique(np.concatenate([front, reuse]))
        counts = rng.integers(1, 8, size=pages.size)
        tr.append(IntervalAccess(pages=pages, counts=counts,
                                 ops=float(counts.sum()) * 40.0))
    return tr


def _stress_trace(seed: int) -> Trace:
    """One tiny churn trace; module-level so the fan-out can pickle it."""
    rng = np.random.default_rng(seed)
    rss = 400
    tr = Trace(name=f"stress{seed}", rss_pages=rss)
    hot_n = 260 + int(rng.integers(0, 80))
    for i in range(4):
        hot = (np.arange(hot_n) + i * 97) % rss
        pages = np.unique(np.concatenate([hot, rng.choice(rss, 40, replace=False)]))
        counts = rng.integers(4, 9, size=pages.size)
        tr.append(IntervalAccess(pages=pages, counts=counts, ops=100.0))
    return tr


def _harvest(trace: Trace) -> dict:
    rs = run(Experiment(name="harvest", scenarios=[Scenario(trace=trace)],
                        fm_fracs=HARVEST_FRACS, collect_configs=True))
    return {float(r.fm_frac): r.result.configs for r in rs.runs}


def _operating_points(trace: Trace, by_frac: dict) -> list:
    configs = [
        _representative_from(steady_from(by_frac[f]), trace)
        for f in (1.0, 0.9, 0.8)
    ]
    for f in (0.75, 0.6, 0.45, 0.3):
        steady = steady_from(by_frac[f])
        configs.extend(steady[:: max(1, len(steady) // 2)][:2])
    return configs[:DB_MAX_CONFIGS]


def _build(configs: list):
    return build_database(configs, fm_fracs=DB_FM_FRACS, n_intervals=DB_INTERVALS,
                          max_rss_pages=DB_MAX_RSS, workers=None)


@pytest.fixture(scope="module")
def app():
    """The application trace, its harvest and the database built from it."""
    trace = _app_trace(APP_RSS, APP_INTERVALS)
    by_frac = _harvest(trace)
    configs = _operating_points(trace, by_frac)
    return trace, by_frac, configs, _build(configs)


@pytest.fixture(scope="module")
def churn():
    return thrash_trace(n_intervals=THRASH_INTERVALS, rss_pages=THRASH_RSS)


def _tuner_specs() -> list:
    # k_neighbors=1: the database is tiny, and k-NN averaging over it mixes
    # distant records into every query; with k=1 the tuners actuate
    return [
        PolicySpec(label=f"tau{tau:g}", tuner=TunerSpec(
            target_loss=tau, tune_every=TUNE_EVERY, k_neighbors=1,
            cooldown_windows=3, max_step_frac=0.05))
        for tau in TUNED_TARGETS
    ]


def _same(a, b, fm_sizes=False) -> None:
    assert a.stats == b.stats
    assert np.array_equal(a.interval_times, b.interval_times)
    assert a.configs == b.configs
    if fm_sizes:
        assert np.array_equal(a.fm_sizes, b.fm_sizes)


def _churn_run(churn, sc=None, **experiment):
    """The churn trace over the thrash sizes; ``sc`` holds Scenario fields."""
    return run(Experiment(scenarios=[Scenario(trace=churn, **(sc or {}))],
                          fm_fracs=THRASH_FRACS, collect_configs=True, **experiment))


def _lane_harvest_records(app, churn):
    # the harvest vectors and the database records, each against one
    # reference-pool run per size
    trace, by_frac, configs, db = app
    for f in HARVEST_FRACS:
        ref = _simulate(trace, fm_frac=f, pool_factory=ReferencePagePool)
        assert ref.configs == by_frac[f], f
    assert len(db.records) == len(configs) == DB_MAX_CONFIGS
    for cv, rec in zip(configs, db.records, strict=True):
        mb = generate_microbench(scale_config(cv, DB_MAX_RSS), n_intervals=DB_INTERVALS)
        times = [
            _simulate(mb.fast_only(), fm_frac=1.0, pool_factory=ReferencePagePool).total_time
            if f >= 1.0 - 1e-9 else
            _simulate(mb, fm_frac=float(f), pool_factory=ReferencePagePool).total_time
            for f in DB_FM_FRACS
        ]
        assert np.array_equal(rec.times, times)


def _lane_tuned(app, churn):
    trace, _, _, db = app
    rs = run(Experiment(scenarios=[Scenario(trace=trace)], fm_fracs=(1.0,),
                        policies=_tuner_specs()), db=db)
    assert rs.chunked_step_count == 0
    for tau, rec in zip(TUNED_TARGETS, rs.runs, strict=True):
        tuner = TunaTuner(db, WatermarkController(max_step_frac=0.05),
                          TunerConfig(target_loss=tau, cooldown_windows=3, k_neighbors=1))
        ref = _simulate(trace, fm_frac=1.0, tuner=tuner, tune_every=TUNE_EVERY,
                        pool_factory=ReferencePagePool)
        _same(ref, rec.result, fm_sizes=True)
    # a tuned run without watermark actuation checks nothing of the loop
    assert sum(r.result.migrations for r in rs.runs) > 0


def _lane_thrash(app, churn):
    rs = _churn_run(churn)
    assert rs.chunked_step_count == 0
    for f, rec in zip(THRASH_FRACS, rs.runs, strict=True):
        _same(_simulate(churn, fm_frac=f, pool_factory=ReferencePagePool), rec.result)
    assert sum(r.result.migrations for r in rs.runs) > 0


def _lane_admission(app, churn):
    rs = _churn_run(churn, policies=[PolicySpec(kind="admission")])
    assert rs.chunked_step_count == 0
    for f, rec in zip(THRASH_FRACS, rs.runs, strict=True):
        ref = _simulate(churn, fm_frac=f, policy=AdmissionTPPPolicy(),
                        pool_factory=ReferencePagePool)
        _same(ref, rec.result)
    assert sum(c.pm_admit_fail for r in rs.runs for c in r.result.configs) > 0


def _lane_jax(app, churn):
    pytest.importorskip("jax")
    oracle = _churn_run(churn, sc={"engine": "numpy"})
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_PALLAS", "interpret")  # the Pallas kernel, not its jnp reference
        jx = _churn_run(churn, sc={"engine": "jax"})
    assert jx.chunked_step_count == 0
    for a, b in zip(oracle.runs, jx.runs, strict=True):
        assert (a.backend, b.backend) == ("sweep", "jax_sweep")
        _same(a.result, b.result)
    assert sum(r.result.migrations for r in jx.runs) > 0


def _lane_fleet(app, churn):
    # one tenant at the whole budget: the arbiter may only hold, so every
    # run equals the plain tuned sweep it wraps
    trace, _, _, db = app
    plain = run(Experiment(scenarios=[Scenario(trace=trace)], fm_fracs=(1.0,),
                           policies=_tuner_specs()), db=db)
    fleet = run(Experiment(
        scenarios=[FleetScenario(tenants=(TenantSpec(trace=trace, name="solo"),),
                                 name="fleet", budget_frac=1.0,
                                 arbiter=ArbiterSpec(every=2))],
        fm_fracs=(1.0,), policies=_tuner_specs()), db=db)
    assert fleet.chunked_step_count == 0
    for a, b in zip(plain.runs, fleet.runs, strict=True):
        assert (a.backend, b.backend) == ("tuned_sweep", "fleet")
        assert b.arbiter_log
        assert all(e["mode"] == "within_budget" for e in b.arbiter_log)
        _same(a.result, b.result, fm_sizes=True)
    assert sum(r.result.migrations for r in fleet.runs) > 0


def _lane_stress(app, churn):
    # the planner's process fan-out, two workers, one call
    rs = run(Experiment(
        name="stress",
        scenarios=[Scenario(trace=functools.partial(_stress_trace, s), name=f"stress{s}")
                   for s in range(STRESS_SCENARIOS)],
        fm_fracs=(0.5,)), parallelism=2)
    assert [r.scenario for r in rs.runs] == [f"stress{s}" for s in range(STRESS_SCENARIOS)]
    assert rs.chunked_step_count == 0
    assert sum(r.result.migrations for r in rs.runs) > 0


def _lane_null_injector(app, churn):
    # no fault spec builds no injector; a zero-rate spec injects nothing
    base = _churn_run(churn)
    zero = _churn_run(churn, sc={"faults": FaultSpec(seed=5)})
    for a, b in zip(base.runs, zero.runs, strict=True):
        assert a.fault_events is None
        assert not b.fault_events
        _same(a.result, b.result)


LANES = {
    "harvest_records": _lane_harvest_records,
    "tuned": _lane_tuned,
    "thrash": _lane_thrash,
    "admission": _lane_admission,
    "jax": _lane_jax,
    "fleet": _lane_fleet,
    "stress": _lane_stress,
    "null_injector": _lane_null_injector,
}


@pytest.mark.parametrize("lane", list(LANES))
def test_lane_matches_oracle(lane, app, churn):
    LANES[lane](app, churn)
