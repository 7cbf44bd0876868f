"""Engine performance harness: seed implementation vs incremental + sweep.

Measures the ``build_bench_db`` path and the TPP+Tuna closed-loop path end
to end, seed vs current engine:

1. **harvest** — collecting per-interval configuration vectors from an
   application trace at every probe fast-memory size. Seed: one
   ``simulate()`` per size over the reference (dense-rescan) pool.
   New: one untuned :class:`~repro.sim.api.Experiment`
   (``collect_configs=True``), which the :func:`repro.sim.api.run`
   planner executes as a single batched sweep across all sizes.
2. **db build** — populating the performance database over the harvested
   operating points. Seed: serial per-(config, fm_frac) reference-pool
   loop. New: :func:`repro.core.tuner.build_database`, one scenario per
   configuration through the same planner (batched sweep per record,
   process fan-out across scenarios).
3. **tuned path** — the paper's headline evaluation loop (TPP+Tuna,
   Figs. 3-8 / Tables 2-3): one closed-loop run per loss target. Seed:
   per-target ``simulate(..., tuner=...)`` over the reference pool. New:
   one experiment whose per-target :class:`~repro.sim.api.TunerSpec`
   policies ride a single tuned-sweep pass as live slices.
4. **thrash path** — the knee regime the Tuna model hunts (hot set ~2x
   the fast tier, rotating: reclaim demand reaches into same-interval
   promotions). Seed: per-size reference-pool loop. New: one untuned
   experiment executed as a single sweep pass, asserted chunked-loop-free
   via the ``RunSet.chunked_step_count`` provenance counter.
5. **admission path** — the same churn scenario under the TierBPF-style
   ``admission`` policy backend (registry-routed, per-candidate admission
   control layered on the TPP schedule). Seed: per-size reference-pool
   loop with the same policy. New: one experiment whose spec names the
   backend by kind only, executed as a single sweep pass — asserted
   bit-identical, actually rejecting candidates (``pm_admit_fail`` > 0),
   and chunked-loop-free, so the pluggable backends' sweep path cannot
   silently regress onto the per-size chunked loop.
6. **jax path** — the same churn scenario through the accelerator-native
   sweep backend (``Scenario(engine="jax")``, :mod:`repro.sim.jax_engine`,
   Pallas victim-partition kernel per ``pallas_mode()``). Seed side: the
   *numpy sweep* (the equivalence oracle), not the reference pool — the
   lane gates the device step against the oracle it must match bit-for-bit
   (stats, interval times, config vectors) before timing. On 2-core CI
   runners under interpret mode the ratio is informational headroom; the
   equivalence assertions are the contract.
7. **fleet path** — the tuned path's closed-loop runs wrapped as a
   single-tenant :class:`~repro.fleet.FleetScenario` at the full budget,
   tuned at every loss target. The degenerate case is the fleet layer's contract: the
   arbiter may only hold (``within_budget`` events in the
   ``arbiter_log``), and every run must be bit-identical to the bare
   tuned sweep — so the lane times (and ratio-gates) exactly the fleet
   scaffolding's overhead: trace merge, slice mapping, arbiter holds.
8. **stress section** — a fleet-sized experiment: 1000 tiny scenarios
   (150 in quick mode) through the :func:`repro.sim.api.run` planner and
   its process fan-out in one call. Correctness-gated (every scenario must
   complete, with zero chunked steps); wall clock is reported as
   ``stress_path_*`` keys, informational (there is no seed-side twin to
   ratio against).

Plus single-run engine throughput (intervals/sec) on the application
trace. Every path is asserted to produce bit-identical outputs (config
vectors, execution records, migration counters, interval times, fm-size
trajectories) before timing, so the speedup can never come from computing
something else. Results are appended as report rows and persisted to
``BENCH_engine.json`` at the repo root so later PRs can track the
trajectory.

CI quick mode / bench gate
--------------------------
``python -m benchmarks.bench_engine --quick`` runs a scaled-down
configuration (same code paths, smaller trace / fewer repeats) suitable
for a CI job; ``--gate BENCH_engine.json`` then compares the fresh
quick-mode timings against the committed baseline's ``quick_baseline``
section and exits non-zero on a >25% regression. The gate compares the
**new/seed wall-clock ratio** rather than absolute seconds: both sides
run on the same machine in the same job, so the ratio cancels runner
speed while still failing when the optimized path regresses relative to
the frozen seed implementation. ``--update-baseline`` refreshes the
committed baseline's ``quick_baseline`` section in place (run it on a
CI-class 2-core box). Mixed-mode baseline updates are refused:
``--update-baseline`` without ``--quick`` errors out (full runs rewrite
the top level themselves), quick mode refuses ``--out BENCH_engine.json``
(that would clobber the committed full baseline with quick medians), and
the gate refuses to compare a quick run against a baseline that has no
``quick_baseline`` section. Schema additions for the new lanes:
``jax_path_{seed_s,new_s,speedup,ratio}``, ``jax_sweep_chunked_steps``,
``jax_migrations``, ``jax_pallas_mode``,
``fleet_path_{seed_s,new_s,speedup,ratio}``, ``fleet_migrations``,
``fleet_sweep_chunked_steps``, and ``stress_scenarios``,
``stress_path_new_s``, ``stress_scenarios_per_s``.

The application trace is a self-contained deterministic stand-in for the
benchmark workloads (xsbench-scale RSS, skewed reuse, a migrating hot
front) — no multi-second workload generation inside the harness.
"""

from __future__ import annotations

import functools
import json
import os
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from benchmarks.common import DB_FM_FRACS, _representative_from, steady_from
from repro.core.microbench import generate_microbench
from repro.kernels.ops import pallas_mode
from repro.core.trace import IntervalAccess, Trace
from repro.core.tuner import TunaTuner, TunerConfig, build_database, scale_config
from repro.core.watermark import WatermarkController
from repro.fleet import ArbiterSpec, FleetScenario, TenantSpec
from repro.sim.api import Experiment, PolicySpec, Scenario, TunerSpec
from repro.sim.api import run as run_experiment

# the seed lanes deliberately pin the frozen pre-redesign implementation
# (the timing baseline), not the deprecation shim around it
from repro.sim.engine import _simulate as simulate
from repro.sim.workloads import thrash_trace
from repro.tiering.page_pool import TieredPagePool
from repro.tiering.policy import AdmissionTPPPolicy
from repro.tiering.reference_pool import ReferencePagePool

OUT_PATH = Path(__file__).resolve().parent.parent / "BENCH_engine.json"

# what build_bench_db harvests: representative fracs + probe fracs. The
# seed path runs one simulate() per entry — including the 1.0/0.9
# duplicates, exactly as representative_config + the probe loop do — while
# the new path sweeps the deduplicated union once.
REP_FRACS = (1.0, 0.95, 0.9, 0.8)
PROBE_FRACS = (1.0, 0.9, 0.75, 0.6, 0.45, 0.3)
HARVEST_FRACS = tuple(sorted(set(REP_FRACS + PROBE_FRACS), reverse=True))


@dataclass(frozen=True)
class BenchParams:
    """One benchmark configuration (full trajectory run vs CI quick run)."""

    quick: bool
    app_rss: int = 40_000
    app_intervals: int = 100
    n_intervals: int = 12  # micro-benchmark intervals per db record
    max_rss: int = 20_000
    repeats: int = 5  # best-of repeats for the timed sections
    # the tuned path's sections are short (hundreds of ms); more best-of
    # repeats ride out multi-second CPU-steal bursts on shared runners
    tuned_repeats: int = 6
    ips_repeats: int = 3
    max_configs: int | None = None  # cap on db operating points
    # loss-target vector for the closed-loop path: spread like the
    # Table 3 sensitivity sweep so the tuners actually shrink/grow
    tuned_targets: tuple = (0.02, 0.05, 0.10, 0.15, 0.25)
    tune_every: int = 3
    # thrash scenario: rotating hot set ~2x the mid-curve fast tier, the
    # fracs chosen so every size's reclaim digs into same-step promotions
    thrash_rss: int = 20_000
    thrash_intervals: int = 40
    thrash_fracs: tuple = (0.6, 0.45, 0.35, 0.25)
    thrash_repeats: int = 5
    # fleet-sized planner stress: scenario count for the stress section
    stress_scenarios: int = 1000


FULL = BenchParams(quick=False)
QUICK = BenchParams(
    quick=True,
    app_rss=16_000,
    app_intervals=48,
    n_intervals=8,
    max_rss=10_000,
    repeats=4,
    ips_repeats=2,
    max_configs=6,
    thrash_rss=8_000,
    thrash_intervals=16,
    thrash_repeats=4,
    stress_scenarios=150,
)


def _app_trace(rss: int, n_intervals: int, seed: int = 7) -> Trace:
    """Deterministic workload-like trace: a skewed-reuse resident set plus
    a hot front that migrates through the RSS (what makes pages churn).
    Sized like the xsbench benchmark workload (~26 K touched pages per
    interval over a 40 K-page RSS, ~100 intervals) in full mode."""
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
    """One fleet-stress workload: a tiny deterministic churn trace.

    Module-level (and invoked via ``functools.partial``) so the planner's
    process fan-out can pickle the factory instead of shipping arrays.
    """
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


def _seed_harvest(trace: Trace):
    """Seed path: one reference-pool simulate() per harvested size — with
    the representative/probe duplicates the seed build actually ran."""
    out = {}
    for f in REP_FRACS + PROBE_FRACS:
        res = simulate(trace, fm_frac=f, pool_factory=ReferencePagePool)
        out[f] = res.configs
    return out


def _new_harvest(trace: Trace):
    rs = run_experiment(
        Experiment(
            name="bench_harvest",
            scenarios=[Scenario(trace=trace)],
            fm_fracs=HARVEST_FRACS,
            collect_configs=True,
        )
    )
    return {float(r.fm_frac): r.result.configs for r in rs.runs}


def _operating_points(trace: Trace, by_frac, max_configs: int | None) -> list:
    configs = [
        _representative_from(steady_from(by_frac[f]), trace)
        for f in (1.0, 0.9, 0.8)
    ]
    for f in (0.75, 0.6, 0.45, 0.3):
        steady = steady_from(by_frac[f])
        configs.extend(steady[:: max(1, len(steady) // 2)][:2])
    return configs[:max_configs] if max_configs else configs


def _seed_build(configs, p: BenchParams):
    """The seed ``build_database``: one reference-pool ``simulate()`` per
    (config, fm_frac), serial — timing baseline AND record oracle."""
    from repro.core.perfdb import PerfDB, PerfRecord

    db = PerfDB()
    for cv in configs:
        trace = generate_microbench(
            scale_config(cv, p.max_rss), n_intervals=p.n_intervals
        )
        times = np.empty(DB_FM_FRACS.shape, dtype=np.float64)
        for i, f in enumerate(DB_FM_FRACS):
            if f >= 1.0 - 1e-9:
                times[i] = simulate(
                    trace.fast_only(), fm_frac=1.0,
                    pool_factory=ReferencePagePool,
                ).total_time
            else:
                times[i] = simulate(
                    trace, fm_frac=float(f), pool_factory=ReferencePagePool
                ).total_time
        db.add(PerfRecord(config=cv, fm_fracs=DB_FM_FRACS, times=times))
    db.build()
    return db


def _new_build(configs, p: BenchParams):
    # build_database picks serial vs process fan-out itself (None = auto);
    # that choice is part of the path under test
    return build_database(
        configs, fm_fracs=DB_FM_FRACS, n_intervals=p.n_intervals,
        max_rss_pages=p.max_rss, workers=None,
    )


def _mk_tuner(db, tau: float) -> TunaTuner:
    # k_neighbors=1: the bench db is deliberately tiny, and k-NN averaging
    # over it mixes distant records into every query — with k=1 the tuner
    # follows the nearest record's curve and genuinely actuates (watermark
    # moves + migrations), which is the behaviour worth timing
    return TunaTuner(
        db,
        WatermarkController(max_step_frac=0.05),
        TunerConfig(target_loss=tau, cooldown_windows=3, k_neighbors=1),
    )


def _per_size_tuned(trace: Trace, db, p: BenchParams, pool_factory):
    """The pre-sweep TPP+Tuna path: one closed-loop ``simulate()`` per
    loss target (what Figs. 3-8 / Tables 2-3 ran before the tuned sweep),
    over the seed pool (``ReferencePagePool``) or the incremental one."""
    return [
        simulate(
            trace, fm_frac=1.0, tuner=_mk_tuner(db, tau),
            tune_every=p.tune_every, pool_factory=pool_factory,
        )
        for tau in p.tuned_targets
    ]


def _new_tuned(trace: Trace, db, p: BenchParams):
    """New TPP+Tuna path: one declarative experiment whose per-target
    tuner specs (mirroring :func:`_mk_tuner`) ride one batched tuned
    sweep; the tuners themselves are constructed inside the run."""
    rs = run_experiment(
        Experiment(
            name="bench_tuned",
            scenarios=[Scenario(trace=trace)],
            fm_fracs=(1.0,),
            policies=[
                PolicySpec(
                    label=f"tau{tau:g}",
                    tuner=TunerSpec(
                        target_loss=tau,
                        tune_every=p.tune_every,
                        k_neighbors=1,
                        cooldown_windows=3,
                        max_step_frac=0.05,
                    ),
                )
                for tau in p.tuned_targets
            ],
        ),
        db=db,
    )
    return [r.result for r in rs.runs]


def _timed(fn) -> float:
    import gc

    gc.collect()  # don't charge the previous section's garbage to this one
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def _churn_lane(report, name, seed_fn, new_fn, check_pair, repeats,
                empty_msg):
    """Shared scaffold of the churn-scenario lanes (thrash, admission).

    Runs both sides once; asserts the sweep never dropped to the chunked
    loop (``RunSet.chunked_step_count`` provenance) and, via
    ``check_pair(seed_result, run_record) -> activity`` per (size) pair,
    that the outputs are bit-identical — raising ``empty_msg`` when the
    summed activity is zero (a lane that exercised nothing times the
    wrong thing). Then times interleaved best-of-``repeats`` and reports
    the three ``engine/{name}_path_*`` rows. Returns ``(seed_s, new_s,
    speedup, ratio, chunked, activity)`` with ``ratio`` the paired-median
    gate metric.
    """
    seed_runs = seed_fn()
    new_rs = new_fn()
    chunked = new_rs.chunked_step_count
    if chunked:
        raise AssertionError(
            f"engine bench: {name} sweep executed the chunked loop "
            f"{chunked} times"
        )
    activity = 0
    # strict: a planner regression that drops runs must fail the gate,
    # not shrink its coverage
    for r_seed, rec in zip(seed_runs, new_rs.runs, strict=True):
        activity += check_pair(r_seed, rec)
    if activity == 0:
        raise AssertionError(empty_msg)
    seed_ts, new_ts = [], []
    for _ in range(repeats):
        seed_ts.append(_timed(seed_fn))
        new_ts.append(_timed(new_fn))
    t_seed, t_new = min(seed_ts), min(new_ts)
    ratio = float(np.median([n / s for s, n in zip(seed_ts, new_ts)]))
    speedup = t_seed / t_new
    report(f"engine/{name}_path_seed", t_seed * 1e6, f"{t_seed:.2f}s")
    report(f"engine/{name}_path_new", t_new * 1e6, f"{t_new:.2f}s")
    report(
        f"engine/{name}_path_speedup", speedup * 1e6, f"{speedup:.2f}x"
    )
    return t_seed, t_new, speedup, ratio, chunked, activity


def run(report, params: BenchParams = FULL) -> dict:
    p = params
    trace = _app_trace(p.app_rss, p.app_intervals)

    # --- correctness gates: identical harvest vectors, identical records
    by_frac_seed = _seed_harvest(trace)
    by_frac_new = _new_harvest(trace)
    for f in HARVEST_FRACS:
        if by_frac_seed[f] != by_frac_new[f]:
            raise AssertionError("engine bench: harvest vectors diverge")
    configs = _operating_points(trace, by_frac_new, p.max_configs)
    db_seed = _seed_build(configs, p)
    db_new = _new_build(configs, p)
    for r_seed, r_new in zip(db_seed.records, db_new.records):
        if not np.array_equal(r_seed.times, r_new.times):
            raise AssertionError("engine bench: db records diverge")

    # --- correctness gate: the tuned (TPP+Tuna) path, counters + times +
    #     fm trajectories, seed per-target loop vs one tuned sweep
    tuned_seed = _per_size_tuned(trace, db_new, p, ReferencePagePool)
    tuned_new = _new_tuned(trace, db_new, p)
    tuned_migrations = 0
    for r_seed, r_new in zip(tuned_seed, tuned_new):
        if (
            r_seed.stats != r_new.stats
            or not np.array_equal(r_seed.interval_times, r_new.interval_times)
            or not np.array_equal(r_seed.fm_sizes, r_new.fm_sizes)
            or r_seed.configs != r_new.configs
        ):
            raise AssertionError("engine bench: tuned path outputs diverge")
        tuned_migrations += r_new.migrations
    if tuned_migrations == 0:
        # a tuned path without watermark actuation times the wrong thing
        raise AssertionError("engine bench: tuned path exercised no migration")

    # --- single-run engine throughput on the application trace
    ips_seed = len(trace) / min(
        _timed(lambda: simulate(trace, fm_frac=0.6,
                                pool_factory=ReferencePagePool))
        for _ in range(p.ips_repeats)
    )
    ips_new = len(trace) / min(
        _timed(lambda: simulate(trace, fm_frac=0.6,
                                pool_factory=TieredPagePool))
        for _ in range(p.ips_repeats)
    )
    report("engine/intervals_per_s_seed", 1e6 / ips_seed, f"{ips_seed:.1f}/s")
    report("engine/intervals_per_s_new", 1e6 / ips_new, f"{ips_new:.1f}/s")

    # --- the build_bench_db path: harvest + db build, best of N,
    #     interleaved so machine noise hits both sides alike
    seed_ts, new_ts = [], []
    for _ in range(p.repeats):
        seed_ts.append(
            _timed(lambda: (_seed_harvest(trace), _seed_build(configs, p)))
        )
        new_ts.append(
            _timed(lambda: (_new_harvest(trace), _new_build(configs, p)))
        )
    t_seed, t_new = min(seed_ts), min(new_ts)
    speedup = t_seed / t_new
    # the gate metric: per-repeat (seed, new) pairs run back to back, so
    # each pair shares the machine's state; the *median* paired ratio is
    # robust on both sides, where a min would record whichever pairing a
    # noise burst skewed furthest
    db_ratio = float(np.median([n / s for s, n in zip(seed_ts, new_ts)]))
    report("engine/bench_db_path_seed", t_seed * 1e6, f"{t_seed:.2f}s")
    report("engine/bench_db_path_new", t_new * 1e6, f"{t_new:.2f}s")
    report("engine/bench_db_path_speedup", speedup * 1e6, f"{speedup:.2f}x")

    # --- the TPP+Tuna path: per-target closed loops (seed pool AND the
    #     pre-sweep incremental-pool loop) vs one tuned sweep
    tuned_seed_ts, tuned_per_ts, tuned_new_ts = [], [], []
    for _ in range(p.tuned_repeats):
        tuned_seed_ts.append(
            _timed(lambda: _per_size_tuned(trace, db_new, p, ReferencePagePool))
        )
        tuned_per_ts.append(
            _timed(lambda: _per_size_tuned(trace, db_new, p, TieredPagePool))
        )
        tuned_new_ts.append(_timed(lambda: _new_tuned(trace, db_new, p)))
    tt_seed, tt_per, tt_new = (
        min(tuned_seed_ts), min(tuned_per_ts), min(tuned_new_ts)
    )
    tuned_ratio = float(
        np.median([n / s for s, n in zip(tuned_seed_ts, tuned_new_ts)])
    )
    tuned_speedup = tt_seed / tt_new
    report("engine/tuned_path_seed", tt_seed * 1e6, f"{tt_seed:.2f}s")
    report("engine/tuned_path_per_size", tt_per * 1e6, f"{tt_per:.2f}s")
    report("engine/tuned_path_new", tt_new * 1e6, f"{tt_new:.2f}s")
    report(
        "engine/tuned_path_speedup", tuned_speedup * 1e6,
        f"{tuned_speedup:.2f}x",
    )

    # --- the thrash path: the migration-failure knee (hot set ~2x the
    #     fast tier, rotating). Seed: per-size reference loop. New: one
    #     fixed-size sweep, which must stay on the bulk policy step —
    #     zero chunked-loop executions — while reproducing the seed
    #     outputs exactly.
    thrash_tr = thrash_trace(
        n_intervals=p.thrash_intervals, rss_pages=p.thrash_rss
    )
    thrash_fracs = np.asarray(p.thrash_fracs, dtype=np.float64)

    def _seed_thrash():
        return [
            simulate(
                thrash_tr, fm_frac=float(f), pool_factory=ReferencePagePool
            )
            for f in thrash_fracs
        ]

    def _new_thrash():
        return run_experiment(
            Experiment(
                name="bench_thrash",
                scenarios=[Scenario(trace=thrash_tr)],
                fm_fracs=tuple(float(f) for f in thrash_fracs),
            )
        )

    def _check_thrash(r_seed, rec):
        if r_seed.stats != rec.result.stats or not np.array_equal(
            r_seed.interval_times, rec.result.interval_times
        ):
            raise AssertionError("engine bench: thrash path outputs diverge")
        if rec.fault_events is not None:
            # the gated lanes time the fault-free hot path: a non-null
            # injector here means the timing includes fault bookkeeping
            raise AssertionError("engine bench: fault injector engaged")
        return r_seed.migrations

    th_seed, th_new, thrash_speedup, thrash_ratio, thrash_chunked, \
        thrash_migrations = _churn_lane(
            report, "thrash", _seed_thrash, _new_thrash, _check_thrash,
            p.thrash_repeats,
            # without churn the scenario is not in the thrash regime at all
            empty_msg="engine bench: thrash scenario did not migrate",
        )

    # --- the admission path: the registry-routed TierBPF-style backend on
    #     the same churn scenario. Seed: per-size reference loop with the
    #     same policy. New: one sweep pass named by PolicySpec.kind alone —
    #     bit-identical outputs, really rejecting candidates, and never on
    #     the chunked loop.
    def _seed_admission():
        return [
            simulate(
                thrash_tr, fm_frac=float(f),
                policy=AdmissionTPPPolicy(),
                pool_factory=ReferencePagePool,
            )
            for f in thrash_fracs
        ]

    def _new_admission():
        return run_experiment(
            Experiment(
                name="bench_admission",
                scenarios=[Scenario(trace=thrash_tr)],
                fm_fracs=tuple(float(f) for f in thrash_fracs),
                policies=[PolicySpec(kind="admission")],
                collect_configs=True,
            )
        )

    def _check_admission(r_seed, rec):
        if (
            r_seed.stats != rec.result.stats
            or not np.array_equal(
                r_seed.interval_times, rec.result.interval_times
            )
            or r_seed.configs != rec.result.configs
        ):
            raise AssertionError(
                "engine bench: admission path outputs diverge"
            )
        if rec.fault_events is not None:
            raise AssertionError("engine bench: fault injector engaged")
        return int(sum(c.pm_admit_fail for c in rec.result.configs))

    adm_seed, adm_new_t, adm_speedup, adm_ratio, adm_chunked, \
        adm_rejects = _churn_lane(
            report, "admission", _seed_admission, _new_admission,
            _check_admission, p.thrash_repeats,
            # without rejections the admission stage timed nothing at all
            empty_msg="engine bench: admission policy rejected no candidates",
        )

    # --- the jax path: the same churn scenario through the
    #     accelerator-native sweep backend. Seed side is the *numpy sweep*
    #     (the equivalence oracle the device step must match bit-for-bit),
    #     not the reference pool — this lane gates the jitted JAX step +
    #     Pallas victim-partition kernel against the oracle. Equivalence
    #     (stats, interval times, config vectors) is asserted on the first
    #     pair of runs, before any timing; the first new-side call also
    #     warms the jit cache so compile time stays out of the record. On
    #     2-core CI runners under interpret mode the speedup is
    #     informational headroom — the equivalence assertions are the
    #     contract the gate protects.
    def _seed_jax():
        return run_experiment(
            Experiment(
                name="bench_jax_oracle",
                scenarios=[Scenario(trace=thrash_tr, engine="numpy")],
                fm_fracs=tuple(float(f) for f in thrash_fracs),
                collect_configs=True,
            )
        ).runs

    def _new_jax():
        return run_experiment(
            Experiment(
                name="bench_jax",
                scenarios=[Scenario(trace=thrash_tr, engine="jax")],
                fm_fracs=tuple(float(f) for f in thrash_fracs),
                collect_configs=True,
            )
        )

    def _check_jax(r_seed, rec):
        if r_seed.backend != "sweep" or rec.backend != "jax_sweep":
            raise AssertionError(
                "engine bench: jax path routed to the wrong backends "
                f"({r_seed.backend!r} vs {rec.backend!r})"
            )
        if (
            r_seed.result.stats != rec.result.stats
            or not np.array_equal(
                r_seed.result.interval_times, rec.result.interval_times
            )
            or r_seed.result.configs != rec.result.configs
        ):
            raise AssertionError(
                "engine bench: jax path outputs diverge from the numpy sweep"
            )
        return rec.result.migrations

    jx_seed, jx_new, jax_speedup, jax_ratio, jax_chunked, \
        jax_migrations = _churn_lane(
            report, "jax", _seed_jax, _new_jax, _check_jax,
            p.thrash_repeats,
            # without churn the lane never exercises the device commit path
            empty_msg="engine bench: jax path scenario did not migrate",
        )

    # --- the fleet path: the tuned closed-loop runs as a single-tenant
    #     FleetScenario at budget_frac=1.0 — the degenerate case the fleet
    #     layer promises is free. With one tenant and the whole budget the
    #     arbiter can only ever hold (within_budget), so every tuned run
    #     must be bit-identical to the plain tuned sweep it wraps (stats,
    #     interval times, fm trajectories, config vectors), while the
    #     arbiter_log proves arbitration actually stepped. Times the fleet
    #     scaffolding (trace merge, slice mapping, arbiter holds) against
    #     the bare tuned sweep, and gates the ratio so the wrapper's
    #     overhead cannot silently grow.
    fleet_policies = [
        PolicySpec(
            label=f"tau{tau:g}",
            tuner=TunerSpec(
                target_loss=tau,
                tune_every=p.tune_every,
                k_neighbors=1,
                cooldown_windows=3,
                max_step_frac=0.05,
            ),
        )
        for tau in p.tuned_targets
    ]

    def _seed_fleet():
        return run_experiment(
            Experiment(
                name="bench_fleet_oracle",
                scenarios=[Scenario(trace=trace)],
                fm_fracs=(1.0,),
                policies=fleet_policies,
            ),
            db=db_new,
        ).runs

    def _new_fleet():
        return run_experiment(
            Experiment(
                name="bench_fleet",
                scenarios=[
                    FleetScenario(
                        tenants=(TenantSpec(trace=trace, name="solo"),),
                        name="fleet",
                        budget_frac=1.0,
                        arbiter=ArbiterSpec(every=2),
                    )
                ],
                fm_fracs=(1.0,),
                policies=fleet_policies,
            ),
            db=db_new,
        )

    def _check_fleet(r_seed, rec):
        if r_seed.backend != "tuned_sweep" or rec.backend != "fleet":
            raise AssertionError(
                "engine bench: fleet path routed to the wrong backends "
                f"({r_seed.backend!r} vs {rec.backend!r})"
            )
        if not rec.arbiter_log:
            raise AssertionError(
                "engine bench: fleet path ran without arbitration events"
            )
        if any(e["mode"] != "within_budget" for e in rec.arbiter_log):
            raise AssertionError(
                "engine bench: single-tenant full-budget fleet actuated "
                "the arbiter"
            )
        if (
            r_seed.result.stats != rec.result.stats
            or not np.array_equal(
                r_seed.result.interval_times, rec.result.interval_times
            )
            or not np.array_equal(r_seed.result.fm_sizes, rec.result.fm_sizes)
            or r_seed.result.configs != rec.result.configs
        ):
            raise AssertionError(
                "engine bench: fleet degenerate case diverges from the "
                "tuned sweep"
            )
        return rec.result.migrations

    fl_seed, fl_new, fleet_speedup, fleet_ratio, fleet_chunked, \
        fleet_migrations = _churn_lane(
            report, "fleet", _seed_fleet, _new_fleet, _check_fleet,
            p.thrash_repeats,
            # a fleet lane whose tuners never actuate times an idle wrapper
            empty_msg="engine bench: fleet path scenario did not migrate",
        )

    # --- fleet-sized stress: the run() planner and its process fan-out at
    #     experiment scale — p.stress_scenarios tiny scenarios (1000 full,
    #     scaled down in quick mode) in one call. Correctness-gated: every
    #     scenario must come back, all on the bulk sweep path, with real
    #     migration activity. Wall clock lands in the informational
    #     ``stress_path_*`` keys — there is no seed-side twin to ratio
    #     against, so the timing gate does not apply to this section.
    stress_n = int(p.stress_scenarios)
    stress_scenarios = [
        Scenario(trace=functools.partial(_stress_trace, s), name=f"stress{s}")
        for s in range(stress_n)
    ]

    def _stress_run():
        return run_experiment(
            Experiment(
                name="bench_stress",
                scenarios=stress_scenarios,
                fm_fracs=(0.5,),
            )
        )

    stress_box = []
    stress_t = _timed(lambda: stress_box.append(_stress_run()))
    stress_rs = stress_box[0]
    if len(stress_rs.runs) != stress_n:
        raise AssertionError(
            f"engine bench: stress fan-out returned {len(stress_rs.runs)} "
            f"of {stress_n} scenarios"
        )
    if stress_rs.chunked_step_count != 0:
        raise AssertionError(
            "engine bench: stress sweep fell off the bulk policy step"
        )
    stress_migrations = sum(r.result.migrations for r in stress_rs.runs)
    if stress_migrations <= 0:
        raise AssertionError("engine bench: stress scenarios did not migrate")
    report(
        "engine/stress_path_new", stress_t * 1e6,
        f"{stress_n} scenarios in {stress_t:.2f}s",
    )

    results = {
        "quick": p.quick,
        "n_configs": len(configs),
        "n_harvest_fracs": len(HARVEST_FRACS),
        "n_db_fm_fracs": int(DB_FM_FRACS.size),
        "n_intervals": p.n_intervals,
        "workers_auto": True,
        "cpus": os.cpu_count(),
        # the gated lanes run with faults=None: the injector's only cost
        # on these paths is the is-None check, and the >25% ratio gate
        # (check_gate) holds that overhead to the committed baseline
        "null_injector_gated": True,
        "harvest_and_records_identical": True,
        "tuned_outputs_identical": True,
        "tuned_targets": list(p.tuned_targets),
        "tune_every": p.tune_every,
        "intervals_per_s_seed": round(ips_seed, 2),
        "intervals_per_s_new": round(ips_new, 2),
        "bench_db_path_seed_s": round(t_seed, 3),
        "bench_db_path_new_s": round(t_new, 3),
        "bench_db_path_speedup": round(speedup, 2),
        "bench_db_path_ratio": round(db_ratio, 4),
        "tuned_migrations": int(tuned_migrations),
        "tuned_path_seed_s": round(tt_seed, 3),
        "tuned_path_per_size_s": round(tt_per, 3),
        "tuned_path_new_s": round(tt_new, 3),
        "tuned_path_speedup": round(tuned_speedup, 2),
        "tuned_path_ratio": round(tuned_ratio, 4),
        "thrash_rss": p.thrash_rss,
        "thrash_intervals": p.thrash_intervals,
        "thrash_fracs": list(p.thrash_fracs),
        "thrash_migrations": int(thrash_migrations),
        "thrash_sweep_chunked_steps": int(thrash_chunked),
        "thrash_path_seed_s": round(th_seed, 3),
        "thrash_path_new_s": round(th_new, 3),
        "thrash_path_speedup": round(thrash_speedup, 2),
        "thrash_path_ratio": round(thrash_ratio, 4),
        "admission_rejects": int(adm_rejects),
        "admission_sweep_chunked_steps": int(adm_chunked),
        "admission_path_seed_s": round(adm_seed, 3),
        "admission_path_new_s": round(adm_new_t, 3),
        "admission_path_speedup": round(adm_speedup, 2),
        "admission_path_ratio": round(adm_ratio, 4),
        "jax_pallas_mode": pallas_mode(),
        "jax_migrations": int(jax_migrations),
        "jax_sweep_chunked_steps": int(jax_chunked),
        "jax_path_seed_s": round(jx_seed, 3),
        "jax_path_new_s": round(jx_new, 3),
        "jax_path_speedup": round(jax_speedup, 2),
        "jax_path_ratio": round(jax_ratio, 4),
        "fleet_migrations": int(fleet_migrations),
        "fleet_sweep_chunked_steps": int(fleet_chunked),
        "fleet_path_seed_s": round(fl_seed, 3),
        "fleet_path_new_s": round(fl_new, 3),
        "fleet_path_speedup": round(fleet_speedup, 2),
        "fleet_path_ratio": round(fleet_ratio, 4),
        "stress_scenarios": stress_n,
        "stress_path_new_s": round(stress_t, 3),
        "stress_scenarios_per_s": round(stress_n / stress_t, 2),
    }
    if not p.quick:
        # full runs own the committed baseline; they keep the CI quick
        # section (written by --quick --update-baseline) intact
        committed = (
            json.loads(OUT_PATH.read_text()) if OUT_PATH.exists() else {}
        )
        if committed.get("quick_baseline") is not None:
            results["quick_baseline"] = committed["quick_baseline"]
        OUT_PATH.write_text(json.dumps(results, indent=2) + "\n")
    return results


GATED_PATHS = (
    "bench_db_path", "tuned_path", "thrash_path", "admission_path",
    "jax_path", "fleet_path",
)


def check_gate(fresh: dict, baseline: dict, margin: float = 1.25) -> list[str]:
    """Compare a fresh quick-mode run against the committed baseline.

    The committed ``*_ratio`` baselines should be recorded on (or with
    headroom for) the CI runner class — ``--update-baseline`` on a
    representative box, or hand-set to the upper end of a few calibration
    runs' medians — so that runner-to-runner noise sits inside the
    baseline and the ``margin`` stays reserved for real regressions.

    Returns a list of failure messages (empty = gate passes). The metric
    is the optimized/seed wall-clock ratio per gated path — the *median*
    of the paired (same-repeat, back-to-back) per-repeat ratios, so
    runner speed cancels and single noise bursts cannot skew the record —
    and the gate fails exactly when the optimized engine got >``margin``x
    slower *relative to the frozen seed implementation* than the
    committed baseline says it should be.
    """
    if fresh.get("quick") and "quick_baseline" not in baseline:
        # a quick run ratioed against full-mode medians gates CI on the
        # wrong machine class and workload scale — refuse outright
        return [
            "baseline has no 'quick_baseline' section to compare this "
            "quick run against; record one with `bench_engine --quick "
            "--update-baseline` (mixed quick-vs-full comparison refused)"
        ]
    base = baseline.get("quick_baseline") or baseline
    failures = []
    for key in GATED_PATHS:
        b_ratio = base.get(f"{key}_ratio")
        f_ratio = fresh.get(f"{key}_ratio")
        if not b_ratio or not f_ratio:
            failures.append(f"{key}: baseline or fresh ratio missing")
            continue
        if f_ratio > b_ratio * margin:
            failures.append(
                f"{key}: new/seed ratio {f_ratio:.3f} exceeds baseline "
                f"{b_ratio:.3f} by more than {margin:.2f}x"
            )
    return failures


def _csv_report(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="scaled-down CI configuration")
    ap.add_argument("--gate", metavar="BASELINE_JSON",
                    help="fail (exit 1) on >25%% regression vs this "
                         "baseline's quick section")
    ap.add_argument("--out", metavar="PATH",
                    help="where to write the fresh results JSON "
                         "(default: BENCH_engine.json in full mode, "
                         "BENCH_engine.quick.json in quick mode)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="merge this quick run into BENCH_engine.json's "
                         "'quick_baseline' section (full runs rewrite the "
                         "top level themselves)")
    args = ap.parse_args(argv)

    if args.update_baseline and not args.quick:
        ap.error(
            "--update-baseline is quick-mode only: it rewrites the "
            "committed baseline's quick_baseline section from this run's "
            "medians. Full runs rewrite the top level themselves; mixing "
            "the modes would gate CI against the wrong machine class. "
            "Re-run with --quick."
        )
    if args.quick and args.out and Path(args.out).resolve() == OUT_PATH:
        ap.error(
            f"refusing to overwrite {OUT_PATH.name} with quick-mode "
            "results: the committed file holds the full-mode baseline. "
            "Use --update-baseline to refresh its quick_baseline section, "
            "or pick a different --out path."
        )

    params = QUICK if args.quick else FULL
    results = run(_csv_report, params)

    if args.quick and args.update_baseline:
        committed = {}
        if OUT_PATH.exists():
            committed = json.loads(OUT_PATH.read_text())
        committed["quick_baseline"] = results
        OUT_PATH.write_text(json.dumps(committed, indent=2) + "\n")
        print(f"# baseline updated: {OUT_PATH}")

    out = args.out or (None if not args.quick else "BENCH_engine.quick.json")
    if out:
        Path(out).write_text(json.dumps(results, indent=2) + "\n")
        print(f"# results written: {out}")

    if args.gate:
        baseline = json.loads(Path(args.gate).read_text())
        failures = check_gate(results, baseline)
        if failures:
            for msg in failures:
                print(f"BENCH GATE FAIL: {msg}")
            return 1
        print("# bench gate: OK")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
