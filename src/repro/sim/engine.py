"""Interval-driven execution engine.

Runs a page-access trace through the real tiering stack (pool + policy +
watermarks) and accumulates time from the cost model. Used for three jobs:

1. executing the Tuna **micro-benchmark** across fast-memory sizes to build
   the performance database (offline component);
2. executing **application workloads** (BFS/SSSP/...) to evaluate model
   accuracy and runtime tuning (the paper's evaluation);
3. executing workloads **with the Tuna tuner in the loop** (TPP+Tuna).

Runs are described with :class:`repro.sim.api.Scenario` /
:class:`repro.sim.api.Experiment` and executed through
:func:`repro.sim.api.run`, whose planner falls back to the per-size engine
loop here (:func:`_simulate`) only for specs the batched sweeps cannot
absorb (custom ``pool_factory``, non-TPP policies). The tests call
:func:`_simulate` directly as the per-size oracle.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from repro.core.telemetry import ConfigVector, IntervalProfiler
from repro.core.trace import Trace
from repro.core.tuner import TunaTuner
from repro.sim.faults import FaultInjector, FaultSpec
from repro.sim.costmodel import (
    HardwareProfile,
    IntervalCosts,
    OPTANE_LIKE,
    absorb_cache,
    effective_mlp,
    interval_time,
)
from repro.tiering.page_pool import Tier, TieredPagePool
from repro.tiering.policy import MigrationPolicy, TPPPolicy


@dataclass
class SimResult:
    name: str
    total_time: float
    interval_times: np.ndarray
    configs: list  # ConfigVector per interval
    fm_sizes: np.ndarray  # effective fm size (pages) per interval
    stats: dict  # final pool counters
    costs: list = field(default_factory=list)  # IntervalCosts per interval

    @property
    def migrations(self) -> int:
        return self.stats["pgpromote_success"] + (
            self.stats["pgdemote_kswapd"] + self.stats["pgdemote_direct"]
        )


def _simulate(
    trace: Trace,
    fm_frac: float = 1.0,
    policy: MigrationPolicy | None = None,
    hw: HardwareProfile = OPTANE_LIKE,
    hw_capacity_pages: int | None = None,
    tuner: TunaTuner | None = None,
    tune_every: int | None = None,
    seed: int = 0,
    pool_factory=TieredPagePool,
    faults: FaultSpec | FaultInjector | None = None,
) -> SimResult:
    """Run ``trace`` with the fast tier sized at ``fm_frac`` of its RSS.

    ``hw_capacity_pages`` defaults to the trace RSS (the paper initializes
    fast memory to the workload's peak consumption via the GRUB memory map,
    then *shrinks* it with watermarks). If a ``tuner`` is given, it is
    stepped every ``tune_every`` intervals (the 2.5 s tuning interval mapped
    onto profiling intervals) and drives the watermarks itself.
    ``pool_factory`` swaps the pool implementation (the equivalence tests
    run the same trace through
    :class:`repro.tiering.reference_pool.ReferencePagePool`).
    ``faults`` (a :class:`repro.sim.faults.FaultSpec` or a pre-built
    injector) turns on the deterministic fault model; ``None`` keeps the
    exact fault-free hot path.
    """
    if policy is None:
        policy = TPPPolicy()
    inj: FaultInjector | None = None
    if faults is not None:
        inj = faults if isinstance(faults, FaultInjector) else FaultInjector(faults)
        policy.fault_injector = inj
    cap = int(hw_capacity_pages or trace.rss_pages)
    pool = pool_factory(
        num_pages=trace.rss_pages,
        hw_capacity=cap,
        page_bytes=hw.page_bytes,
        seed=seed,
    )
    pool.set_fm_size(int(round(fm_frac * cap)))
    if trace.slow_pages is not None:
        pool.place(trace.slow_pages, Tier.SLOW)
    if tuner is not None:
        tuner.bind_pool(pool, cap)
        if inj is not None:
            inj.wire_tuner(tuner)
    profiler = IntervalProfiler(
        hot_thr=getattr(policy, "hot_thr", 4), num_threads=trace.num_threads
    )
    times = []
    fm_sizes = []
    configs: list[ConfigVector] = []
    costs: list[IntervalCosts] = []
    t_now = 0.0
    for i, ia in enumerate(trace):
        # on-chip cache absorbs re-references to the hottest pages before
        # they reach either memory tier
        counts_mem = absorb_cache(ia.counts, hw.llc_pages)
        (pacc_f, pacc_s, ptouch_f, ptouch_s, warm_pg, warm_tc) = (
            pool.apply_accesses(
                ia.pages, counts_mem, ia.touches,
                touch_cap=getattr(policy, "hot_thr", 4),
            )
        )
        # the profiler reports fault-like touches (what the paper's runtime
        # library measures via hint faults / perf counters)
        profiler.record_accesses(ptouch_f, ptouch_s, ia.ops,
                                 cachelines=pacc_f + pacc_s,
                                 warm_pages=warm_pg, warm_touches=warm_tc)
        before_direct = pool.stats.pgdemote_direct
        if inj is not None:
            inj.begin_interval(pool)
            base_kb = pool.kswapd_batch
            eff_kb = inj.kswapd_budget(pool, base_kb)
            if eff_kb != base_kb:
                pool.kswapd_batch = eff_kb
            outcome = policy.step(pool, ia.pages)
            if eff_kb != base_kb:
                pool.kswapd_batch = base_kb
        else:
            outcome = policy.step(pool, ia.pages)
        profiler.record_policy(outcome)
        mlp_eff = effective_mlp(counts_mem, hw.mlp, trace.num_threads)
        cost = interval_time(
            hw,
            pacc_f=pacc_f,
            pacc_s=pacc_s,
            ops=ia.ops,
            pm_pr=outcome.pm_pr,
            pm_de=outcome.pm_de,
            pm_fail=outcome.pm_fail,
            direct_reclaimed=pool.stats.pgdemote_direct - before_direct,
            mlp_eff=mlp_eff,
            num_threads=trace.num_threads,
            rand_frac=ia.rand_frac,
        )
        cv = profiler.finish(pool)
        pool.end_interval()
        t_now += cost.total
        times.append(cost.total)
        costs.append(cost)
        fm_sizes.append(pool.effective_fm_size)
        configs.append(cv)
        if tuner is not None and tune_every and (i + 1) % tune_every == 0:
            window = costs[-tune_every:]
            acc = sum(
                c.pacc_f + c.pacc_s for c in configs[-tune_every:]
            )
            tpa = sum(c.total for c in window) / max(acc, 1)
            if inj is not None:
                cv_t, tpa, ok = inj.telemetry(pool, cv, tpa)
                tuner.step(cv_t, t=t_now, measured_tpa=tpa, telemetry_ok=ok)
            else:
                tuner.step(cv, t=t_now, measured_tpa=tpa)
    return SimResult(
        name=trace.name,
        total_time=float(np.sum(times)),
        interval_times=np.array(times),
        configs=configs,
        fm_sizes=np.array(fm_sizes, dtype=np.int64),
        stats=pool.stats.snapshot(),
        costs=costs,
    )


def run_trace(
    trace: Trace,
    fm_frac: float,
    hw: HardwareProfile = OPTANE_LIKE,
    hot_thr: int = 4,
) -> float:
    """Execution-time backend used to build the performance database."""
    return _simulate(
        trace, fm_frac=fm_frac, policy=TPPPolicy(hot_thr=hot_thr), hw=hw
    ).total_time
