"""Batched fast-memory-size sweep engine (the offline database hot path
and the TPP+Tuna closed-loop evaluation path).

This module is the **execution backend layer** of the unified experiment
API: runs are described declaratively with
:class:`repro.sim.api.Scenario` / :class:`repro.sim.api.Experiment` and
executed through :func:`repro.sim.api.run`, whose planner dispatches onto
the batched sweeps here (:func:`_sweep_fm_fracs` for untuned size vectors,
:func:`_sweep_tuned` for tuner-in-the-loop slices) and falls back to the
per-size engine loop (:func:`repro.sim.engine._simulate`) only for specs
the sweeps cannot absorb.

Tuna's offline component executes the same micro-benchmark trace at ~21
fast-memory sizes (paper Sections 3.3/5). Running :func:`repro.sim.engine.
_simulate` once per size repeats every size-independent computation — trace
iteration, LLC absorption, MLP estimation, and the whole hotness bookkeeping
— 21 times. This module simulates **one trace across the whole size vector
in a single pass**:

* page touches are trace-driven, so per-page heat and the interval touch
  counters are *identical at every size*: one shared
  :class:`~repro.tiering.page_pool.LazyHeat` and one shared dense touch
  array serve all sizes;
* only tier occupancy differs per size: it lives in one stacked
  ``[n_sizes, rss_pages]`` array, and each size's policy steps over a
  lightweight slice pool (:meth:`TieredPagePool._shared_slice`) that views
  its row — the *same* ``TPPPolicy`` code the per-size engine runs, so the
  sweep cannot drift semantically;
* per-interval tier classification of the touched pages is one batched
  ``[n_sizes, n_touched]`` gather instead of ``n_sizes`` passes;
* the per-size TPP promote/reclaim schedules are computed in **one
  vectorized policy decision batch per interval**
  (:meth:`~repro.tiering.policy.TPPPolicy.step_batch` over stacked
  watermark/free-page vectors), so the policy layer does not pay
  ``n_sizes`` Python loops either;
* every size commits its schedule through the pool's bulk step — **in
  every regime, including thrash**. When a size's reclaim demand reaches
  into pages promoted earlier in the same step (watermarks near capacity,
  candidate counts far beyond the headroom, kswapd starved — exactly the
  knee region the Tuna model hunts), victim identities are resolved
  against the schedule's availability horizons in one merge per slice
  (:func:`repro.tiering.page_pool._resolve_step_victims`) instead of
  dropping to the per-size chunked loop. Sweeps are chunked-loop-free end
  to end; the policy instance's per-instance ``chunked_steps`` counter
  records any fallback executions (surfaced by the unified API as
  ``RunSet.chunked_step_count``) and the equivalence tests assert it
  stays zero.

Policies are pluggable: :func:`_sweep_fm_fracs` / :func:`_sweep_tuned`
accept any *batchable* :class:`~repro.tiering.policy.MigrationPolicy`
instance via ``policy=`` (default: :class:`~repro.tiering.policy.
TPPPolicy`); the :mod:`repro.sim.api` planner constructs it from the
``POLICIES`` registry, so admission-controlled and thrash-responsive
backends ride the exact same vectorized decision batch.

Tuned-sweep mode (:func:`_sweep_tuned`)
---------------------------------------
Each size-slice can carry **live actuation state**: a
:class:`~repro.core.tuner.TunaTuner` + :class:`~repro.core.watermark.
WatermarkController` pair per slice, described by a :class:`TunedSlice`.
The tuner is stepped every ``tune_every`` intervals with that slice's
telemetry (config vector + measured time-per-access window) and actuates
*its own slice's* watermarks — so per-slice effective fast-memory sizes
change mid-run while the trace is still swept once. Watermark moves
re-partition the stacked tiers row-locally; the shared global demotion
ranking is trace-driven (heat + interval touches) and therefore stays
valid across every slice's effective capacity — each slice consumes it
through its own cursor, exactly as in the fixed-size sweep. A slice with
``tuner=None`` is a plain fixed-size run, which is how the TPP-only
baseline rides along in the same pass. Results come back as one
:class:`~repro.sim.engine.SimResult` per slice, bit-exact against
``_simulate(trace, fm_frac=..., tuner=..., tune_every=...)`` — migration
counters, interval times, config vectors, per-interval fm sizes, tuner
decisions and watermark event logs — which
``tests/test_engine_equivalence.py`` asserts (anchored, like every engine
path, on the frozen :class:`~repro.tiering.reference_pool.
ReferencePagePool` golden model).

Exactness: every per-size arithmetic sequence matches a standalone
``_simulate(trace, fm_frac=f)`` bit for bit (integer counters; float times),
which ``tests/test_engine_equivalence.py`` asserts.

Experiment results have a serialized form: the versioned **RunSet JSON schema**
(``tuna-runset-v2`` — spec echo incl. policy ``params``, per-run results,
tuner decisions, watermark logs, ``chunked_step_count`` provenance),
documented in full in the :mod:`repro.sim.api` module docstring and
round-trip-tested by ``tests/test_api.py``. Speed is measured on the
chip by the ``bench/`` cells.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core.trace import Trace
from repro.sim.costmodel import (
    HardwareProfile,
    OPTANE_LIKE,
    absorb_cache,
    effective_mlp,
    interval_time,
)
from repro.tiering.page_pool import (
    LazyGrankBox,
    LazyHeat,
    Tier,
    TieredPagePool,
)
from repro.tiering.policy import MigrationPolicy, TPPPolicy


@dataclass
class SweepResult:
    """Per-size outcome of one batched sweep."""

    name: str
    fm_fracs: np.ndarray  # [n_sizes]
    interval_times: np.ndarray  # [n_sizes, n_intervals]
    stats: list  # final pool counter snapshot per size
    configs: list | None = None  # per size: ConfigVector per interval
    costs: list | None = None  # per size: IntervalCosts per interval

    @property
    def total_times(self) -> np.ndarray:
        return self.interval_times.sum(axis=1)


@dataclass
class TunedSlice:
    """One slice of a tuned sweep: a starting fast-memory fraction plus
    optional live actuation state.

    ``tuner`` (with its :class:`~repro.core.watermark.WatermarkController`,
    which may be constructed unbound — the sweep binds it to the slice's
    pool) is stepped every ``tune_every`` profiling intervals, mirroring
    ``_simulate(trace, fm_frac=fm_frac, tuner=tuner,
    tune_every=tune_every)``. ``tuner=None`` gives a plain fixed-size run
    (the TPP-only baseline slice).
    """

    fm_frac: float = 1.0
    tuner: object | None = None  # TunaTuner (kept untyped: no import cycle)
    tune_every: int | None = None


def _hot_sorted(pages: np.ndarray, acc_now: np.ndarray, hot_thr: int) -> np.ndarray:
    """The interval's promotion candidates, hottest first, stable.

    Touch counts are size-independent, so this order is computed once per
    interval; each size keeps its slow-tier subset (subsets preserve it).
    """
    hot_mask = acc_now >= hot_thr
    hot = pages[hot_mask]
    acc_hot = acc_now[hot_mask]
    if not acc_hot.size:
        return hot
    vmax = int(acc_hot.max())
    if vmax - hot_thr <= 32:
        # touch counts span a handful of values: a stable counting sort
        # (hottest first) beats argsort on tens of thousands of
        # candidates, with the identical tie order
        order = np.concatenate(
            [np.flatnonzero(acc_hot == v) for v in range(vmax, hot_thr - 1, -1)]
        )
    else:
        order = np.argsort(-acc_hot, kind="stable")
    return hot[order]


def _fold_heat(heat: LazyHeat, interval_touch: np.ndarray, pages: np.ndarray) -> None:
    """End the interval: one shared heat fold for all sizes (mirrors
    ``TieredPagePool.end_interval``'s dense/indexed hybrid) and clear the
    interval's touch counters."""
    if pages.size >= interval_touch.size // 8:
        heat.fold_dense(interval_touch)
        interval_touch[:] = 0
    elif pages.size:
        heat.fold(pages, interval_touch[pages])
        interval_touch[pages] = 0
    else:
        heat.fold(np.empty(0, np.int64), np.empty(0, np.int64))


def _sweep_run(
    trace: Trace,
    fm_fracs: np.ndarray,
    policy: MigrationPolicy,
    hw: HardwareProfile,
    hw_capacity_pages: int | None,
    seed: int,
    collect_configs: bool,
    tuners: list | None = None,
    tune_everys: list | None = None,
    kswapd_batch: int | None = None,
    faults=None,
    page_owner: np.ndarray | None = None,
    slice_caps: np.ndarray | None = None,
    arbiter=None,
):
    """Shared sweep driver: one trace pass across the whole size vector.

    ``policy`` is any *batchable* :class:`~repro.tiering.policy.
    MigrationPolicy` instance (it must follow the TPP candidate contract:
    per-interval hot-threshold promotion candidates fed to
    ``step_batch``); the registry-driven planner in :mod:`repro.sim.api`
    constructs it from the spec. Returns ``(times, pools, configs_out,
    fm_sizes, costs)`` where the last two are ``None`` unless ``tuners``
    is given (tuned mode).

    **Fleet mode** (``page_owner`` given, :mod:`repro.fleet`): the slices
    are *tenants* over disjoint page ranges of a merged fleet trace
    instead of candidate sizes of one workload — ``page_owner[p]`` names
    the slice that owns page ``p``. Each slice then first-touch-allocates,
    promotes, and accounts only its own pages (telemetry and interval
    cost per slice cover the tenant's accesses, with the interval's ops
    split by access share), while heat, the interval touch counters, and
    the demotion ranking stay shared — disjoint ownership makes the
    shared state exact per tenant. ``slice_caps`` sizes each slice pool's
    hardware capacity (the tenant's own RSS rather than the merged
    total); ``arbiter`` is stepped every ``arbiter.every`` intervals
    after the per-slice tuner steps and re-divides the global budget
    across the tenant pools (see :class:`repro.fleet.arbiter.
    FleetTunaArbiter`). With one tenant every fleet formula degenerates
    to the plain tuned-sweep arithmetic bit for bit, which
    ``tests/test_fleet.py`` pins.
    """
    n_sizes = fm_fracs.size
    num_pages = int(trace.rss_pages)
    cap = int(hw_capacity_pages or trace.rss_pages)
    hot_thr = policy.hot_thr
    fleet = page_owner is not None
    caps = (
        np.asarray(slice_caps, dtype=np.int64)
        if slice_caps is not None
        else np.full(n_sizes, cap, dtype=np.int64)
    )

    # stacked per-size tier state + state shared across sizes
    tier_b = np.full((n_sizes, num_pages), int(Tier.UNALLOCATED), dtype=np.int8)
    halflife_decay = 0.5 ** (1.0 / 2.0)  # TieredPagePool default halflife
    heat = LazyHeat(num_pages, halflife_decay)
    interval_acc = np.zeros(num_pages, dtype=np.int64)
    interval_touch = np.zeros(num_pages, dtype=np.int64)
    pools = []
    for s in range(n_sizes):
        pool = TieredPagePool._shared_slice(
            tier_row=tier_b[s],
            heat=heat,
            interval_acc=interval_acc,
            interval_touch=interval_touch,
            hw_capacity=int(caps[s]),
            page_bytes=hw.page_bytes,
            kswapd_batch=kswapd_batch,
            seed=seed,
        )
        pool.set_fm_size(int(round(fm_fracs[s] * caps[s])))
        if trace.slow_pages is not None:
            if fleet:  # a tenant slice only places its own pages
                own_slow = trace.slow_pages[
                    page_owner[trace.slow_pages] == s
                ]
                if own_slow.size:
                    pool.place(own_slow, Tier.SLOW)
            else:
                pool.place(trace.slow_pages, Tier.SLOW)
        pools.append(pool)

    tuned = tuners is not None
    if tuned:
        for s, (pool, tuner) in enumerate(zip(pools, tuners)):
            if tuner is not None:
                tuner.bind_pool(pool, int(caps[s]))
                if faults is not None:
                    faults.wire_tuner(tuner)

    n_intervals = len(trace)
    times = np.zeros((n_sizes, n_intervals), dtype=np.float64)
    fast_code = int(Tier.FAST)
    slow_code = int(Tier.SLOW)
    profilers = configs_out = None
    if collect_configs:
        from repro.core.telemetry import IntervalProfiler

        profilers = [
            IntervalProfiler(hot_thr=hot_thr, num_threads=trace.num_threads)
            for _ in range(n_sizes)
        ]
        configs_out = [[] for _ in range(n_sizes)]
    # the per-(size, interval) IntervalCosts are computed either way for
    # the time accumulation; retaining them keeps every slice's result
    # identical to the per-size engine's (which always returns costs)
    costs = [[] for _ in range(n_sizes)]
    fm_sizes = t_now = None
    if tuned:
        fm_sizes = np.zeros((n_sizes, n_intervals), dtype=np.int64)
        t_now = [0.0] * n_sizes
    for i, ia in enumerate(trace):
        pages = ia.pages
        # --- size-independent work, computed once for all sizes
        counts_mem = absorb_cache(ia.counts, hw.llc_pages)
        mlp_eff = effective_mlp(counts_mem, hw.mlp, trace.num_threads)
        owner_t = page_owner[pages] if fleet else None
        if fleet:
            # each tenant slice allocates only its own pages (its row never
            # sees another tenant's pages, so row-s is the authority)
            for s, pool in enumerate(pools):
                pool._grank_box = None  # new touches change the ranking
                own = pages[owner_t == s]
                new = own[tier_b[s, own] == Tier.UNALLOCATED]
                if new.size:
                    pool._first_touch_alloc(new)
        else:
            new_mask = tier_b[0, pages] == Tier.UNALLOCATED
            new_pages = pages[new_mask] if bool(new_mask.any()) else None
            for pool in pools:
                pool._grank_box = None  # new touches change the ranking
                if new_pages is not None:
                    pool._first_touch_alloc(new_pages)
        interval_touch[pages] += ia.touches
        # one stable ranking of every page by (effective heat, id) serves
        # the victim selection of all sizes this interval — materialized
        # lazily, since demotion-free intervals never need it
        grank_box = LazyGrankBox(heat, interval_touch)
        for pool in pools:
            pool._grank_box = grank_box
            pool._gptr = 0
        # --- batched tier classification of the touched pages; counts are
        # small enough that a float64 BLAS matvec is exact (< 2**53), and
        # every touched page is allocated, so pacc_s is the complement
        tiers_all = tier_b[:, pages]  # [n_sizes, n_touched]
        counts_f = counts_mem.astype(np.float64)
        fast_f = (tiers_all == fast_code).astype(np.float64)
        if profilers is None:
            pacc_f_all = (fast_f @ counts_f).astype(np.int64)
        else:
            # what _simulate()'s profiler records per interval, batched in
            # one GEMM: reported touches saturate at hot_thr, warm =
            # below-threshold fast-tier observations
            rep = np.minimum(ia.touches, hot_thr)
            rep_f = rep.astype(np.float64)
            warm = (rep < hot_thr).astype(np.float64)
            sums = (
                fast_f
                @ np.stack([counts_f, rep_f, warm, rep_f * warm], axis=1)
            ).astype(np.int64)
            pacc_f_all = sums[:, 0]
            ptouch_f_all = sums[:, 1]
            if fleet:
                # per-tenant touch totals: only the pages a slice owns are
                # its slow complement (integer-valued float sums < 2**53
                # stay exact, so the single-tenant case is bit-identical)
                ptouch_s_all = (
                    np.bincount(owner_t, weights=rep_f, minlength=n_sizes)
                    .astype(np.int64) - ptouch_f_all
                )
            else:
                ptouch_s_all = int(rep.sum()) - ptouch_f_all
            warm_pages_all = sums[:, 2]
            warm_touch_all = sums[:, 3]
        if fleet:
            tot_counts = np.bincount(
                owner_t, weights=counts_f, minlength=n_sizes
            ).astype(np.int64)
            pacc_s_all = tot_counts - pacc_f_all
            # the interval's arithmetic work splits by access share (the
            # merged trace sums per-tenant ops; a 1-tenant share is 1.0)
            total_c = int(counts_mem.sum())
            ops_share = (
                tot_counts / total_c
                if total_c > 0
                else np.zeros(n_sizes, dtype=np.float64)
            )
        else:
            pacc_s_all = int(counts_mem.sum()) - pacc_f_all
        # --- promotion candidates: touch counts are size-independent, so
        # the hottest-first stable order is computed once; each size keeps
        # its slow-tier subset (subsets preserve the stable order)
        hot_sorted = _hot_sorted(pages, interval_touch[pages], policy.hot_thr)
        hot_unique = bool(
            hot_sorted.size
            and int(
                np.bincount(hot_sorted, minlength=num_pages).max()
            ) <= 1
        )
        # one batched gather for every size's promotion-candidate filter
        cand_slow_all = (
            tier_b[:, hot_sorted] == slow_code
            if hot_sorted.size
            else None
        )
        if fleet and cand_slow_all is not None:
            # a tenant promotes only its own hot pages (the stable
            # hottest-first order is preserved by the subset)
            hot_owner = page_owner[hot_sorted]
            cands = [
                hot_sorted[cand_slow_all[s] & (hot_owner == s)]
                for s in range(n_sizes)
            ]
        else:
            cands = [
                hot_sorted[cand_slow_all[s]]
                if cand_slow_all is not None
                else hot_sorted
                for s in range(n_sizes)
            ]
        # --- one cross-size policy decision batch (identical outcomes to
        # per-size TPPPolicy.step_hot_sorted calls, in order)
        before_direct = [pool.stats.pgdemote_direct for pool in pools]
        if faults is not None:
            # each slice pool advances its own fault-schedule cursor and
            # may see its background-reclaim budget stalled or shed
            base_kb = [pool.kswapd_batch for pool in pools]
            for pool in pools:
                faults.begin_interval(pool)
                eff_kb = faults.kswapd_budget(pool, pool.kswapd_batch)
                if eff_kb != pool.kswapd_batch:
                    pool.kswapd_batch = eff_kb
            outcomes = policy.step_batch(pools, cands, assume_unique=hot_unique)
            for pool, kb in zip(pools, base_kb):
                pool.kswapd_batch = kb
        else:
            outcomes = policy.step_batch(pools, cands, assume_unique=hot_unique)
        # --- per-size telemetry + cost
        for s, pool in enumerate(pools):
            outcome = outcomes[s]
            ops_s = ia.ops * float(ops_share[s]) if fleet else ia.ops
            if profilers is not None:
                profilers[s].record_accesses(
                    int(ptouch_f_all[s]),
                    int(ptouch_s_all[s]),
                    ops_s,
                    cachelines=int(pacc_f_all[s]) + int(pacc_s_all[s]),
                    warm_pages=int(warm_pages_all[s]),
                    warm_touches=int(warm_touch_all[s]),
                )
                profilers[s].record_policy(outcome)
                configs_out[s].append(profilers[s].finish(pool))
            cost = interval_time(
                hw,
                pacc_f=int(pacc_f_all[s]),
                pacc_s=int(pacc_s_all[s]),
                ops=ops_s,
                pm_pr=outcome.pm_pr,
                pm_de=outcome.pm_de,
                pm_fail=outcome.pm_fail,
                direct_reclaimed=pool.stats.pgdemote_direct - before_direct[s],
                mlp_eff=mlp_eff,
                num_threads=trace.num_threads,
                rand_frac=ia.rand_frac,
            )
            times[s, i] = cost.total
            costs[s].append(cost)
            if tuned:
                # what _simulate() records *before* the tuner step: the fm
                # size in effect during this interval
                fm_sizes[s, i] = pool.effective_fm_size
                t_now[s] += cost.total
        # --- one shared heat fold for all sizes (mirrors
        # TieredPagePool.end_interval's dense/indexed hybrid)
        _fold_heat(heat, interval_touch, pages)
        # --- per-slice tuner steps (_simulate() order: after end_interval);
        # watermark moves re-partition this slice's stacked tier row from
        # the next interval on — the shared ranking is size-independent
        # and needs no invalidation
        if tuned:
            for s, tuner in enumerate(tuners):
                te = tune_everys[s]
                if tuner is not None and te and (i + 1) % te == 0:
                    window = costs[s][-te:]
                    acc = sum(
                        c.pacc_f + c.pacc_s for c in configs_out[s][-te:]
                    )
                    tpa = sum(c.total for c in window) / max(acc, 1)
                    if faults is not None:
                        cv_t, tpa, ok = faults.telemetry(
                            pools[s], configs_out[s][-1], tpa
                        )
                        tuner.step(
                            cv_t, t=t_now[s], measured_tpa=tpa,
                            telemetry_ok=ok,
                        )
                    else:
                        tuner.step(
                            configs_out[s][-1], t=t_now[s], measured_tpa=tpa
                        )
        # --- fleet budget arbitration (after the tuner steps, so the
        # arbiter sees each tenant's unconstrained Tuna trajectory and
        # re-divides the global budget across the tenant pools)
        if arbiter is not None and (i + 1) % arbiter.every == 0:
            arbiter.step(
                pools, configs_out=configs_out, t_now=t_now, interval=i
            )
    return times, pools, configs_out, fm_sizes, costs


def _sweep_fm_fracs(
    trace: Trace,
    fm_fracs,
    hot_thr: int = 4,
    hw: HardwareProfile = OPTANE_LIKE,
    hw_capacity_pages: int | None = None,
    seed: int = 0,
    collect_configs: bool = False,
    kswapd_batch: int | None = None,
    policy: MigrationPolicy | None = None,
    faults=None,
    fault_log: list | None = None,
    engine: str = "numpy",
) -> SweepResult:
    """Run ``trace`` once, concurrently at every fraction in ``fm_fracs``.

    Equivalent to ``[_simulate(trace, fm_frac=f, policy=TPPPolicy(hot_thr))
    for f in fm_fracs]`` (same counters, same interval times), at roughly
    the cost of the most expensive single size plus one cross-size
    vectorized policy step per interval. ``kswapd_batch`` overrides every
    slice pool's background-reclaim budget (the equivalence tests starve
    it to force the thrash regime); ``None`` keeps the pool default.
    ``policy`` swaps in any batchable policy instance (its ``hot_thr``
    wins over the ``hot_thr`` argument); its per-instance
    ``chunked_steps`` counter records any fallback executions of the run.

    **Backend selection** (``engine``): ``"numpy"`` — this module's
    stacked-array interval loop, the equivalence oracle; ``"jax"`` — the
    jitted device step of :mod:`repro.sim.jax_engine` (bit-exact by
    contract, Pallas victim-partition kernel per
    :func:`repro.kernels.ops.pallas_mode`).
    The JAX backend refuses fault injection, non-``jax_batchable``
    policies, and traces with duplicate page ids per interval; callers
    opt in explicitly (the :mod:`repro.sim.api` planner routes
    ``Scenario(engine="jax")`` here and validates eligibility up front).
    """
    fm_fracs = np.asarray(fm_fracs, dtype=np.float64)
    if fm_fracs.size == 0:
        raise ValueError("sweep_fm_fracs needs at least one fm fraction")
    if policy is None:
        policy = TPPPolicy(hot_thr=hot_thr)
    times, pools, configs_out, _, costs = _resolve_engine(engine)(
        trace, fm_fracs, policy, hw, hw_capacity_pages, seed,
        collect_configs, kswapd_batch=kswapd_batch, faults=faults,
    )
    if faults is not None and fault_log is not None:
        for pool in pools:
            fault_log.append(faults.events(pool))
    return SweepResult(
        name=trace.name,
        fm_fracs=fm_fracs,
        interval_times=times,
        stats=[pool.stats.snapshot() for pool in pools],
        configs=configs_out,
        costs=costs,
    )


def _sweep_tuned(
    trace: Trace,
    slices,
    hot_thr: int = 4,
    hw: HardwareProfile = OPTANE_LIKE,
    hw_capacity_pages: int | None = None,
    seed: int = 0,
    kswapd_batch: int | None = None,
    policy: MigrationPolicy | None = None,
    faults=None,
    fault_log: list | None = None,
    engine: str = "numpy",
) -> list:
    """Run ``trace`` once across a vector of :class:`TunedSlice` settings.

    The TPP+Tuna closed loop at sweep speed: every slice's tuner runs *in
    the loop* against its own slice pool while the trace is swept once.
    Returns one :class:`~repro.sim.engine.SimResult` per slice, in order —
    bit-exact against ``_simulate(trace, fm_frac=sl.fm_frac,
    tuner=sl.tuner, tune_every=sl.tune_every)`` per slice (counters,
    interval times, config vectors, fm sizes; the tuner's decision list
    and its controller's watermark event log accumulate identically).
    ``policy`` swaps in any batchable policy instance (stateful policies
    keep fully independent per-slice trajectories: their state is scoped
    per pool); its ``hot_thr`` wins over the ``hot_thr`` argument.
    ``engine`` selects the sweep backend exactly as in
    :func:`_sweep_fm_fracs` (``"numpy"`` oracle / ``"jax"`` device step);
    tuner decision sequences are part of the bit-exactness contract.
    """
    from repro.sim.engine import SimResult

    slices = [
        sl if isinstance(sl, TunedSlice) else TunedSlice(*sl) for sl in slices
    ]
    if not slices:
        raise ValueError("sweep_tuned needs at least one slice")
    if policy is None:
        policy = TPPPolicy(hot_thr=hot_thr)
    fm_fracs = np.asarray([sl.fm_frac for sl in slices], dtype=np.float64)
    tuners = [sl.tuner for sl in slices]
    tune_everys = [sl.tune_every for sl in slices]
    times, pools, configs_out, fm_sizes, costs = _resolve_engine(engine)(
        trace, fm_fracs, policy, hw, hw_capacity_pages, seed,
        collect_configs=True, tuners=tuners, tune_everys=tune_everys,
        kswapd_batch=kswapd_batch, faults=faults,
    )
    if faults is not None and fault_log is not None:
        for pool in pools:
            fault_log.append(faults.events(pool))
    return [
        SimResult(
            name=trace.name,
            total_time=float(np.sum(times[s])),
            interval_times=times[s].copy(),
            configs=configs_out[s],
            fm_sizes=fm_sizes[s].copy(),
            stats=pools[s].stats.snapshot(),
            costs=costs[s],
        )
        for s in range(len(slices))
    ]


def _resolve_engine(engine: str):
    """Map an ``engine`` name to its sweep-run driver.

    ``"numpy"`` is the frozen oracle; ``"jax"`` lazily imports
    :mod:`repro.sim.jax_engine` so environments without a working JAX
    install can still run every numpy path.
    """
    if engine == "numpy":
        return _sweep_run
    if engine == "jax":
        from repro.sim.jax_engine import _sweep_run_jax

        return _sweep_run_jax
    raise ValueError(f"unknown sweep engine {engine!r} (use 'numpy' or 'jax')")
