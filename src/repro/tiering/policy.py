"""Pluggable page-management policies: the ``MigrationPolicy`` protocol,
the ``POLICIES`` registry, and the built-in backends.

A policy is invoked once per profiling interval with the pool and the set
of pages touched in that interval, and returns a :class:`PolicyOutcome`
(the per-interval migration telemetry that feeds the Tuna config vector).
Four backends ship in this module:

* :class:`TPPPolicy` — hot-threshold promotion + watermark demotion, the
  paper's management system (TPP/AutoNUMA-style);
* :class:`FirstTouchPolicy` — NUMA first-touch, no migration (the Fig. 1
  baseline);
* :class:`AdmissionTPPPolicy` — TPP plus TierBPF-style *migration
  admission control*: promotion candidates whose predicted fast-tier
  residency would not amortize the migration cost are rejected
  (``PolicyOutcome.pm_admit_fail``);
* :class:`ThrashGuardPolicy` — TPP plus a Jenga-style *thrash guard*:
  promote/demote ping-pong is detected through a per-page
  recently-promoted stamp, and promotion aggressiveness backs off while
  the churn persists.

Adding a backend in one file
----------------------------
Subclass :class:`MigrationPolicy` (or, for TPP-derived behaviour,
:class:`TPPPolicy` — override the :meth:`TPPPolicy._admit` /
:meth:`TPPPolicy._note_step` hooks and both the per-size engine and the
batched sweeps pick the behaviour up), give it a unique ``kind`` string
and capability flags, and decorate it with :func:`register_policy`::

    from repro.tiering.policy import TPPPolicy, register_policy

    @register_policy
    class MyPolicy(TPPPolicy):
        kind = "mine"

        def _admit(self, pool, cand):
            keep = my_filter(pool, cand)
            return cand[keep], int(cand.size - keep.sum())

That is the whole integration: ``repro.sim.api.PolicySpec(kind="mine",
params={...})`` resolves the class through the registry, the
:func:`repro.sim.api.run` planner routes it onto the batched sweeps or
the per-size engine from the capability flags alone, and the ``params``
dict is passed to the constructor and echoed losslessly through
``RunSet`` JSON. No ``api.py`` edits are needed.

Capability flags (class attributes)
-----------------------------------
``kind``
    Registry name (``PolicySpec.kind``).
``batchable``
    Whether the policy supports the batched sweep contract
    (:meth:`MigrationPolicy.step_batch` over presorted per-size candidate
    vectors); non-batchable policies run on the per-size engine.
``tunable``
    Whether a Tuna tuner may run in the loop with this policy
    (``PolicySpec(tuner=...)`` is validated against this flag).
``jax_batchable``
    Whether the accelerator sweep backend (:mod:`repro.sim.jax_engine`)
    replicates this policy's decision semantics on device. The JAX
    interval step reimplements the TPP candidate contract plus the
    trace-pure admission criterion of :class:`AdmissionTPPPolicy`
    inside one jitted kernel — it does *not* call :meth:`_admit` /
    :meth:`_note_step` per interval — so a subclass overriding either
    hook with new behaviour MUST set ``jax_batchable = False`` unless
    the device path is taught its semantics
    (:class:`ThrashGuardPolicy` does exactly that: its per-pool guard
    state is host-side and stateful, so it pins the flag off and runs
    on the numpy sweep). Only consulted when a scenario opts into
    ``engine="jax"``.

``batchable``, ``jax_batchable`` and ``tunable`` are what the planner
and spec validation consult. ``migrates`` (does the policy move pages at all) is descriptive
metadata the planner never routes on; the benchmark drivers derive their
backend-comparison sets from it (``benchmarks.common.policy_kinds``).

Chunked-loop telemetry
----------------------
Every policy instance counts executions of the per-chunk Python fallback
loop in :attr:`MigrationPolicy.chunked_steps`. The bulk path covers every
in-engine regime including thrash, so the sweep engines are expected to
keep their policy instance's counter at zero — the equivalence tests
assert it, and :class:`repro.sim.api.RunSet` surfaces the sweep backends'
total as provenance. Every candidate-bearing chunked execution counts,
whatever the pool: pools without a bulk path (the reference pool runs
chunked by design) increment it too. The counter lives on the instance,
not the module, so concurrent ``run()`` workers cannot cross-pollute
each other's provenance.
"""

from __future__ import annotations

import weakref
from dataclasses import dataclass

import numpy as np

from repro.tiering.page_pool import (
    Tier,
    TieredPagePool,
    _bulk_schedule_batch,
)

# --------------------------------------------------------------- registry

# kind -> MigrationPolicy subclass; populated by @register_policy.
POLICIES: dict[str, type] = {}


def register_policy(cls):
    """Class decorator: add ``cls`` to :data:`POLICIES` under its
    ``kind``. Re-registering the same class is a no-op; a different class
    under a taken kind is an error (no silent shadowing)."""
    kind = getattr(cls, "kind", None)
    if not isinstance(kind, str) or not kind:
        raise ValueError(
            f"{cls.__qualname__} needs a non-empty string `kind` class "
            "attribute to be registered"
        )
    prev = POLICIES.get(kind)
    if prev is not None and prev is not cls:
        raise ValueError(
            f"policy kind {kind!r} is already registered by "
            f"{prev.__qualname__}"
        )
    POLICIES[kind] = cls
    return cls


def resolve_policy(kind: str) -> type:
    """The registered policy class for ``kind``; unknown kinds raise with
    the registered alternatives listed."""
    try:
        return POLICIES[kind]
    except KeyError:
        raise ValueError(
            f"unknown policy kind {kind!r}; registered kinds: "
            f"{', '.join(sorted(POLICIES))}"
        ) from None


@dataclass
class PolicyOutcome:
    """Per-interval migration telemetry (feeds the Tuna config vector)."""

    pm_pr: int = 0  # successful promotions
    pm_de: int = 0  # demotions (background + direct)
    pm_fail: int = 0  # promotion failures (fast tier full, reclaim spent)
    direct_reclaim: int = 0
    # candidates the policy itself declined to promote (admission control
    # / thrash-guard suppression) — distinct from pm_fail, which counts
    # *attempted* promotions the pool could not place
    pm_admit_fail: int = 0


class MigrationPolicy:
    """Abstract per-interval page-management policy (the plug-in protocol).

    Subclasses implement :meth:`step`; batchable subclasses additionally
    implement :meth:`step_batch` (one vectorized decision pass across a
    whole fm-size vector) and set ``batchable = True``. See the module
    docstring for the capability flags and the registration walkthrough.
    """

    kind: str = ""
    migrates: bool = True
    batchable: bool = False
    # device-side sweep support (see the module docstring): only policies
    # whose per-interval decision semantics the jitted JAX interval step
    # replicates exactly may opt in
    jax_batchable: bool = False
    tunable: bool = False

    def __init__(self, hot_thr: int = 4) -> None:
        self.hot_thr = int(hot_thr)
        # executions of the per-chunk Python fallback loop by THIS
        # instance (see the module docstring's telemetry section)
        self.chunked_steps = 0
        # a repro.sim.faults.FaultInjector attached by the execution
        # engine for fault-injected runs; None (the default) keeps every
        # step on the exact pre-fault-model path
        self.fault_injector = None

    def step(
        self,
        pool: TieredPagePool,
        touched: np.ndarray,
        hot_thr: int | None = None,
    ) -> PolicyOutcome:
        """One profiling interval's policy decision for one pool."""
        raise NotImplementedError

    def step_batch(
        self,
        pools,
        cands,
        assume_unique: bool = False,
    ) -> list[PolicyOutcome]:
        """Batched-sweep contract; only called when ``batchable``."""
        raise NotImplementedError(
            f"{type(self).__qualname__} is not batchable (batchable="
            f"{self.batchable}); the planner must route it onto the "
            "per-size engine"
        )


@register_policy
class TPPPolicy(MigrationPolicy):
    """Hot-threshold promotion + watermark demotion.

    Parameters
    ----------
    hot_thr:
        Number of accesses within the profiling window that makes a page
        "hot" (promotion candidate). Invariant for TPP/AutoNUMA-style
        systems; MEMTIS-style dynamic thresholds are supported by passing a
        new value to :meth:`step`.
    promote_batch:
        Upper bound on promotions per interval (migration bandwidth limit of
        the kernel thread); ``None`` = unbounded.

    Subclass hooks
    --------------
    :meth:`_admit` filters the hottest-first candidate vector before any
    scheduling (admission control, guards); :meth:`_note_step` observes the
    step's outcome (per-page policy state). Both run identically on the
    per-size engine and the batched sweeps, so a subclass overriding only
    them inherits the bulk scheduling machinery — and its chunked-loop-free
    guarantee — unchanged.
    """

    kind = "tpp"
    migrates = True
    batchable = True
    jax_batchable = True
    tunable = True

    def __init__(self, hot_thr: int = 4, promote_batch: int | None = None) -> None:
        if hot_thr < 2:
            raise ValueError("hot_thr must be >= 2 (paper Eq. 4 divides by hot_thr-1)")
        super().__init__(hot_thr=hot_thr)
        self.promote_batch = promote_batch

    # ------------------------------------------------------ subclass hooks
    def _admit(self, pool, cand: np.ndarray) -> tuple[np.ndarray, int]:
        """Candidate admission hook: ``(admitted, n_rejected)``.

        ``cand`` is the interval's promotion-candidate vector (unique ids,
        hottest first, stable tie order); the returned vector must be a
        subsequence of it (subsequences preserve both invariants). Called
        exactly once per (pool, interval), *before* ``promote_batch``
        truncation, on every execution path. Base TPP admits everything.
        """
        return cand, 0

    def _note_step(self, pool, admitted: np.ndarray, out: PolicyOutcome) -> None:
        """Post-step hook, called exactly once per (pool, interval) with
        the admitted candidates and the realized outcome. The promoted
        pages are exactly ``admitted[:out.pm_pr]`` (promotions are a
        prefix on every path — bulk, chunked, and the reference pool).
        Base TPP keeps no state.
        """

    # ------------------------------------------------------------ stepping
    def step(
        self,
        pool: TieredPagePool,
        touched: np.ndarray,
        hot_thr: int | None = None,
    ) -> PolicyOutcome:
        thr = self.hot_thr if hot_thr is None else int(hot_thr)
        touched = np.asarray(touched, dtype=np.int64)
        # TPP-style: promotion is decided on fault-like touch events within
        # the profiling window (pool.interval_touch at policy time); the
        # decayed heat only ranks demotion victims.
        acc_now = pool.interval_touch[touched]
        cand_mask = (pool.tier[touched] == Tier.SLOW) & (acc_now >= thr)
        cand = touched[cand_mask]
        hottest_first = np.argsort(-acc_now[cand_mask], kind="stable")
        cand = cand[hottest_first]
        cand, n_rej = self._admit(pool, cand)
        n_inj_fail = 0
        if self.fault_injector is not None:
            # injected transient migration failures (after admission: a
            # failed attempt is an admitted migration the pool lost)
            cand, n_inj_fail = self.fault_injector.filter_promotions(pool, cand)
        assume_unique = bool(
            cand.size
            and hasattr(pool, "_try_bulk_step")
            and np.unique(cand).size == cand.size
        )
        out = self.step_hot_sorted(pool, cand, assume_unique=assume_unique)
        out.pm_admit_fail += n_rej
        out.pm_fail += n_inj_fail
        self._note_step(pool, cand, out)
        return out

    def step_hot_sorted(
        self,
        pool: TieredPagePool,
        cand: np.ndarray,
        assume_unique: bool = False,
        _sched=None,
    ) -> PolicyOutcome:
        """Run the promotion/reclaim loop on presorted candidates.

        ``cand`` must be the interval's promotion candidates (slow tier,
        touches >= hot_thr), hottest first with a *stable* tie order — what
        :meth:`step` computes itself, and what the batched sweep engine
        precomputes once per interval and mask-filters per fast-memory size
        (a subset of a stably sorted sequence keeps the stable order).
        With ``assume_unique`` (the caller has verified ``cand`` holds no
        duplicate ids) the pool's bulk path executes the whole
        promote/reclaim schedule in O(1) array operations — including the
        thrash regime, where same-step promotions are resolved as demotion
        victims by the bulk merge (see
        :meth:`~repro.tiering.page_pool.TieredPagePool._try_bulk_step`).
        The chunked loop below only runs for non-unique candidates, pools
        without a bulk path (the reference pool), or queue state perturbed
        from outside a policy step; executions are counted in this
        instance's :attr:`~MigrationPolicy.chunked_steps`. ``_sched`` is a
        precomputed bulk schedule from :meth:`step_batch` (already clamped
        to ``promote_batch``).
        """
        out = PolicyOutcome()
        if self.promote_batch is not None and cand.size > self.promote_batch:
            cand = cand[: self.promote_batch]
        promote = pool.promote
        if assume_unique:
            bulk = getattr(pool, "_try_bulk_step", None)
            if bulk is not None:
                res = bulk(cand, _sched=_sched)
                if res is not None:
                    out.pm_pr, out.pm_de, out.pm_fail, out.direct_reclaim = res
                    return out
            # chunked fallback: the promotion chunks inherit cand's
            # verified invariants (unique, all slow)
            promote = getattr(pool, "_promote_cand", pool.promote)
        if cand.size:
            self.chunked_steps += 1
        # Promotion is interleaved with background reclaim (TPP decouples
        # allocation and reclaim): promote only into the headroom above the
        # min watermark, let kswapd restore the watermark, repeat. Direct
        # (blocking) reclaim happens only when kswapd's rate limit cannot
        # keep up with the promotion demand.
        done = 0
        while done < cand.size:
            headroom = max(0, pool.fast_free - pool.watermarks.min_free)
            if headroom == 0:
                bg, direct = pool.run_reclaim(allow_direct=True)
                out.pm_de += bg + direct
                out.direct_reclaim += direct
                headroom = max(0, pool.fast_free - pool.watermarks.min_free)
                if headroom == 0:
                    # reclaim exhausted: remaining promotions fail
                    out.pm_fail += cand.size - done
                    break
            chunk = cand[done : done + headroom]
            n_ok, n_fail = promote(chunk)
            out.pm_pr += n_ok
            out.pm_fail += n_fail
            done += chunk.size
        bg, direct = pool.run_reclaim()
        out.pm_de += bg + direct
        out.direct_reclaim += direct
        return out

    def step_batch(
        self,
        pools,
        cands,
        assume_unique: bool = False,
    ) -> list[PolicyOutcome]:
        """One policy decision batch across a whole fm-size vector.

        ``pools[s]`` / ``cands[s]`` are one fast-memory size's pool and its
        presorted promotion candidates (see :meth:`step_hot_sorted` for the
        candidate contract). Per size, the :meth:`_admit` hook filters the
        candidates first; the TPP promote/reclaim schedules of every size
        are then computed in **one vectorized pass** over stacked
        watermark/free-page vectors (:func:`repro.tiering.page_pool.
        _bulk_schedule_batch`) instead of ``n_sizes`` Python loops; each
        pool then applies its schedule through the same bulk commit path a
        serial :meth:`step_hot_sorted` call uses, and :meth:`_note_step`
        observes each outcome. Sizes whose reclaim demand reaches into
        their own step's promotions (the thrash regime) stay on the bulk
        path too: their victim identities are resolved against the
        schedule's availability horizons in one merge per slice, so no
        size drops to the chunked loop. Outcome-identical to calling
        :meth:`step` per size, in order.
        """
        admitted, rejected, inj_failed = [], [], []
        fi = self.fault_injector
        for pool, cand in zip(pools, cands):
            a, r = self._admit(pool, cand)
            n_inj = 0
            if fi is not None:
                a, n_inj = fi.filter_promotions(pool, a)
            admitted.append(a)
            rejected.append(r)
            inj_failed.append(n_inj)
        outs = self._schedule_batch(pools, admitted, assume_unique)
        for pool, a, r, n_inj, out in zip(
            pools, admitted, rejected, inj_failed, outs
        ):
            out.pm_admit_fail += r
            out.pm_fail += n_inj
            self._note_step(pool, a, out)
        return outs

    def _schedule_batch(
        self,
        pools,
        cands,
        assume_unique: bool,
    ) -> list[PolicyOutcome]:
        """The cross-size vectorized schedule over *admitted* candidates."""
        if not assume_unique:
            return [
                self.step_hot_sorted(pool, cand, assume_unique=False)
                for pool, cand in zip(pools, cands)
            ]
        if self.promote_batch is not None:
            cands = [c[: self.promote_batch] for c in cands]
        n = len(pools)
        free = np.empty(n, dtype=np.int64)
        fast_count = np.empty(n, dtype=np.int64)
        min_free = np.empty(n, dtype=np.int64)
        low_free = np.empty(n, dtype=np.int64)
        high_free = np.empty(n, dtype=np.int64)
        kswapd = np.empty(n, dtype=np.int64)
        n_cand = np.empty(n, dtype=np.int64)
        for s, (pool, cand) in enumerate(zip(pools, cands)):
            wm = pool.watermarks
            free[s] = pool.fast_free
            fast_count[s] = pool.fast_used
            min_free[s] = wm.min_free
            low_free[s] = wm.low_free
            high_free[s] = wm.high_free
            kswapd[s] = pool.kswapd_batch
            n_cand[s] = cand.size
        sched = _bulk_schedule_batch(
            free, fast_count, min_free, low_free, high_free, kswapd, n_cand
        )
        return [
            self.step_hot_sorted(
                pool,
                cand,
                assume_unique=True,
                _sched=tuple(int(col[s]) for col in sched),
            )
            for s, (pool, cand) in enumerate(zip(pools, cands))
        ]


def _effective_heat(pool, pages: np.ndarray) -> np.ndarray:
    """The interval-frozen demotion-ranking key: decayed access history
    carried through the current interval plus this interval's touches.
    Identical arithmetic on every pool implementation (the incremental
    pool's ``heat_of`` is pinned bit-exact against the reference dense
    decay), so admission decisions cannot diverge between lanes."""
    return pool.heat_of(pages) * pool.decay + pool.interval_touch[pages]


@register_policy
class AdmissionTPPPolicy(TPPPolicy):
    """TPP with TierBPF-style migration admission control.

    TierBPF's observation (PAPERS.md): a large share of promotions never
    pay off — the page is demoted again before its fast-tier accesses
    amortize the migration cost — so migrations should pass an *admission*
    stage instead of being granted to every hot page. Here the predicted
    benefit of promoting a candidate is its effective heat (decayed access
    history + this interval's touches: the pages it will beat in the
    demotion ranking, hence a monotone proxy for expected fast-tier
    residency), and a candidate is admitted only when

        ``effective_heat >= admit_margin * hot_thr``

    i.e. when its history-backed access mass exceeds the bare promotion
    threshold by the amortization margin. One-interval spikes with no
    reuse history are rejected; rejections are reported as
    :attr:`PolicyOutcome.pm_admit_fail` (flowing into the config vector's
    ``pm_admit_fail`` extra), and never reach the pool — they are not
    migration *failures*, the controller simply declined them.

    ``admit_margin <= 1`` admits every candidate (plain TPP). The
    criterion is a pure per-page function of trace-driven state, so it is
    identical at every fast-memory size and on every execution path.
    """

    kind = "admission"

    def __init__(
        self,
        hot_thr: int = 4,
        promote_batch: int | None = None,
        admit_margin: float = 2.0,
    ) -> None:
        super().__init__(hot_thr=hot_thr, promote_batch=promote_batch)
        self.admit_margin = float(admit_margin)
        if not np.isfinite(self.admit_margin) or self.admit_margin < 0:
            raise ValueError("admit_margin must be a finite non-negative float")

    def _admit(self, pool, cand: np.ndarray) -> tuple[np.ndarray, int]:
        if cand.size == 0:
            return cand, 0
        ok = _effective_heat(pool, cand) >= self.admit_margin * self.hot_thr
        n_ok = int(np.count_nonzero(ok))
        if n_ok == cand.size:
            return cand, 0
        return cand[ok], cand.size - n_ok


class _GuardState:
    """Per-pool thrash-guard state (one per pool a policy instance steps)."""

    __slots__ = ("last_promoted", "t", "cooldown")

    def __init__(self, num_pages: int) -> None:
        self.last_promoted = np.full(num_pages, -(2**62), dtype=np.int64)
        self.t = 0  # policy steps taken on this pool
        self.cooldown = 0  # remaining backoff steps


@register_policy
class ThrashGuardPolicy(TPPPolicy):
    """TPP with a Jenga-style thrash guard.

    Jenga's motivating failure mode (PAPERS.md): under churn, eagerly
    promoting every hot page evicts pages that are about to be hot again,
    and the management system spends its time ping-ponging the same pages
    between tiers. The guard detects exactly that signature without
    needing demotion identities: a promotion candidate that this policy
    itself promoted within the last ``reuse_window`` steps is *slow again*
    — it must have been demoted in between — i.e. it ping-ponged. When
    ping-pong candidates exceed ``churn_frac`` of the interval's
    candidates, the policy enters a ``backoff_intervals``-step backoff
    during which ping-pong candidates are suppressed (reported as
    :attr:`PolicyOutcome.pm_admit_fail`), letting the resident set settle
    instead of churning. Outside backoff the policy is plain TPP.

    State is tracked per pool (a per-page last-promotion stamp plus the
    step/backoff counters), so one instance can serve a whole sweep's
    slice pools with fully independent per-size trajectories.
    """

    kind = "thrash_guard"
    # per-pool guard state (stamps, cooldown) lives host-side and mutates
    # every step — the jitted interval step does not replicate it
    jax_batchable = False

    def __init__(
        self,
        hot_thr: int = 4,
        promote_batch: int | None = None,
        reuse_window: int = 2,
        churn_frac: float = 0.25,
        backoff_intervals: int = 2,
    ) -> None:
        super().__init__(hot_thr=hot_thr, promote_batch=promote_batch)
        self.reuse_window = int(reuse_window)
        self.churn_frac = float(churn_frac)
        self.backoff_intervals = int(backoff_intervals)
        if self.reuse_window < 1:
            raise ValueError("reuse_window must be >= 1 (steps)")
        if not 0.0 <= self.churn_frac <= 1.0:
            raise ValueError("churn_frac must be within [0, 1]")
        if self.backoff_intervals < 1:
            raise ValueError("backoff_intervals must be >= 1")
        # weak keys: a long-lived instance stepping many pools (the
        # plug-in audience's natural usage) must not pin dead pools or
        # their per-page stamp arrays
        self._states = weakref.WeakKeyDictionary()

    def _state(self, pool) -> _GuardState:
        st = self._states.get(pool)
        if st is None:
            st = _GuardState(pool.num_pages)
            self._states[pool] = st
        return st

    def _admit(self, pool, cand: np.ndarray) -> tuple[np.ndarray, int]:
        st = self._state(pool)
        if cand.size == 0:
            return cand, 0
        # promoted recently by this policy, yet slow again now => the page
        # was demoted within the window: the ping-pong signature. Stamps
        # are pre-increment step numbers, so >= covers exactly the last
        # `reuse_window` steps (reuse_window=1: the immediately preceding
        # step only).
        recent = st.last_promoted[cand] >= st.t - self.reuse_window
        n_ping = int(np.count_nonzero(recent))
        if n_ping > self.churn_frac * cand.size:
            st.cooldown = self.backoff_intervals
        if st.cooldown > 0 and n_ping:
            return cand[~recent], n_ping
        return cand, 0

    def _note_step(self, pool, admitted: np.ndarray, out: PolicyOutcome) -> None:
        st = self._state(pool)
        if out.pm_pr:
            st.last_promoted[admitted[: out.pm_pr]] = st.t
        if st.cooldown > 0:
            st.cooldown -= 1
        st.t += 1


@register_policy
class FirstTouchPolicy(MigrationPolicy):
    """NUMA first-touch with no migration (the paper's Fig. 1 baseline).

    Allocation behaviour is already first-touch inside the pool; this policy
    simply never migrates. Watermark reclaim is also disabled — pages stay
    where they landed — matching the no-page-management configuration in the
    motivation study.
    """

    kind = "first_touch"
    migrates = False
    batchable = False
    tunable = False

    def step(
        self,
        pool: TieredPagePool,
        touched: np.ndarray,
        hot_thr: int | None = None,
    ) -> PolicyOutcome:
        return PolicyOutcome()
