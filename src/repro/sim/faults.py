"""Seeded, deterministic fault injection for the tiering stack.

Tuna's thesis is that migration *failures* are first-class sizing signals
(`pgpromote_fail`, direct reclaim) — yet organically the simulator only
produces them at the knee. This module injects them on purpose, plus the
degraded-input regimes ARMS argues a tiering system must survive and that
Nomad's transactional migrations show are *normal* under thrash
(PAPERS.md): transient promotion/demotion failures with per-page bounded
retry + exponential backoff, kswapd stall windows, telemetry
dropout/noise, :class:`~repro.core.perfdb.PerfDB` query outages, and
watermark-actuation lag.

Design contract
---------------
* **Declarative**: :class:`FaultSpec` is a frozen, JSON-round-trippable
  dataclass carried by :class:`repro.sim.api.Scenario` (``faults=...``)
  and echoed into the RunSet provenance (schema ``tuna-runset-v3``).
* **Deterministic**: every decision is a pure hash of
  ``(spec.seed, interval, page)`` — no sequential RNG state — so the
  per-size engine, both batched sweeps, and process fan-out workers all
  reproduce the identical fault schedule for the same seed, regardless
  of evaluation order. Identical seeds ⇒ identical event logs
  (acceptance-tested by ``tests/test_faults.py``).
* **Zero overhead when absent**: with ``Scenario(faults=None)`` no
  injector exists; every integration point is a single ``is not None``
  check outside the vectorized inner loops, and all equivalence lanes
  stay bit-exact (``tests/test_engine_equivalence.py`` /
  ``tests/test_api.py``). A zero-rate :class:`FaultSpec` is also
  bit-exact — the injector filters nothing and logs nothing.
* **Visible to the model**: retry-exhausted promotions are credited into
  ``pool.stats.pgpromote_fail`` and the interval's
  :class:`~repro.tiering.policy.PolicyOutcome.pm_fail` — the same
  counters the paper's ConfigVector and cost model consume — so the
  tuner *sees* the injected faults instead of being silently lied to.

Per-pool state (retry counters, backoff deadlines, interval cursor,
event log) is keyed on the pool object, so one injector instance serves
a whole batched sweep: every size-slice keeps an independent trajectory
over the same seeded schedule, exactly like per-slice policies scope
their state per pool.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import numpy as np

__all__ = ["FaultSpec", "FaultInjector"]

# splitmix64 mixing constants (public-domain PRNG finalizer)
_C2 = np.uint64(0xBF58476D1CE4E5B9)
_C3 = np.uint64(0x94D049BB133111EB)
_PAGE_STRIDE = np.uint64(0x100000001B3)
_T_STRIDE = np.uint64(0x9E3779B97F4A7C15)
_MASK = 0xFFFFFFFFFFFFFFFF

# channel salts: each fault channel draws from an independent stream
_SALT_PROMOTE = 0x01
_SALT_DEMOTE = 0x02
_SALT_STALL = 0x03
_SALT_DROP = 0x04
_SALT_NOISE = 0x05
_SALT_NOISE_MAG = 0x06
_SALT_DB = 0x07


def _u01(keys: np.ndarray, seed: int, salt: int) -> np.ndarray:
    """Vectorized splitmix64-style hash of integer keys into [0, 1)."""
    z = np.atleast_1d(np.asarray(keys)).astype(np.uint64)
    mix = (seed * 0x9E3779B97F4A7C15 + salt * 0xD6E8FEB86659FD93) & _MASK
    z = z + np.uint64(mix)
    z ^= z >> np.uint64(30)
    z *= _C2
    z ^= z >> np.uint64(27)
    z *= _C3
    z ^= z >> np.uint64(31)
    return z.astype(np.float64) / float(2**64)


def _u01_scalar(key: int, seed: int, salt: int) -> float:
    return float(_u01(np.asarray([key], dtype=np.uint64), seed, salt)[0])


@dataclass(frozen=True)
class FaultSpec:
    """Declarative fault model for one scenario (all channels optional).

    Rates are per-draw probabilities in ``[0, 1]``; a default-constructed
    spec injects nothing. The spec is JSON-round-trippable
    (:meth:`to_dict` / :meth:`from_dict`) and is echoed verbatim in the
    RunSet provenance.
    """

    seed: int = 0
    # --- transient migration failures (per-page bounded retry + backoff)
    promote_fail_rate: float = 0.0  # P(attempted promotion fails) per draw
    max_retries: int = 3  # retries before the migration is abandoned
    backoff_base: int = 1  # intervals; doubles per consecutive failure
    demote_fail_rate: float = 0.0  # fraction of kswapd budget that fails
    # --- kswapd stall windows (background reclaim fully unavailable)
    kswapd_stall_rate: float = 0.0  # P(a stall window opens at interval t)
    kswapd_stall_len: int = 2  # intervals per stall window
    # --- telemetry faults (what the tuner sees at tuning steps)
    telemetry_drop_rate: float = 0.0  # P(tuning window's telemetry lost)
    telemetry_noise_rate: float = 0.0  # P(tuning window's counters noisy)
    telemetry_noise_scale: float = 0.5  # max multiplicative perturbation
    # --- PerfDB query outages (windows keyed on the tuner's step index)
    db_outage_rate: float = 0.0  # P(an outage window opens at step s)
    db_outage_len: int = 2  # tuner steps per outage window
    # --- watermark-actuation lag (set_size takes effect N calls late)
    actuation_lag: int = 0

    def __post_init__(self) -> None:
        for name in (
            "promote_fail_rate", "demote_fail_rate", "kswapd_stall_rate",
            "telemetry_drop_rate", "telemetry_noise_rate", "db_outage_rate",
        ):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"FaultSpec.{name} must be in [0, 1], got {v}")
        for name in ("max_retries", "backoff_base", "kswapd_stall_len",
                     "db_outage_len", "actuation_lag"):
            if int(getattr(self, name)) < 0:
                raise ValueError(f"FaultSpec.{name} must be >= 0")
        if self.telemetry_noise_scale < 0:
            raise ValueError("FaultSpec.telemetry_noise_scale must be >= 0")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "FaultSpec":
        return cls(**d)


class _PoolFaultState:
    """Per-pool fault trajectory: retry bookkeeping + event log."""

    __slots__ = ("t", "fail_count", "blocked_until", "events")

    def __init__(self, num_pages: int) -> None:
        self.t = -1  # interval cursor, ticked by begin_interval
        self.fail_count = np.zeros(num_pages, dtype=np.int64)
        # first interval a blocked page may retry (exclusive backoff)
        self.blocked_until = np.zeros(num_pages, dtype=np.int64)
        self.events: list[dict] = []


@dataclass
class FaultInjector:
    """Live fault engine for one run (or one whole sweep pass).

    Stateless over the *schedule* (pure hashes of the spec seed) and
    stateful only per pool (retry counters, event log). The execution
    engines drive it:

    * :meth:`begin_interval` — once per (pool, interval), before the
      policy step; ticks the pool's interval cursor.
    * :meth:`kswapd_budget` — effective background-reclaim budget for
      this interval (stall windows zero it; ``demote_fail_rate`` sheds a
      seeded fraction of it — failed background demotions are re-driven
      by the watermark deficit next interval, which is how they surface
      in later ``pm_de`` telemetry).
    * :meth:`filter_promotions` — called by the policy after admission:
      draws per-(page, interval) transient failures, applies bounded
      retry + exponential backoff, credits retry-exhausted pages into
      ``pool.stats.pgpromote_fail``, and returns the surviving candidate
      subsequence plus the failed-attempt count (added to ``pm_fail``).
    * :meth:`telemetry` — perturbs (or drops) the tuning window's
      ConfigVector/TPA.
    * :meth:`db_outage` — whether the PerfDB is unreachable at a tuner
      step; :meth:`wire_tuner` arms a bound tuner with this injector,
      enables its shrink-hysteresis clamp when telemetry noise is
      configured, and programs the watermark controller's actuation lag.
    """

    spec: FaultSpec
    _states: dict = field(default_factory=dict)  # pool -> _PoolFaultState

    def __post_init__(self) -> None:
        if isinstance(self.spec, dict):
            self.spec = FaultSpec.from_dict(self.spec)

    # ------------------------------------------------------------- state
    def _state(self, pool) -> _PoolFaultState:
        st = self._states.get(pool)
        if st is None:
            st = self._states[pool] = _PoolFaultState(int(pool.num_pages))
        return st

    def events(self, pool) -> list:
        """The event log of one pool's trajectory (chronological)."""
        st = self._states.get(pool)
        return list(st.events) if st is not None else []

    def all_events(self) -> list:
        """Every logged event, pools in first-seen order."""
        out: list[dict] = []
        for st in self._states.values():
            out.extend(st.events)
        return out

    # ---------------------------------------------------------- interval
    def begin_interval(self, pool) -> int:
        """Advance the pool's interval cursor; returns the new index."""
        st = self._state(pool)
        st.t += 1
        return st.t

    def kswapd_budget(self, pool, base: int) -> int:
        """Effective kswapd batch for this (pool, interval)."""
        sp = self.spec
        st = self._state(pool)
        t = max(st.t, 0)
        if sp.kswapd_stall_rate > 0.0 and sp.kswapd_stall_len > 0:
            for k in range(min(sp.kswapd_stall_len, t + 1)):
                if _u01_scalar(t - k, sp.seed, _SALT_STALL) < sp.kswapd_stall_rate:
                    st.events.append({"i": t, "kind": "kswapd_stall"})
                    return 0
        if sp.demote_fail_rate > 0.0 and base > 0:
            # seeded probabilistic rounding of base * rate failed slots
            u = _u01_scalar(t, sp.seed, _SALT_DEMOTE)
            n_fail = int(base * sp.demote_fail_rate + u)
            if n_fail > 0:
                n_fail = min(n_fail, base)
                st.events.append(
                    {"i": t, "kind": "demote_fail", "count": n_fail}
                )
                return base - n_fail
        return base

    # --------------------------------------------------------- migration
    def filter_promotions(self, pool, cand: np.ndarray):
        """Inject transient promotion failures into admitted candidates.

        Returns ``(kept, n_failed)`` where ``kept`` is a subsequence of
        ``cand`` (preserving the hottest-first stable order the scheduler
        requires) and ``n_failed`` counts this interval's injected failed
        attempts (transient + exhausted), to be added to the outcome's
        ``pm_fail``. Pages in backoff are withheld without counting as a
        new attempt. A page's ``max_retries + 1``-th consecutive failure
        abandons the migration: the page is credited to
        ``pool.stats.pgpromote_fail`` and its retry state resets.
        """
        sp = self.spec
        if sp.promote_fail_rate <= 0.0 or cand.size == 0:
            return cand, 0
        st = self._state(pool)
        t = max(st.t, 0)
        keep = np.ones(cand.size, dtype=bool)
        in_backoff = st.blocked_until[cand] > t
        n_withheld = int(in_backoff.sum())
        if n_withheld:
            keep[in_backoff] = False
            st.events.append(
                {"i": t, "kind": "promote_backoff_withheld", "count": n_withheld}
            )
        attempt_idx = np.flatnonzero(~in_backoff)
        attempt = cand[attempt_idx]
        # the interval term is mixed in Python int space: a scalar uint64
        # product would raise numpy's overflow warning (array ops wrap)
        t_mix = np.uint64((t * 0x9E3779B97F4A7C15) & _MASK)
        keys = attempt.astype(np.uint64) * _PAGE_STRIDE + t_mix
        fail = _u01(keys, sp.seed, _SALT_PROMOTE) < sp.promote_fail_rate
        n_failed = int(fail.sum())
        if n_failed:
            keep[attempt_idx[fail]] = False
            failed = attempt[fail]
            st.fail_count[failed] += 1
            exhausted = st.fail_count[failed] > sp.max_retries
            exh_pages = failed[exhausted]
            retrying = failed[~exhausted]
            if exh_pages.size:
                # abandoned migrations: the paper's failure counter sees
                # them, and the page may restart a fresh attempt later
                pool.stats.pgpromote_fail += int(exh_pages.size)
                st.fail_count[exh_pages] = 0
                st.blocked_until[exh_pages] = 0
                st.events.append(
                    {"i": t, "kind": "promote_fail_exhausted",
                     "count": int(exh_pages.size)}
                )
            if retrying.size:
                st.blocked_until[retrying] = t + sp.backoff_base * (
                    2 ** (st.fail_count[retrying] - 1)
                )
                st.events.append(
                    {"i": t, "kind": "promote_fail_transient",
                     "count": int(retrying.size)}
                )
        ok = attempt[~fail]
        if ok.size:
            st.fail_count[ok] = 0  # a successful attempt clears the streak
        return cand[keep], n_failed

    # --------------------------------------------------------- telemetry
    def telemetry(self, pool, cv, tpa):
        """Perturb one tuning window's telemetry.

        Returns ``(cv, tpa, ok)``: ``ok=False`` marks a dropout (the
        tuner must hold its last decision); a noise draw scales the
        ConfigVector's migration/access counters and the measured TPA by
        a seeded multiplicative factor in
        ``[1 - scale, 1 + scale]``.
        """
        sp = self.spec
        st = self._state(pool)
        t = max(st.t, 0)
        if (
            sp.telemetry_drop_rate > 0.0
            and _u01_scalar(t, sp.seed, _SALT_DROP) < sp.telemetry_drop_rate
        ):
            st.events.append({"i": t, "kind": "telemetry_dropout"})
            return cv, tpa, False
        if (
            sp.telemetry_noise_rate > 0.0
            and _u01_scalar(t, sp.seed, _SALT_NOISE) < sp.telemetry_noise_rate
        ):
            f = 1.0 + sp.telemetry_noise_scale * (
                2.0 * _u01_scalar(t, sp.seed, _SALT_NOISE_MAG) - 1.0
            )
            st.events.append(
                {"i": t, "kind": "telemetry_noise", "factor": f}
            )
            cv = dataclasses.replace(
                cv,
                pacc_f=cv.pacc_f * f,
                pacc_s=cv.pacc_s * f,
                pm_de=cv.pm_de * f,
                pm_pr=cv.pm_pr * f,
            )
            return cv, tpa * f, True
        return cv, tpa, True

    # ------------------------------------------------------------ perfdb
    def db_outage(self, pool, step_idx: int) -> bool:
        """Whether the PerfDB is unreachable at the tuner's ``step_idx``."""
        sp = self.spec
        if sp.db_outage_rate <= 0.0 or sp.db_outage_len <= 0:
            return False
        for k in range(min(sp.db_outage_len, step_idx + 1)):
            if _u01_scalar(step_idx - k, sp.seed, _SALT_DB) < sp.db_outage_rate:
                self._state(pool).events.append(
                    {"i": int(step_idx), "kind": "db_outage"}
                )
                return True
        return False

    # ------------------------------------------------------------ wiring
    def wire_tuner(self, tuner) -> None:
        """Arm a pool-bound tuner with this injector's fault channels."""
        tuner.fault_injector = self
        if self.spec.telemetry_noise_rate > 0.0:
            # a single noisy window must not trigger a multi-step shrink
            tuner.cfg.shrink_confirm = True
        if self.spec.actuation_lag > 0:
            tuner.controller.lag_steps = int(self.spec.actuation_lag)
            self._state(tuner.controller.pool).events.append(
                {"i": -1, "kind": "actuation_lag",
                 "lag": int(self.spec.actuation_lag)}
            )
