"""Unified experiment API: the declarative front door to the simulator.

The Tuna evaluation is one pipeline — run a workload at a vector of
fast-memory sizes and performance-loss targets, with or without a tuner in
the loop, then compare against the model's prediction. This module exposes
that pipeline as **data**:

* :class:`Scenario` — what to run: a trace (object, workload name, or a
  picklable zero-arg factory), the hardware profile, the hardware fast-tier
  capacity, the RNG seed, pool overrides (``kswapd_batch``,
  ``pool_factory``), and an optional fault model (``faults``, see below).
  A scenario can instead carry a custom ``runner``
  callable, which is how non-simulator engines (e.g. the tiered-KV serving
  benchmark) plug into the same experiment shape.
* :class:`PolicySpec` — how to manage pages: a ``kind`` resolved through
  the :data:`repro.tiering.policy.POLICIES` registry (built-ins:
  ``tpp``, ``admission``, ``thrash_guard``, ``first_touch``; third-party
  backends join via :func:`repro.tiering.policy.register_policy` and need
  zero edits here), a ``params`` dict passed verbatim to the policy
  constructor and echoed losslessly through ``RunSet`` JSON, plus an
  optional :class:`TunerSpec` (allowed iff the registered class is
  ``tunable``). Tuners are *constructed inside the run* from their spec
  (never passed pre-bound), so experiments stay serializable and scenario
  fan-out across processes works.
* :class:`Experiment` — scenarios x fm-size vector x policy variants.
* :func:`run` — executes an experiment and returns a :class:`RunSet`.

The planner inside :func:`run` picks the execution backend per scenario
from the registered policy class's capability flags — there is no
policy-kind string matching anywhere in the planner:

==========================  ==================================================
spec shape                  backend
==========================  ==================================================
untuned batchable vector    one batched :func:`repro.sim.sweep.
                            _sweep_fm_fracs` pass per spec, sweeping its
                            whole size vector (``backend="sweep"``)
any tuner in the loop       one :func:`repro.sim.sweep._sweep_tuned` pass
                            per (kind, hot_thr, params) group — the
                            group's untuned specs ride along as plain
                            slices (``backend="tuned_sweep"``)
unbatchable spec            per-size :func:`repro.sim.engine._simulate` — a
                            custom ``pool_factory`` (e.g. the frozen
                            ``ReferencePagePool`` golden model) or a policy
                            whose class has ``batchable=False`` (e.g.
                            first-touch) (``backend="simulate"``)
``Scenario.runner`` set     the scenario's own callable (``backend="custom"``)
``FleetScenario``           the multi-tenant fleet layer (:mod:`repro.fleet`):
                            tenant traces merge onto disjoint page ranges and
                            each *tenant* becomes one slice of the batched
                            sweep's stacked ``[n_slices, rss]`` tier array —
                            per-tenant pools/tuners/watermarks plus the
                            fleet-level budget arbiter run in one trace pass
                            (``backend="fleet"``, one RunRecord per tenant
                            named ``"{fleet}/{tenant}"``). Numpy sweeps only;
                            every policy must be batchable.
``Scenario.engine="jax"``   the sweep passes above on the jitted JAX device
                            step (:mod:`repro.sim.jax_engine`) instead of the
                            numpy interval loop (``backend="jax_sweep"`` /
                            ``"jax_tuned_sweep"``) — an explicit opt-in,
                            validated up front: fault-free, no custom pool or
                            runner, and every policy class ``jax_batchable``.
                            ``engine="auto"`` (default) and ``"numpy"`` keep
                            the numpy sweeps; results are bit-exact either
                            way, so the choice is purely a speed/provenance
                            knob.
==========================  ==================================================

Scenarios fan out across processes with ``concurrent.futures``
(``parallelism=None`` keeps the database-build heuristic: serial below 12
scenarios, else one worker per core) — except ``engine="jax"`` scenarios,
which always run in the calling process, the one that holds the
accelerator. This is what absorbed the old
``build_database`` fan-out helper. The fan-out is resilient: a scenario
that raises inside a worker is re-raised in the parent as
:class:`ScenarioExecutionError` naming the scenario and echoing its spec;
``run(scenario_timeout=...)`` bounds each scenario's wall-clock (a hung
worker raises instead of blocking forever); a broken executor (OOM-killed
worker, fork ban) gets ONE fresh executor for the unfinished scenarios
before the planner falls back to serial execution. Every backend is
bit-exact against the pre-redesign entry points (``simulate`` /
``sweep_fm_fracs`` / ``sweep_tuned``), which ``tests/test_api.py`` pins —
counters, interval times, config vectors, tuner decision lists, watermark
event logs.

Fault model (``Scenario.faults``)
---------------------------------
A :class:`~repro.sim.faults.FaultSpec` turns on the seeded, deterministic
fault-injection layer (:mod:`repro.sim.faults`): transient promotion
failures with per-page bounded retry + exponential backoff (exhausted
retries credit ``pgpromote_fail``), kswapd stall windows and demotion
shedding, telemetry dropout/noise at tuning steps, PerfDB query outages
(the tuner holds, retries with backoff, then freezes its watermarks —
surfaced per decision via ``TunerDecision.degraded``), and
watermark-actuation lag. Every decision is a pure hash of
``(seed, interval, page)``, so the per-size engine, the batched sweeps,
and fan-out workers reproduce identical fault schedules; every injected
event is logged into the RunSet provenance (``runs[*].fault_events``).
``faults=None`` (the default) keeps the exact fault-free hot path.

RunSet JSON schema (``RunSet.to_json`` / ``RunSet.from_json``)
--------------------------------------------------------------
Lossless (floats round-trip via ``repr``), versioned by ``schema``.
Current version ``tuna-runset-v4``: additive over v3 — run entries
gained the ``arbiter_log`` (fleet runs: the budget arbiter's allocation
events as plain dicts), and fleet scenario echoes carry a ``fleet``
block (``budget_frac``, ``arbiter`` spec, per-tenant
``name``/``trace``/``share``/``floor_frac``/``ceil_frac``) instead of
the trace/runner fields. v3 added the ``faults`` spec echo, the
``fault_events`` log, and the decision ``degraded`` marker over v2; v2
added the policy ``params`` echo over v1. :meth:`RunSet.from_json`
still loads v1–v3 documents (missing keys take their defaults)::

    {
      "schema": "tuna-runset-v4",
      "name": str,                     # experiment name
      "spec": {                        # provenance: the experiment echo
        "name": str,
        "fm_fracs": [float, ...],
        "collect_configs": bool,
        "scenarios": [{"name", "trace", "seed", "hw",
                       "hw_capacity_pages", "kswapd_batch",
                       "pool_factory", "fast_only_at_full",
                       "runner", "params",
                       "faults": {FaultSpec fields} | null}, ...],
        "policies":  [{"label", "kind", "hot_thr", "fm_frac",
                       "params": {policy-constructor kwargs},
                       "tuner": {TunerSpec fields} | null}, ...],
        "db_records": int | null       # size of the PerfDB used
      },
      "chunked_step_count": int,       # chunked-loop executions inside the
                                       # sweep backends (0 = sweeps stayed
                                       # fully vectorized)
      "backends": [str, ...],          # backends the planner used
      "runs": [{
        "scenario": str, "policy": str, "fm_frac": float, "backend": str,
        "result":                      # one per (scenario, policy, size)
          {"kind": "sim", "name": str, "total_time": float,
           "interval_times": [float, ...], "fm_sizes": [int, ...],
           "configs": [{ConfigVector fields}, ...],
           "stats": {counter: int, ...},
           "costs": [{IntervalCosts fields}, ...]}
          | {"kind": "custom", "payload": <runner dict>},
        "decisions":                   # tuned specs only, else null
          [{"t", "config": {ConfigVector fields}, "fm_frac", "fm_pages",
            "predicted_loss", "degraded": str | null}, ...] | null,
        "watermark_log": [{"t", "old_fm", "new_fm"}, ...] | null,
        "fault_events":                # fault-injected runs only
          [{"i": int, "kind": str, ...}, ...] | null,
        "arbiter_log":                 # fleet runs only (shared per fleet)
          [{"interval", "t", "mode", "desired": [int, ...],
            "granted": [int, ...], "degraded"}, ...] | null
      }, ...]
    }

``runs`` order is deterministic: scenario-major (experiment order), then
policy order, then size order. ``chunked_step_count`` counts only the sweep
backends — the per-size ``simulate`` fallback may legitimately execute the
chunked loop; the sweeps must not, and the engine benchmark asserts it.
The count is aggregated from the *per-policy-instance* counters
(:attr:`repro.tiering.policy.MigrationPolicy.chunked_steps`) of the
instances this run constructed, so concurrent ``run()`` calls and fan-out
workers can never cross-pollute each other's provenance.

Result caching
--------------
``run(experiment, ..., cache_dir=...)`` memoizes the whole RunSet as its
JSON document under ``cache_dir`` (opt-in; the benchmark drivers pass
``benchmarks/_cache``). The key is a stable hash of the experiment spec
echo plus the RunSet schema version, so any spec change — or a schema
bump — misses cleanly. Spec echoes identify traces by name/RSS (factory
callables by qualified name, plus bound arguments for
``functools.partial`` factories) and the database by record count only:
regenerating a workload or rebuilding the database under the same
identity requires deleting the cache directory, exactly like the
existing trace/perfdb caches (see ``benchmarks/common.py``).
"""

from __future__ import annotations

import concurrent.futures as cf
import functools
import hashlib
import inspect
import json
import multiprocessing as mp
import os
import pickle
import re
import sys
import uuid
from dataclasses import asdict, dataclass, field
from dataclasses import fields as dataclass_fields
from pathlib import Path
from typing import Callable, Sequence

import numpy as np

from repro.core.telemetry import ConfigVector
from repro.core.trace import Trace
from repro.core.tuner import TunaTuner, TunerConfig, TunerDecision
from repro.core.watermark import WatermarkController, WatermarkEvent
from repro.runtime import tracing
from repro.sim.costmodel import HardwareProfile, IntervalCosts, OPTANE_LIKE
from repro.sim.engine import SimResult, _simulate
from repro.sim.faults import FaultInjector, FaultSpec
from repro.sim.sweep import TunedSlice, _sweep_fm_fracs, _sweep_tuned
from repro.tiering.page_pool import TieredPagePool
from repro.tiering.policy import register_policy, resolve_policy

RUNSET_SCHEMA = "tuna-runset-v4"
# older schema versions from_json still understands (additive evolution)
RUNSET_SCHEMA_COMPAT = (
    "tuna-runset-v1",
    "tuna-runset-v2",
    "tuna-runset-v3",
    RUNSET_SCHEMA,
)

__all__ = [
    "Experiment",
    "FaultSpec",
    "PolicySpec",
    "RunRecord",
    "RunSet",
    "RUNSET_SCHEMA",
    "Scenario",
    "ScenarioExecutionError",
    "TunerSpec",
    "run",
]


class ScenarioExecutionError(RuntimeError):
    """A scenario failed (or timed out) during :func:`run` fan-out.

    Wraps the worker-side exception with the failing scenario's name and
    its spec echo, so a fan-out failure is diagnosable without re-running
    serially; the original exception rides along as ``__cause__``.
    """


# ------------------------------------------------------------------- specs


@dataclass(frozen=True)
class TunerSpec:
    """Declarative Tuna tuner: everything needed to *construct* a
    :class:`~repro.core.tuner.TunaTuner` + unbound
    :class:`~repro.core.watermark.WatermarkController` pair inside the run
    (the performance database itself is passed to :func:`run` — it is
    runtime state, not spec)."""

    target_loss: float = 0.05
    tune_every: int = 3  # profiling intervals per tuning step
    k_neighbors: int = 3
    cooldown_windows: int = 3
    min_fm_frac: float = 0.05
    feedback: bool = True
    feedback_margin: float = 1.0
    tuning_interval_s: float = 2.5
    # watermark-controller actuation limits
    max_step_frac: float = 0.10
    deadband_frac: float = 0.005
    # resilience knobs (see repro.core.tuner.TunerConfig): db outage
    # retries before the watermarks freeze, and the shrink-hysteresis
    # clamp (auto-enabled by the fault layer when telemetry noise is
    # injected; False keeps the legacy bit-exact behaviour)
    db_retry_limit: int = 3
    shrink_confirm: bool = False

    def build(self, db) -> TunaTuner:
        """Construct the live tuner (controller unbound; the execution
        backend binds it to its pool)."""
        if db is None:
            raise ValueError(
                "PolicySpec has a TunerSpec but run() was given no "
                "performance database (db=None)"
            )
        return TunaTuner(
            db,
            WatermarkController(
                max_step_frac=self.max_step_frac,
                deadband_frac=self.deadband_frac,
            ),
            TunerConfig(
                target_loss=self.target_loss,
                tuning_interval_s=self.tuning_interval_s,
                k_neighbors=self.k_neighbors,
                min_fm_frac=self.min_fm_frac,
                feedback=self.feedback,
                feedback_margin=self.feedback_margin,
                cooldown_windows=self.cooldown_windows,
                db_retry_limit=self.db_retry_limit,
                shrink_confirm=self.shrink_confirm,
            ),
        )


@dataclass(frozen=True)
class PolicySpec:
    """One page-management variant of an experiment.

    ``kind`` names a class registered in
    :data:`repro.tiering.policy.POLICIES` — built-ins: ``"tpp"``
    (promotion/watermark-reclaim, the paper's management system),
    ``"admission"`` (TierBPF-style migration admission control),
    ``"thrash_guard"`` (Jenga-style ping-pong backoff), ``"first_touch"``
    (no migration, the Fig. 1 baseline); anything a third party registered
    works identically. ``params`` is passed verbatim to the policy
    constructor (it must be JSON-serializable — it is echoed losslessly in
    the ``RunSet`` provenance). ``tuner`` puts a Tuna tuner in the loop,
    allowed iff the registered class is ``tunable``. ``fm_frac`` overrides
    the experiment's size vector for this spec — tuned specs usually start
    at 1.0 while untuned curves sweep the vector.
    """

    kind: str = "tpp"
    hot_thr: int = 4
    tuner: TunerSpec | None = None
    fm_frac: float | None = None
    label: str | None = None
    params: dict = field(default_factory=dict)

    def __post_init__(self) -> None:
        cls = resolve_policy(self.kind)  # raises listing registered kinds
        if self.tuner is not None and not cls.tunable:
            raise ValueError(
                f"policy kind {self.kind!r} ({cls.__qualname__}) is not "
                "tunable (registry tunable=False); tuners require a kind "
                "whose registered class sets tunable=True"
            )
        if "hot_thr" in self.params:
            # the dedicated field both feeds the constructor and keys the
            # planner's sweep grouping; a params duplicate would bypass
            # the grouping and then TypeError inside a fan-out worker
            raise ValueError(
                "pass hot_thr via the PolicySpec.hot_thr field, not params"
            )
        sig = inspect.signature(cls.__init__)
        accepts_any = any(
            p.kind is p.VAR_KEYWORD for p in sig.parameters.values()
        )
        if not accepts_any:
            unknown = sorted(set(self.params) - set(sig.parameters))
            if unknown:
                accepted = sorted(
                    k for k in sig.parameters if k not in ("self", "hot_thr")
                )
                raise ValueError(
                    f"policy kind {self.kind!r} does not accept params "
                    f"{unknown}; {cls.__qualname__} accepts {accepted}"
                )

    @property
    def name(self) -> str:
        if self.label is not None:
            return self.label
        base = self.kind
        if self.params:
            # distinct params must yield distinct default labels, or a
            # params sweep trips run()'s duplicate-label validation
            kv = ",".join(
                f"{k}={v!r}" for k, v in sorted(self.params.items())
            )
            base = f"{self.kind}({kv})"
        if self.tuner is not None:
            return (
                f"{base}+tuna(tau={self.tuner.target_loss:g},"
                f"every={self.tuner.tune_every})"
            )
        return base

    @property
    def policy_cls(self):
        """The registered :class:`~repro.tiering.policy.MigrationPolicy`
        subclass this spec resolves to (capability flags live here)."""
        return resolve_policy(self.kind)

    def build_policy(self):
        return self.policy_cls(hot_thr=self.hot_thr, **self.params)


@dataclass
class Scenario:
    """What to run: workload + hardware + seed + pool overrides.

    ``trace`` is a :class:`~repro.core.trace.Trace`, a workload name from
    :data:`repro.sim.workloads.WORKLOADS`, or a picklable zero-arg callable
    returning a Trace (resolved inside the worker, so process fan-out does
    not ship trace arrays). ``pool_factory`` forces the per-size
    ``simulate`` backend (the batched sweeps are specialized to the
    incremental :class:`~repro.tiering.page_pool.TieredPagePool`).
    ``fast_only_at_full`` runs full-size slices (``fm_frac >= 1``) on
    ``trace.fast_only()`` — the micro-benchmark's NP_slow = 0 baseline
    variant (paper Section 3.2/3.3) the database build needs.
    ``runner(scenario, fm_frac, policy_spec, db) -> dict`` swaps the whole
    execution engine (``backend="custom"``); ``params`` carries its
    JSON-serializable knobs. ``faults`` opts into the deterministic
    fault-injection layer (module docstring, *Fault model*); each
    simulator backend gets its own :class:`~repro.sim.faults.
    FaultInjector` over the same spec — identical seeded schedules,
    independent per-pool trajectories. ``engine`` selects the sweep
    backend: ``"auto"`` (default, currently the numpy sweeps),
    ``"numpy"`` (pin the oracle), or ``"jax"`` (the jitted device step —
    see the planner table in the module docstring for the eligibility
    rules :func:`run` enforces).
    """

    trace: Trace | str | Callable[[], Trace] | None = None
    name: str | None = None
    hw: HardwareProfile = OPTANE_LIKE
    hw_capacity_pages: int | None = None
    seed: int = 0
    kswapd_batch: int | None = None
    pool_factory: Callable | None = None
    fast_only_at_full: bool = False
    runner: Callable | None = None
    params: dict = field(default_factory=dict)
    faults: FaultSpec | None = None
    engine: str = "auto"  # "auto" | "numpy" | "jax" (sweep backend)

    @property
    def resolved_name(self) -> str:
        if self.name is not None:
            return self.name
        if isinstance(self.trace, Trace):
            return self.trace.name
        if isinstance(self.trace, str):
            return self.trace
        if self.trace is not None:
            f = getattr(self.trace, "func", self.trace)
            return getattr(f, "__name__", "scenario")
        return "scenario"


@dataclass
class Experiment:
    """Scenarios x fm-size vector x policy variants.

    ``collect_configs`` asks the untuned sweep backend for per-interval
    :class:`~repro.core.telemetry.ConfigVector` telemetry (the tuned sweep
    and the per-size engine always collect it).
    """

    scenarios: Sequence[Scenario]
    fm_fracs: Sequence[float] = (1.0,)
    policies: Sequence[PolicySpec] = (PolicySpec(),)
    collect_configs: bool = False
    name: str = "experiment"


# ----------------------------------------------------------------- results


@dataclass
class RunRecord:
    """One (scenario, policy, fm size) cell of a :class:`RunSet`."""

    scenario: str
    policy: str
    fm_frac: float
    backend: str  # "sweep" | "tuned_sweep" | "jax_sweep" |
    # "jax_tuned_sweep" | "simulate" | "custom" | "fleet"
    result: SimResult | dict
    decisions: list | None = None  # TunerDecision list (tuned specs)
    watermark_log: list | None = None  # WatermarkEvent list (tuned specs)
    fault_events: list | None = None  # injected-fault log (fault runs)
    # fleet runs only: the FleetTunaArbiter's allocation-event log as
    # plain dicts (shared across the fleet's tenant records)
    arbiter_log: list | None = None


@dataclass
class RunSet:
    """Uniform result of :func:`run`: named, stacked per-slice results plus
    provenance (spec echo, seeds, backends used, ``chunked_step_count``).
    Lossless ``to_json``/``from_json`` — the schema is documented in the
    module docstring."""

    name: str
    spec: dict
    runs: list
    chunked_step_count: int = 0
    backends: tuple = ()

    # ------------------------------------------------------------ access
    def select(
        self,
        scenario: str | None = None,
        policy: str | None = None,
        fm_frac: float | None = None,
    ) -> list:
        out = []
        for r in self.runs:
            if scenario is not None and r.scenario != scenario:
                continue
            if policy is not None and r.policy != policy:
                continue
            if fm_frac is not None and abs(r.fm_frac - fm_frac) > 1e-12:
                continue
            out.append(r)
        return out

    def record(self, **kw) -> RunRecord:
        recs = self.select(**kw)
        if len(recs) != 1:
            raise KeyError(
                f"RunSet.record({kw}) matched {len(recs)} runs, expected 1"
            )
        return recs[0]

    def result(self, **kw):
        return self.record(**kw).result

    def results(self, **kw) -> list:
        return [r.result for r in self.select(**kw)]

    def total_times(
        self, scenario: str | None = None, policy: str | None = None
    ) -> np.ndarray:
        """Total execution time of every matching run, in ``runs`` order.

        Simulator-backed runs participate via ``SimResult.total_time``.
        Custom-runner payloads participate via the **interval-times
        protocol**: a payload ``dict`` that carries ``"total_time"`` (a
        float, preferred) and/or ``"interval_times"`` (a list of floats
        summed as a fallback) declares its timing to the reporting
        helpers — ``repro.timing.runner.timing_runner`` emits both.
        Payloads that declare neither key are rejected explicitly, as
        before.
        """
        out = []
        for r in self.select(scenario, policy):
            res = r.result
            if isinstance(res, SimResult):
                out.append(res.total_time)
            elif isinstance(res, dict) and "total_time" in res:
                out.append(float(res["total_time"]))
            elif isinstance(res, dict) and "interval_times" in res:
                out.append(float(np.sum(res["interval_times"])))
            else:
                raise TypeError(
                    f"total_times() needs simulator results or payloads "
                    f"with 'total_time'/'interval_times'; run "
                    f"{r.scenario!r}/{r.policy!r} has backend={r.backend!r}"
                )
        return np.array(out)

    # ----------------------------------------------------- serialization
    def to_json(self, indent: int | None = None) -> str:
        return json.dumps(
            {
                "schema": RUNSET_SCHEMA,
                "name": self.name,
                "spec": self.spec,
                "chunked_step_count": int(self.chunked_step_count),
                "backends": list(self.backends),
                "runs": [
                    {
                        "scenario": r.scenario,
                        "policy": r.policy,
                        "fm_frac": r.fm_frac,
                        "backend": r.backend,
                        "result": _result_to_dict(r.result),
                        "decisions": (
                            None
                            if r.decisions is None
                            else [_decision_to_dict(d) for d in r.decisions]
                        ),
                        "watermark_log": (
                            None
                            if r.watermark_log is None
                            else [asdict(e) for e in r.watermark_log]
                        ),
                        "fault_events": r.fault_events,
                        "arbiter_log": r.arbiter_log,
                    }
                    for r in self.runs
                ],
            },
            indent=indent,
        )

    @classmethod
    def from_json(cls, text: str) -> "RunSet":
        d = json.loads(text)
        if d.get("schema") not in RUNSET_SCHEMA_COMPAT:
            raise ValueError(f"unknown RunSet schema: {d.get('schema')!r}")
        runs = [
            RunRecord(
                scenario=r["scenario"],
                policy=r["policy"],
                fm_frac=float(r["fm_frac"]),
                backend=r["backend"],
                result=_result_from_dict(r["result"]),
                decisions=(
                    None
                    if r["decisions"] is None
                    else [_decision_from_dict(x) for x in r["decisions"]]
                ),
                watermark_log=(
                    None
                    if r["watermark_log"] is None
                    else [WatermarkEvent(**x) for x in r["watermark_log"]]
                ),
                fault_events=r.get("fault_events"),
                arbiter_log=r.get("arbiter_log"),
            )
            for r in d["runs"]
        ]
        return cls(
            name=d["name"],
            spec=d["spec"],
            runs=runs,
            chunked_step_count=int(d["chunked_step_count"]),
            backends=tuple(d["backends"]),
        )


def _result_to_dict(res) -> dict:
    if isinstance(res, SimResult):
        return {
            "kind": "sim",
            "name": res.name,
            "total_time": float(res.total_time),
            "interval_times": [float(x) for x in res.interval_times],
            "fm_sizes": [int(x) for x in res.fm_sizes],
            "configs": [c.to_dict() for c in res.configs],
            "stats": {k: int(v) for k, v in res.stats.items()},
            "costs": [asdict(c) for c in res.costs],
        }
    return {"kind": "custom", "payload": res}


def _result_from_dict(d: dict):
    if d["kind"] == "custom":
        return d["payload"]
    return SimResult(
        name=d["name"],
        total_time=float(d["total_time"]),
        interval_times=np.array(d["interval_times"], dtype=np.float64),
        configs=[ConfigVector(**c) for c in d["configs"]],
        fm_sizes=np.array(d["fm_sizes"], dtype=np.int64),
        stats=dict(d["stats"]),
        costs=[IntervalCosts(**c) for c in d["costs"]],
    )


def _decision_to_dict(d: TunerDecision) -> dict:
    return {
        "t": d.t,
        "config": None if d.config is None else d.config.to_dict(),
        "fm_frac": d.fm_frac,
        "fm_pages": d.fm_pages,
        "predicted_loss": d.predicted_loss,
        "degraded": d.degraded,
    }


def _decision_from_dict(d: dict) -> TunerDecision:
    return TunerDecision(
        t=d["t"],
        config=(
            None if d["config"] is None else ConfigVector(**d["config"])
        ),
        fm_frac=d["fm_frac"],
        fm_pages=d["fm_pages"],
        predicted_loss=d["predicted_loss"],
        degraded=d.get("degraded"),
    )


# ----------------------------------------------------------------- planner


@tracing.traced("scenario.trace")
def _resolve_trace(scenario: Scenario) -> Trace | None:
    tr = scenario.trace
    if tr is None or isinstance(tr, Trace):
        return tr
    if isinstance(tr, str):
        from repro.sim.workloads import WORKLOADS

        return WORKLOADS[tr]()
    return tr()


def _spec_fracs(spec: PolicySpec, fm_fracs: tuple) -> tuple:
    return (float(spec.fm_frac),) if spec.fm_frac is not None else fm_fracs


def _sim_result_from_slice(sweep_res, i: int, eff_fm: int) -> SimResult:
    """Lift one fixed-size sweep slice into the uniform SimResult shape
    (bit-identical to the per-size engine's result for the same slice)."""
    times = sweep_res.interval_times[i]
    return SimResult(
        name=sweep_res.name,
        total_time=float(np.sum(times)),
        interval_times=times.copy(),
        configs=(
            sweep_res.configs[i] if sweep_res.configs is not None else []
        ),
        fm_sizes=np.full(times.size, eff_fm, dtype=np.int64),
        stats=sweep_res.stats[i],
        costs=list(sweep_res.costs[i]) if sweep_res.costs is not None else [],
    )


def _effective_fm(cap: int, frac: float) -> int:
    # Watermarks.for_size clamping: what effective_fm_size reports all run
    return int(max(1, min(cap, int(round(frac * cap)))))


@tracing.traced("scenario")
def _run_scenario(
    scenario: Scenario,
    fm_fracs: tuple,
    policies: tuple,
    db,
    collect_configs: bool,
    policy_classes: tuple = (),
):
    """Execute every (policy, size) cell of one scenario.

    Returns ``(records, chunked)`` where ``records`` is in (policy-major,
    size) order and ``chunked`` counts chunked-loop executions inside the
    *sweep* backends only. Module-level so the process fan-out can pickle
    it. ``policy_classes`` carries the specs' resolved policy classes:
    spawn-start fan-out workers re-import :mod:`repro` but not the user
    module that registered a third-party kind, so the classes ride the
    job payload (pickled by reference, which imports their defining
    module) and are re-registered here before any spec resolves.
    """
    for cls in policy_classes:
        register_policy(cls)

    if getattr(scenario, "is_fleet", False):
        # FleetScenario (repro.fleet): tenants-as-slices over the batched
        # sweep, one RunRecord per tenant (lazy import — repro.fleet
        # imports this module at load time, the reverse edge is runtime)
        from repro.fleet.runner import run_fleet_scenario

        return run_fleet_scenario(
            scenario, fm_fracs, policies, db, collect_configs
        )

    sname = scenario.resolved_name
    cells: dict = {}
    chunked = 0

    if scenario.runner is not None:
        for pi, spec in enumerate(policies):
            for fi, f in enumerate(_spec_fracs(spec, fm_fracs)):
                payload = scenario.runner(scenario, float(f), spec, db)
                cells[(pi, fi)] = RunRecord(
                    sname, spec.name, float(f), "custom", payload
                )
        return _ordered(cells, policies, fm_fracs), 0

    trace = _resolve_trace(scenario)
    if trace is None:
        raise ValueError(f"scenario {sname!r} has neither trace nor runner")
    cap = int(scenario.hw_capacity_pages or trace.rss_pages)
    faults = scenario.faults
    # sweep backend routing (validated by run(); "auto" stays on numpy)
    sweep_engine = "jax" if getattr(scenario, "engine", "auto") == "jax" else "numpy"
    sweep_backend = "jax_sweep" if sweep_engine == "jax" else "sweep"
    tuned_backend = "jax_tuned_sweep" if sweep_engine == "jax" else "tuned_sweep"

    def make_injector():
        # one injector per constructed policy instance: identical seeded
        # schedules (pure hashes of the spec seed), independent per-pool
        # retry/event state
        return FaultInjector(faults) if faults is not None else None

    def trace_for(frac: float) -> Trace:
        if scenario.fast_only_at_full and frac >= 1.0 - 1e-9:
            return trace.fast_only()
        return trace

    # --- partition specs: batchable (registry capability flag) vs the
    #     per-size engine fallback; batchable specs group per constructed
    #     policy identity (kind, hot_thr, params) — a group with a tuner
    #     shares ONE tuned sweep pass, untuned specs sweep their own size
    #     vector (one pass per spec; sizes, not specs, are what batch)
    sim_cells: list = []
    groups: dict = {}  # (kind, hot_thr, params-json) -> [(pi, spec)]
    for pi, spec in enumerate(policies):
        if scenario.pool_factory is not None or not spec.policy_cls.batchable:
            for fi, f in enumerate(_spec_fracs(spec, fm_fracs)):
                sim_cells.append((pi, fi, float(f), spec))
        else:
            key = (
                spec.kind,
                spec.hot_thr,
                json.dumps(spec.params, sort_keys=True),
            )
            groups.setdefault(key, []).append((pi, spec))

    for group in groups.values():
        if any(spec.tuner is not None for _, spec in group):
            # one tuned sweep carries the whole group; untuned specs ride
            # along as plain (tuner-free) slices. fast_only_at_full splits
            # the group by trace variant (full-size slices run the
            # NP_slow = 0 variant), at most two passes. One policy
            # instance serves every pass (stateful policies scope their
            # state per slice pool).
            group_policy = group[0][1].build_policy()
            inj = make_injector()
            if inj is not None:
                group_policy.fault_injector = inj
            by_variant: dict = {}
            for pi, spec in group:
                for fi, f in enumerate(_spec_fracs(spec, fm_fracs)):
                    tuner = (
                        spec.tuner.build(db)
                        if spec.tuner is not None
                        else None
                    )
                    te = (
                        spec.tuner.tune_every
                        if spec.tuner is not None
                        else None
                    )
                    use_fast_only = (
                        scenario.fast_only_at_full and f >= 1.0 - 1e-9
                    )
                    slices, keys = by_variant.setdefault(
                        use_fast_only, ([], [])
                    )
                    slices.append(TunedSlice(float(f), tuner, te))
                    keys.append((pi, fi, float(f), spec, tuner))
            results, keys = [], []
            flog: list | None = [] if inj is not None else None
            for use_fast_only, (slices, vkeys) in by_variant.items():
                results.extend(
                    _sweep_tuned(
                        trace.fast_only() if use_fast_only else trace,
                        slices,
                        hw=scenario.hw,
                        hw_capacity_pages=scenario.hw_capacity_pages,
                        seed=scenario.seed,
                        kswapd_batch=scenario.kswapd_batch,
                        policy=group_policy,
                        faults=inj,
                        fault_log=flog,
                        engine=sweep_engine,
                    )
                )
                keys.extend(vkeys)
            chunked += group_policy.chunked_steps
            for si, ((pi, fi, f, spec, tuner), res) in enumerate(
                zip(keys, results)
            ):
                cells[(pi, fi)] = RunRecord(
                    sname,
                    spec.name,
                    f,
                    tuned_backend,
                    res,
                    decisions=(
                        list(tuner.decisions) if tuner is not None else None
                    ),
                    watermark_log=(
                        list(tuner.controller.log)
                        if tuner is not None
                        else None
                    ),
                    fault_events=flog[si] if flog is not None else None,
                )
        else:
            for pi, spec in group:
                # one policy instance per spec, shared across its trace
                # variants (state is per pool, so variants stay isolated)
                spec_policy = spec.build_policy()
                inj = make_injector()
                if inj is not None:
                    spec_policy.fault_injector = inj
                fracs = _spec_fracs(spec, fm_fracs)
                farr = np.asarray(fracs, dtype=np.float64)
                full = (
                    farr >= 1.0 - 1e-9
                    if scenario.fast_only_at_full
                    else np.zeros(farr.size, dtype=bool)
                )
                parts = []
                if bool(full.any()):
                    parts.append((np.flatnonzero(full), trace.fast_only()))
                if bool((~full).any()):
                    parts.append((np.flatnonzero(~full), trace))
                for idxs, tr in parts:
                    flog = [] if inj is not None else None
                    res = _sweep_fm_fracs(
                        tr,
                        farr[idxs],
                        hw=scenario.hw,
                        hw_capacity_pages=scenario.hw_capacity_pages,
                        seed=scenario.seed,
                        collect_configs=collect_configs,
                        kswapd_batch=scenario.kswapd_batch,
                        policy=spec_policy,
                        faults=inj,
                        fault_log=flog,
                        engine=sweep_engine,
                    )
                    for j, fi in enumerate(idxs):
                        f = float(farr[fi])
                        cells[(pi, int(fi))] = RunRecord(
                            sname,
                            spec.name,
                            f,
                            sweep_backend,
                            _sim_result_from_slice(
                                res, j, _effective_fm(cap, f)
                            ),
                            fault_events=(
                                flog[j] if flog is not None else None
                            ),
                        )
                chunked += spec_policy.chunked_steps

    # --- per-size engine fallback (custom pool / unbatchable policies)
    for pi, fi, f, spec in sim_cells:
        pool_factory = scenario.pool_factory or TieredPagePool
        if scenario.kswapd_batch is not None:
            pool_factory = functools.partial(
                pool_factory, kswapd_batch=scenario.kswapd_batch
            )
        tuner = spec.tuner.build(db) if spec.tuner is not None else None
        inj = make_injector()
        res = _simulate(
            trace_for(f),
            fm_frac=f,
            policy=spec.build_policy(),
            hw=scenario.hw,
            hw_capacity_pages=scenario.hw_capacity_pages,
            tuner=tuner,
            tune_every=(
                spec.tuner.tune_every if spec.tuner is not None else None
            ),
            seed=scenario.seed,
            pool_factory=pool_factory,
            faults=inj,
        )
        cells[(pi, fi)] = RunRecord(
            sname,
            spec.name,
            f,
            "simulate",
            res,
            decisions=list(tuner.decisions) if tuner is not None else None,
            watermark_log=(
                list(tuner.controller.log) if tuner is not None else None
            ),
            fault_events=inj.all_events() if inj is not None else None,
        )

    return _ordered(cells, policies, fm_fracs), chunked


def _ordered(cells: dict, policies: tuple, fm_fracs: tuple) -> list:
    return [
        cells[(pi, fi)]
        for pi, spec in enumerate(policies)
        for fi in range(len(_spec_fracs(spec, fm_fracs)))
    ]


def _run_scenario_star(args):
    return _run_scenario(*args)


def _run_scenario_trapped(args):
    """Fan-out wrapper: job exceptions come back as values, so the parent
    can tell a failing *job* (re-raise it) from a failing *executor*
    (fall back to serial) — pool.map folds both into raised exceptions.
    The failing scenario's name and spec echo ride along, so the parent's
    re-raise identifies the job without a serial re-run."""
    sc = args[0]
    try:
        return "ok", _run_scenario(*args)
    except Exception as e:  # noqa: BLE001 - transported, re-raised in parent
        try:
            echo = json.dumps(_scenario_ref(sc), sort_keys=True)
        except Exception:  # noqa: BLE001 - echo is best-effort diagnostics
            echo = "<unserializable scenario spec>"
        return "err", (sc.resolved_name, echo, e)


# --------------------------------------------------------------------- run


def _qualname(obj) -> str | None:
    if obj is None:
        return None
    f = getattr(obj, "func", obj)  # unwrap functools.partial
    if not hasattr(f, "__qualname__"):
        f = type(f)  # instance-based callable: name its class, not its id
    return f"{getattr(f, '__module__', '')}.{f.__qualname__}"


def _arg_ref(v):
    """Deterministic, JSON-serializable identity for a factory-bound
    argument. ``repr`` alone is not enough: numpy reprs truncate interior
    elements (silent cache collisions) and default object reprs embed
    memory addresses (provenance noise + a key that never matches)."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, np.ndarray):
        return {
            "ndarray": hashlib.sha256(
                np.ascontiguousarray(v).tobytes()
            ).hexdigest()[:16],
            "dtype": str(v.dtype),
            "shape": list(v.shape),
        }
    if isinstance(v, (list, tuple)):
        return [_arg_ref(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _arg_ref(x) for k, x in sorted(v.items())}
    r = repr(v)
    if " at 0x" in r:
        # default object repr: the address is nondeterministic, so the
        # value cannot be identified across processes. The marker keeps
        # provenance address-free, and run() refuses to *cache* a spec
        # containing one — a silent wrong-entry hit would be far worse.
        return f"<unidentified:{type(v).__module__}.{type(v).__qualname__}>"
    return r


def _callable_ref(obj) -> dict | str | None:
    """Spec-echo identity for a factory/runner callable. Bound arguments
    of ``functools.partial`` are identity: two partials over the same
    function with different bound configs are different experiments (and
    must not share a cache entry)."""
    if obj is None:
        return None
    if isinstance(obj, functools.partial):
        return {
            "factory": _qualname(obj),
            "args": [_arg_ref(a) for a in obj.args],
            "keywords": {
                k: _arg_ref(v) for k, v in sorted(obj.keywords.items())
            },
        }
    return _qualname(obj)


def _trace_ref(trace) -> dict | str | None:
    if isinstance(trace, Trace):
        return {"name": trace.name, "rss_pages": int(trace.rss_pages)}
    if isinstance(trace, str):
        return trace
    return _callable_ref(trace)


def _scenario_ref(sc) -> dict:
    """One scenario's spec echo (provenance, cache key, error reports)."""
    if getattr(sc, "is_fleet", False):
        return {
            "name": sc.resolved_name,
            "seed": int(sc.seed),
            "hw": asdict(sc.hw),
            "kswapd_batch": sc.kswapd_batch,
            "faults": (
                sc.faults.to_dict() if sc.faults is not None else None
            ),
            "fleet": {
                "budget_frac": float(sc.budget_frac),
                "arbiter": asdict(sc.arbiter),
                "tenants": [
                    {
                        "name": t.resolved_name,
                        "trace": _trace_ref(t.trace),
                        "share": t.share,
                        "floor_frac": float(t.floor_frac),
                        "ceil_frac": float(t.ceil_frac),
                    }
                    for t in sc.tenants
                ],
            },
            **({"engine": sc.engine} if sc.engine != "auto" else {}),
        }
    return {
        "name": sc.resolved_name,
        "trace": _trace_ref(sc.trace),
        "seed": int(sc.seed),
        "hw": asdict(sc.hw),
        "hw_capacity_pages": sc.hw_capacity_pages,
        "kswapd_batch": sc.kswapd_batch,
        "pool_factory": _callable_ref(sc.pool_factory),
        "fast_only_at_full": bool(sc.fast_only_at_full),
        "runner": _callable_ref(sc.runner),
        "params": sc.params,
        "faults": sc.faults.to_dict() if sc.faults is not None else None,
        # echoed only when set: pre-engine cache entries stay addressable,
        # and engine choice never perturbs "auto" cache keys
        **({"engine": sc.engine} if sc.engine != "auto" else {}),
    }


def _experiment_spec(
    experiment: Experiment, fm_fracs: tuple, policies: tuple, db
) -> dict:
    return {
        "name": experiment.name,
        "fm_fracs": list(fm_fracs),
        "collect_configs": bool(experiment.collect_configs),
        "scenarios": [_scenario_ref(sc) for sc in experiment.scenarios],
        "policies": [
            {
                "label": p.name,
                "kind": p.kind,
                "hot_thr": int(p.hot_thr),
                "fm_frac": p.fm_frac,
                "params": dict(p.params),
                "tuner": asdict(p.tuner) if p.tuner is not None else None,
            }
            for p in policies
        ],
        "db_records": (
            len(db.records) if db is not None and hasattr(db, "records") else None
        ),
    }


def _unpicklable_fields(spec_obj) -> list[str]:
    bad = []
    for f in dataclass_fields(spec_obj):
        try:
            pickle.dumps(getattr(spec_obj, f.name))
        except Exception:  # noqa: BLE001 - any pickle failure disqualifies
            bad.append(f.name)
    return bad


def _validate_picklable(scenarios, policies) -> None:
    """Fail fast on specs that cannot cross the process fan-out.

    Fan-out jobs are pickled into worker processes; a lambda or closure
    in a ``Scenario(trace=...)``/``pool_factory``/``runner`` (or a
    policy-spec param) would otherwise die inside the executor's feeder
    thread with an opaque ``PicklingError`` long after ``run()``
    accepted the experiment — and only when the parallelism heuristic
    actually fans out. The static complement is the TUNA008 lint
    (:mod:`repro.analysis`); this is the runtime guard that names the
    offending field.
    """
    for kind, objs, name_of in (
        ("scenario", scenarios, lambda o: o.resolved_name),
        ("policy spec", policies, lambda o: o.name),
    ):
        for obj in objs:
            try:
                pickle.dumps(obj)
            except Exception as e:  # noqa: BLE001 - report any failure
                bad = _unpicklable_fields(obj) or ["<whole object>"]
                raise ScenarioExecutionError(
                    f"{kind} {name_of(obj)!r} cannot be pickled into a "
                    f"fan-out worker: offending field(s) {bad} "
                    f"({type(e).__name__}: {e}). Use a module-level "
                    "function or functools.partial instead of a lambda/"
                    "closure, or force serial execution with "
                    "parallelism=1"
                ) from e


def _resolve_start_method(requested, available, jax_loaded):
    """Pick the fan-out workers' multiprocessing start method.

    ``requested`` (``run()``'s ``mp_start_method``) wins when given and
    available. Otherwise fork (where available) spares each worker the
    interpreter + numpy re-import — unless this process has loaded JAX
    (``jax_loaded``): forking after the XLA runtime has started hands the
    child a copy of XLA's locked thread state, which deadlocks or crashes
    it, so such a parent spawns workers that import repro without JAX.
    Returns a method name from ``available``, or ``None`` for the
    platform default.
    """
    if requested is not None:
        if requested not in available:
            raise ValueError(
                f"mp_start_method {requested!r} is not available on this "
                f"platform (available: {list(available)})"
            )
        return requested
    if jax_loaded:
        return "spawn" if "spawn" in available else None
    return "fork" if "fork" in available else None


def _fanout(jobs: list, parallelism: int, scenario_timeout: float | None,
            start_method: str | None = None):
    """Submit-based process fan-out over scenario jobs.

    Returns the jobs' trapped ``("ok" | "err", ...)`` values in job
    order, or ``None`` when process execution is unavailable (sandboxed
    environment, or the executor broke twice) — the caller then falls
    back to serial. Resilience contract:

    * ``scenario_timeout`` bounds each job's wall-clock; a hung worker
      raises :class:`ScenarioExecutionError` (naming the scenario)
      instead of blocking ``run()`` forever. The dead executor is
      abandoned without joining the hung worker.
    * A broken executor (OOM-killed worker, fork ban mid-run) gets ONE
      fresh executor for the jobs that did not finish; already-completed
      results are kept, not recomputed. A second break gives up on
      process fan-out entirely.
    * Job-level exceptions are *values* (``("err", ...)`` from
      :func:`_run_scenario_trapped`) and never trigger a resubmit or the
      serial fallback — a bad spec or unreadable trace must fail fast,
      not run twice.
    """
    try:
        # the caller resolves the method (see _resolve_start_method);
        # None keeps the platform default
        ctx = mp.get_context(start_method)
    except ValueError:
        return None
    results: list = [None] * len(jobs)
    pending = list(range(len(jobs)))
    for _attempt in range(2):
        try:
            pool = cf.ProcessPoolExecutor(parallelism, mp_context=ctx)
        except (OSError, ValueError):
            return None  # sandboxed / restricted env: serial fallback
        futs = {i: pool.submit(_run_scenario_trapped, jobs[i]) for i in pending}
        broken = False
        timed_out: int | None = None
        for i, fut in futs.items():
            try:
                results[i] = fut.result(timeout=scenario_timeout)
            except cf.TimeoutError:
                # must precede OSError: since 3.11 cf.TimeoutError IS the
                # builtin TimeoutError, an OSError subclass
                timed_out = i
                break
            except (OSError, cf.process.BrokenProcessPool):
                broken = True
                break
        # never shutdown(wait=True): a hung or dying worker would block
        # the parent on join
        pool.shutdown(wait=False, cancel_futures=True)
        if timed_out is not None:
            name = jobs[timed_out][0].resolved_name
            raise ScenarioExecutionError(
                f"scenario {name!r} did not finish within "
                f"scenario_timeout={scenario_timeout:g}s in a fan-out worker"
            )
        if not broken:
            return results
        # salvage whatever completed before the executor died, then
        # resubmit only the remainder on the fresh executor
        for i, fut in futs.items():
            if results[i] is None and fut.done() and not fut.cancelled():
                try:
                    results[i] = fut.result(timeout=0)
                except Exception:  # noqa: BLE001 - died with the executor
                    pass
        pending = [i for i in pending if results[i] is None]
        if not pending:
            return results
    return None


def _cache_path(cache_dir, name: str, spec: dict) -> Path:
    """Cache key: stable hash of the experiment spec echo + the RunSet
    schema version, so spec changes and schema bumps miss cleanly."""
    digest = hashlib.sha256(
        (RUNSET_SCHEMA + "\n" + json.dumps(spec, sort_keys=True)).encode()
    ).hexdigest()[:16]
    safe = re.sub(r"[^A-Za-z0-9._\[\]-]", "_", name)[:60]
    return Path(cache_dir) / f"runset_{safe}_{digest}.json"


@tracing.traced("experiment")
def run(
    experiment: Experiment,
    db=None,
    parallelism: int | None = None,
    cache_dir=None,
    scenario_timeout: float | None = None,
    mp_start_method: str | None = None,
) -> RunSet:
    """Execute ``experiment`` and return a :class:`RunSet`.

    ``db`` is the :class:`~repro.core.perfdb.PerfDB` tuned specs query
    (required iff any :class:`PolicySpec` carries a :class:`TunerSpec`;
    custom runners receive it verbatim). ``parallelism`` fans scenarios out
    across processes — ``None`` keeps the database-build heuristic (serial
    below 12 scenarios, else one worker per core); sandboxed environments
    fall back to serial execution automatically, and a fan-out executor
    that dies mid-run (OOM-killed worker) gets one fresh executor for the
    unfinished scenarios before that fallback. ``scenario_timeout`` bounds
    each fanned-out scenario's wall-clock seconds: a hung worker raises
    :class:`ScenarioExecutionError` instead of blocking forever (``None``
    = no bound; serial runs are never timed out). A scenario that *fails*
    in a worker is re-raised as :class:`ScenarioExecutionError` naming the
    scenario and echoing its spec, with the worker exception as
    ``__cause__``; before anything is submitted, every scenario and
    policy spec is checked picklable upfront, and a lambda/closure in a
    factory field raises :class:`ScenarioExecutionError` naming the
    field instead of dying opaquely inside the pool (the static
    complement is the TUNA008 lint in :mod:`repro.analysis`).
    ``cache_dir`` opts into
    the RunSet result cache (see the module docstring's *Result caching*
    section): a directory under which the whole RunSet is memoized as its
    JSON document, keyed on the experiment spec echo + schema version.
    ``engine="jax"`` scenarios never fan out: they run in this process,
    which holds the accelerator. ``mp_start_method`` pins the fan-out
    workers' multiprocessing start method (``"fork"`` / ``"spawn"`` /
    ``"forkserver"``); ``None`` keeps the fork preference (cheap workers)
    unless this process has loaded JAX, which switches the fan-out to
    spawn, because forking a parent whose XLA runtime is running is unsafe
    (see :func:`_resolve_start_method`).
    """
    scenarios = list(experiment.scenarios)
    if not scenarios:
        raise ValueError("Experiment needs at least one scenario")
    fm_fracs = tuple(float(f) for f in experiment.fm_fracs)
    if not fm_fracs:
        raise ValueError("Experiment needs at least one fm fraction")
    policies = tuple(experiment.policies)
    if not policies:
        raise ValueError("Experiment needs at least one policy spec")
    names = [sc.resolved_name for sc in scenarios]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate scenario names: {names}")
    for sc in scenarios:
        if getattr(sc, "is_fleet", False):
            # fleet scenarios carry tenants instead of a trace/runner;
            # every policy must be batchable (tenants ride sweep slices)
            bad = [
                p.name for p in policies if not p.policy_cls.batchable
            ]
            if bad:
                raise ValueError(
                    f"fleet scenario {sc.resolved_name!r} maps tenants "
                    f"onto batched sweep slices; policy specs {bad} are "
                    "not batchable"
                )
            continue
        if sc.trace is None and sc.runner is None:
            raise ValueError(
                f"scenario {sc.resolved_name!r} has neither trace nor runner"
            )
    pnames = [p.name for p in policies]
    if len(set(pnames)) != len(pnames):
        raise ValueError(f"duplicate policy labels: {pnames}")
    for p in policies:
        try:
            json.dumps(p.params, sort_keys=True)
        except TypeError as e:
            raise ValueError(
                f"policy spec {p.name!r} has non-JSON-serializable params "
                f"(they are echoed in the RunSet provenance): {e}"
            ) from None
    for sc in scenarios:
        try:
            json.dumps(getattr(sc, "params", {}), sort_keys=True)
        except TypeError as e:
            raise ValueError(
                f"scenario {sc.resolved_name!r} has non-JSON-serializable "
                f"params (they are echoed in the RunSet provenance): {e}"
            ) from None
    if db is None and any(p.tuner is not None for p in policies):
        raise ValueError(
            "experiment has tuned policy specs but no performance database "
            "was passed to run(db=...)"
        )
    for sc in scenarios:
        eng = getattr(sc, "engine", "auto")
        if eng not in ("auto", "numpy", "jax"):
            raise ValueError(
                f"scenario {sc.resolved_name!r} has unknown engine {eng!r} "
                "(use 'auto', 'numpy' or 'jax')"
            )
        if getattr(sc, "is_fleet", False):
            if eng == "jax":
                raise ValueError(
                    f"fleet scenario {sc.resolved_name!r}: the fleet "
                    "backend runs the numpy sweep driver; use "
                    "engine='auto' or 'numpy'"
                )
            continue
        if eng != "jax":
            continue
        # the JAX backend only replicates the batched sweep passes; refuse
        # anything that would route off them instead of silently degrading
        if sc.runner is not None:
            raise ValueError(
                f"scenario {sc.resolved_name!r}: engine='jax' cannot wrap a "
                "custom runner"
            )
        if sc.pool_factory is not None:
            raise ValueError(
                f"scenario {sc.resolved_name!r}: engine='jax' requires the "
                "batched sweep backends; a custom pool_factory forces the "
                "per-size simulate fallback"
            )
        if sc.faults is not None:
            raise ValueError(
                f"scenario {sc.resolved_name!r}: engine='jax' does not "
                "support fault injection; use engine='numpy'"
            )
        bad = [
            p.name
            for p in policies
            if not getattr(p.policy_cls, "jax_batchable", False)
        ]
        if bad:
            raise ValueError(
                f"scenario {sc.resolved_name!r}: engine='jax' requires "
                f"jax_batchable policy classes, got {bad} (see "
                "repro.tiering.policy capability flags)"
            )

    spec = _experiment_spec(experiment, fm_fracs, policies, db)
    cache_file = None
    if cache_dir is not None:
        if '"<unidentified:' in json.dumps(spec, sort_keys=True):
            # a factory argument with a default (address-bearing) repr has
            # no stable identity: caching would let two different
            # experiments silently share an entry
            raise ValueError(
                "cache_dir requires every factory-bound argument to have "
                "a stable identity; a bound object with a default repr "
                "cannot be keyed (give it a __repr__, or drop cache_dir): "
                + json.dumps(spec["scenarios"])
            )
        cache_file = _cache_path(cache_dir, experiment.name, spec)
        if cache_file.exists():
            try:
                return RunSet.from_json(cache_file.read_text())
            except (ValueError, KeyError, TypeError):
                # truncated/corrupted entry (e.g. an interrupted writer
                # before the atomic-replace era): recompute and overwrite
                pass

    policy_classes = tuple(
        {p.kind: p.policy_cls for p in policies}.values()
    )
    jobs = [
        (sc, fm_fracs, policies, db, experiment.collect_configs,
         policy_classes)
        for sc in scenarios
    ]
    if parallelism is None:
        parallelism = 1 if len(jobs) < 12 else (os.cpu_count() or 1)
    # engine="jax" scenarios need the accelerator, which belongs to this
    # process: they always run here, whatever the parallelism; only the
    # other scenarios fan out
    fan = [
        i for i, sc in enumerate(scenarios)
        if getattr(sc, "engine", "auto") != "jax"
    ]
    parallelism = max(1, min(int(parallelism), len(fan)))
    outs: list = [None] * len(jobs)
    if parallelism > 1:
        _validate_picklable([scenarios[i] for i in fan], policies)
        start_method = _resolve_start_method(
            mp_start_method, mp.get_all_start_methods(), "jax" in sys.modules
        )
        trapped = _fanout(
            [jobs[i] for i in fan], parallelism, scenario_timeout, start_method
        )
        for i, (tag, val) in zip(fan, trapped or ()):
            if tag == "err":
                name, echo, e = val
                raise ScenarioExecutionError(
                    f"scenario {name!r} failed in a fan-out worker: "
                    f"{type(e).__name__}: {e}\n  scenario spec: {echo}"
                ) from e
            outs[i] = val
    for i, job in enumerate(jobs):
        if outs[i] is None:
            outs[i] = _run_scenario_star(job)

    runs, chunked = [], 0
    for records, c in outs:
        runs.extend(records)
        chunked += c
    rs = RunSet(
        name=experiment.name,
        spec=spec,
        runs=runs,
        chunked_step_count=chunked,
        backends=tuple(sorted({r.backend for r in runs})),
    )
    if cache_file is not None:
        cache_file.parent.mkdir(parents=True, exist_ok=True)
        # atomic publish under a per-writer unique temp name: an
        # interrupted run must not leave a truncated document under the
        # final name, and concurrent writers (threads share a pid) must
        # not interleave into each other's temp file — last replace wins,
        # both documents being identical by construction
        tmp = cache_file.with_suffix(f".tmp{uuid.uuid4().hex}")
        tmp.write_text(rs.to_json())
        os.replace(tmp, cache_file)
    return rs
