"""Repo-native static analysis: AST-checked invariant contracts.

Every result this reproduction reports — Tuna's fm-size savings at the
5% loss target, the TPP/Admission/ThrashGuard comparisons — depends on
invariants that used to be enforced only dynamically and only where a
test happened to look. This package turns them into machine-checked
contracts: ``python -m repro.analysis src tests benchmarks`` (or the
``repro-analysis`` console script) parses every file with the stdlib
``ast`` module — the analyzer itself adds no third-party dependency —
and reports violations of the invariant catalog below. The CI ``static-analysis`` job runs it with ``--gate`` and
fails the merge on any un-suppressed, un-baselined finding.

Invariant catalog
-----------------
Each rule is one module in :mod:`repro.analysis.rules`, registered in
:data:`repro.analysis.core.RULES` through the
:func:`~repro.analysis.core.register_rule` decorator — the same
registry pattern as :data:`repro.tiering.policy.POLICIES`. The next
backend/policy PR adds its invariant as one rule file; no core edits.

``TUNA001`` *seeded-rng*
    Unseeded or module-level RNG in ``sim/``, ``tiering/`` or
    ``workloads/``: legacy ``np.random.<fn>`` calls, bare
    ``np.random.default_rng()`` with no seed, and stdlib ``random``
    module functions. Fault schedules and workload traces must be
    reproducible from ``Scenario.seed`` alone.
``TUNA002`` *pool-tier-writes*
    Direct ``<obj>.tier[...]`` subscript *writes* outside the two pool
    classes (``tiering/page_pool.py``, ``tiering/reference_pool.py``).
    The PR-2 ``kv_cache`` bug — occupancy counters silently diverging
    from the tier array — enforced forever: use ``place()`` or the bulk
    scheduling APIs.
``TUNA003`` *frozen-module*
    ``tiering/reference_pool.py`` is the frozen seed golden model; its
    source digest is pinned in the baseline file and any edit is
    flagged. A deliberate re-freeze is ``--update-baseline`` (see
    below) in the same commit as the edit, with review.
``TUNA004`` *jit-purity*
    Inside ``@jax.jit``-reachable functions in ``sim/jax_engine.py``
    and ``kernels/``: fused ``a*b + c`` float expressions (XLA's CPU
    emitter contracts them into an FMA, 1 ULP off numpy's separate
    multiply-then-add — the ``_decay_heat`` lesson) and host side
    effects (``print``, ``time.*`` calls, ``global`` writes) that
    silently freeze into the traced executable. Reachability is the
    module-local call graph from jit roots (decorated functions,
    ``jax.jit(f)`` arguments, ``pl.pallas_call`` kernels).
``TUNA006`` *runset-schema*
    RunSet schema drift in ``sim/api.py``: the set of serialized field
    names in ``RunSet.to_json`` (plus the result/decision encoders) is
    fingerprinted in the baseline. Changing it without bumping
    ``RUNSET_SCHEMA`` is flagged; bumping it without keeping the prior
    version in ``RUNSET_SCHEMA_COMPAT`` (the ``from_json`` compat
    contract) is flagged too. Schema evolution stays additive and
    deliberate.
``TUNA007`` *trace-determinism*
    Wall-clock reads (``time.time``, ``time.perf_counter``,
    ``time.monotonic``, ``datetime.now``, ...) in ``sim/``, ``tiering/``
    or ``checkpoint/``, where results must be a pure function of the
    trace and the seed (benchmarks and ``launch/`` measure real time
    and are exempt by scope).
``TUNA008`` *picklable-specs*
    ``lambda`` passed as a ``Scenario(trace=... / pool_factory=... /
    runner=...)`` factory argument. Lambdas cannot cross the
    :func:`repro.sim.api.run` process fan-out; the runtime complement
    is ``run()``'s upfront ``pickle.dumps`` validation, but the lint
    catches it at review time, including in code paths that only fan
    out under a many-core planner heuristic.
``TUNA009`` *fleet-budget-writes*
    Direct ``.set_size()`` / ``.set_fm_size()`` calls or
    ``.budget_pages`` re-assignments in fleet code (any path containing
    ``fleet``) outside ``fleet/arbiter.py``. Per-tenant fast-memory
    shares have one legal write path —
    :meth:`repro.fleet.arbiter.FleetTunaArbiter.apply` — so grants,
    tuner moves and fault lag share one rate-limited, logged actuator;
    a bypass silently skips floors/ceilings, hysteresis, and the
    allocation event log the benchmarks report.

Suppression policy
------------------
A finding is suppressed in place with a ``# tuna: ignore[RULE]``
comment on the flagged line, or on a comment-only line directly above
it::

    t0 = time.perf_counter()  # tuna: ignore[TUNA007] benchmark timing

    # tuna: ignore[TUNA004] int64 arithmetic; FMA contraction is a
    # float-only hazard
    acc = base * stride + offset

Multiple codes separate with commas (``ignore[TUNA001,TUNA007]``).
Suppressions are for findings that are *correct as written* — the
comment must say why. Findings that are real but not yet fixed belong
in the baseline instead.

Baseline policy
---------------
``analysis-baseline.json`` at the repo root grandfathers known
findings: each entry pins ``(rule, path, fingerprint-of-source-line)``
plus a mandatory human-written ``reason``. Baselined findings do not
fail the gate; a baselined finding that disappears (the code was
fixed, or the line changed) makes its entry *stale*, and ``--gate``
fails on stale entries so the baseline only ever shrinks by deliberate
edits. The file also pins the TUNA003 frozen-module digests and the
TUNA006 schema fingerprint.

``--update-baseline`` rewrites the file from the current tree:
existing reasons are preserved for findings that still match, new
findings get a placeholder reason to be edited before commit, fixed
findings are dropped, and the frozen digests / schema fingerprint are
refreshed. Run it only when the change is deliberate (a reviewed edit
to the frozen reference pool, an intentional additive schema bump) and
commit the result in the same PR.

CLI
---
``python -m repro.analysis [paths ...]`` (default ``src tests
benchmarks``), ``--format text|json``, ``--out report.json`` (written
regardless of format — the CI artifact), ``--gate`` (strict: stale
baseline entries fail too), ``--select TUNA001,TUNA004``,
``--baseline FILE``, ``--root DIR``, ``--list-rules``,
``--update-baseline``. Exit codes: ``0`` clean, ``1`` findings (or,
under ``--gate``, stale baseline entries), ``2`` usage/configuration
error. These codes are a contract (tests pin them); the CI job gates
on them.
"""

from repro.analysis.core import (  # noqa: F401  (public surface)
    Finding,
    RULES,
    Rule,
    register_rule,
    run_analysis,
)
