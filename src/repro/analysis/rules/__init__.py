"""One invariant per module; importing this package registers them all.

Adding a rule is one file here: subclass
:class:`repro.analysis.core.Rule`, give it a unique ``code`` /
``name`` / ``description`` plus ``scope``/``exempt`` path fragments,
decorate with :func:`repro.analysis.core.register_rule`, and import the
module below (keep the list sorted by code). The CLI, gate, baseline,
suppression and fixture meta-test pick it up from the registry — no
other edits anywhere.
"""

from repro.analysis.rules import (  # noqa: F401  (import = register)
    tuna001_seeded_rng,
    tuna002_pool_tier_writes,
    tuna003_frozen_module,
    tuna004_jit_purity,
    tuna006_runset_schema,
    tuna007_trace_determinism,
    tuna008_picklable_specs,
    tuna009_fleet_budget_writes,
    tuna010_timing_independence,
)
