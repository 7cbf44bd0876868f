"""Table 3 + Section 6.3: sensitivity studies on SSSP.

(a) Performance-loss target τ ∈ {5%, 10%, 15%}: paper reports fast-memory
    savings 9% / 18% / 27% with losses 4.6% / 9.6% / 15.1% (the 15% case
    slightly violates because model error grows with shrink).
(b) Tuning frequency {0.5 s, 1 s, 2.5 s, 5 s}: smaller intervals save more
    memory but lose more performance (paper: 0.5 s → up to 25% saving but
    17% loss; 5 s → ~2% saving, ~3% loss).

The whole target/interval matrix — seven tuner configurations plus the
TPP-only baseline — is one declarative experiment over the SSSP trace
(eight policy specs of a single scenario), which the
:func:`repro.sim.api.run` planner executes as **one** batched tuned sweep
instead of fifteen per-configuration per-size runs, each of which would
re-run its own baseline.
"""

from __future__ import annotations

import time

from benchmarks.common import build_bench_db, get_trace
from benchmarks.fig3_7_tuning import TUNE_EVERY, run_tuned_slices, summarize

# (report label, target_loss, tune_every)
SPECS = (
    ("table3/sssp_tau5", 0.05, TUNE_EVERY),
    ("table3/sssp_tau10", 0.10, TUNE_EVERY),
    ("table3/sssp_tau15", 0.15, TUNE_EVERY),
    ("interval/sssp_0.5s", 0.05, 1),
    ("interval/sssp_1s", 0.05, 2),
    ("interval/sssp_2.5s", 0.05, 3),
    ("interval/sssp_5s", 0.05, 6),
)


def run(report) -> None:
    db = build_bench_db()
    tr = get_trace("sssp")
    t0 = time.time()
    base, results = run_tuned_slices(
        tr, db, [(tau, te) for _, tau, te in SPECS]
    )
    # one sweep produced every row: report each row's amortized share so
    # summing the us column still totals one sweep, as it totalled the
    # per-run times before the batching
    per_row_us = (time.time() - t0) * 1e6 / len(SPECS)
    for (label, _, _), res in zip(SPECS, results):
        saving, max_saving, overall_loss = summarize(base, res, tr)
        report(
            label,
            per_row_us,
            f"saving={saving*100:.1f}%;max_saving={max_saving*100:.1f}%"
            f";loss={overall_loss*100:.2f}%",
        )
