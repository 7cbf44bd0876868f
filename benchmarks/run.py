"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (CPU host times; speed on
the chip is measured by the ``bench/`` cells). A failed suite is reported
and the rest still run; the exit code is then non-zero (``--strict``
stops at the first failure instead).

Usage:  PYTHONPATH=src python -m benchmarks.run [filter ...]
"""

from __future__ import annotations

import sys
import time


def _report(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def main() -> int:
    filters = [a for a in sys.argv[1:] if not a.startswith("-")]
    from benchmarks import (
        fig1_motivation,
        table2_accuracy,
        fig3_7_tuning,
        fig8_migrations,
        table3_target_sensitivity,
        fig_fault_resilience,
        fig_fleet,
        fig_model_fidelity,
        serving_tiered,
    )

    suites = [
        ("fig1", fig1_motivation),
        ("table2", table2_accuracy),
        ("fig3_7", fig3_7_tuning),
        ("fig8", fig8_migrations),
        ("table3", table3_target_sensitivity),
        ("fault", fig_fault_resilience),
        ("fleet", fig_fleet),
        ("fidelity", fig_model_fidelity),
        ("serving", serving_tiered),
    ]
    print("name,us_per_call,derived")
    failed = []
    for key, mod in suites:
        if filters and not any(f in key for f in filters):
            continue
        t0 = time.time()
        try:
            mod.run(_report)
            _report(f"{key}/__suite__", (time.time() - t0) * 1e6, "ok")
        except Exception as e:  # keep the harness going; report the failure
            _report(f"{key}/__suite__", (time.time() - t0) * 1e6, f"FAIL:{e!r}")
            failed.append(key)
            if "--strict" in sys.argv:
                raise
    if failed:
        print(f"failed suites: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
