"""Kernel micro-benchmarks (CPU interpret-mode timings are correctness-
oriented; TPU perf is assessed structurally via the roofline dry-run)."""

from __future__ import annotations

import time


def run(report) -> None:
    from repro.kernels import ops as kops

    for name, fn in kops.BENCH_CASES.items():
        t0 = time.time()
        out = fn()
        dt = (time.time() - t0) * 1e6
        report(f"kernels/{name}", dt, f"ok shape={getattr(out, 'shape', None)}")
