"""Steering for CPU rehearsals of the harness: small sizes, no chip.

The harness refuses to run without a TPU. These fixtures take the CPU's
devices in the chip's place, shrink each configuration (a 2^23-word
GUPS table of 16,384 pages, 2,000-page micro-benchmarks; every width and fraction as
configured), keep the persistent compile cache off, and hand back the
result line of a run of ``bench/run.py``'s ``main``.
"""

from __future__ import annotations

import json

import pytest

SMALL = {"gups-8g": {"table_words": 2**23}, "perfdb": {"rss_pages": 2_000, "max_rss_pages": 2_000}}


@pytest.fixture
def small(monkeypatch):
    import jax

    from bench import generate, run

    load = generate.load

    def small_load(kind, name):
        d = load(kind, name)
        if kind == "configs":
            d.update(SMALL.get(name, {}))
        return d

    peaks = json.loads((run.BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    monkeypatch.setattr(generate, "load", small_load)
    monkeypatch.setattr(run, "require_device", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "load_peaks", lambda kind: peaks)
    monkeypatch.setattr(run, "enable_cache", lambda: "(off in tests)")
    return generate


@pytest.fixture
def run_cell(small, capsys):
    """``run_cell(cell, trace=0, seed=...)`` -> (exit code, result line or
    None, standard error)."""
    from bench import run

    def go(cell, trace=0, seed=3_000_000_019, seconds=2):
        rc = run.main(["--workload", cell, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
        out, err = capsys.readouterr()
        lines = out.strip().splitlines()
        result = json.loads(lines[-1]) if rc == 0 and lines else None
        return rc, result, err

    return go
