"""The per-layer metrics read from the program's own spans and counters
(``repro.runtime.tracing``), in a traced run of each named cell on the CPU."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def program_span_metrics(cell):
    """Every ``program_span`` per-layer metric that BENCHMARK.json lists for ``cell``."""
    return {m["name"] for m in SPEC["per_layer"]
            if m["source"] == "program_span" and cell in m.get("workloads", ())}


def sweep_checks(got):
    for m in ("fixup_ms.sweep", "interfering_sizes.sweep", "device_wait_ms.sweep",
              "host_prep_ms.sweep", "account_ms.sweep", "sweep_edges_ms.sweep",
              "transfer_mb.sweep", "rank_ms.sweep"):
        assert got[m]["value"] > 0, m
    # GUPS: every page is hot every interval, so every size below 1.0
    # interferes in every interval
    assert 35 <= got["interfering_sizes.sweep"]["value"] <= 45
    assert got["fixup_ms.sweep"]["value"] >= got["resolve_ms.sweep"]["value"]


def perfdb_checks(got):
    for m in ("db_overhead_ms.perfdb", "interval_host_ms.perfdb", "device_wait_ms.perfdb",
              "dispatches.perfdb"):
        assert got[m]["value"] > 0, m


CELLS = {"gups8g.sweep46": sweep_checks, "perfdb.build": perfdb_checks}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_run_reports_the_program_spans(run_cell, cell):
    from repro.runtime import tracing

    tracing.reset()  # the table holds this run's window alone
    rc, res, err = run_cell(cell, trace=1)
    assert rc == 0, err
    assert res["correct"] is True
    got = res["metrics"]
    want = program_span_metrics(cell)
    assert want <= set(got), sorted(want - set(got))
    assert all(got[m]["value"] >= 0 for m in want)
    CELLS[cell](got)
    tracing.reset()
