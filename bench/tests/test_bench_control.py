"""The control, the reference in float32 put in the program's place, is
rejected by each cell's comparison; the float64 reference against itself
is not."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import control

ROOT = Path(__file__).resolve().parents[2]
CELLS = {w["name"]: w for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]}


@pytest.mark.parametrize("cell,units", [("gups8g.sweep46", 6), ("perfdb.build", 4)])
@pytest.mark.parametrize("seed", [7, 3_000_000_021])
def test_float32_control_is_rejected(small, cell, units, seed):
    w = CELLS[cell]
    cfg, traffic = small.load("configs", w["config"]), small.load("traffic", w["traffic"])
    compared = control.control(w, cfg, traffic, seed, units)
    assert control.rejected(compared), compared
