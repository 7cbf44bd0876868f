"""Each cell's harness path end to end on the CPU, at a small size."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(run_cell, cell):
    rc, res, err = run_cell(cell)
    assert rc == 0, err
    assert list(res) == KEYS  # the compared numbers come last
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    for name, c in res["compared"].items():
        assert c["value"] <= c["limit"], name
    tail = err.strip().splitlines()[-len(res["compared"]):]
    assert [line.split()[1] for line in tail] == list(res["compared"])


def test_traced_run_reports_host_spans(run_cell):
    rc, res, err = run_cell("gups8g.sweep46", trace=1)
    assert rc == 0, err
    assert res["correct"] is True
    assert list(res)[-1] == "compared" and "breakdown" in res
    assert res["metrics"]["resolve_ms.sweep"]["value"] > 0
    assert res["device"]["window_s"] > 0


def test_off_tpu_exits_nonzero_without_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gups8g.sweep46",
         "--seed", "2147483711", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "perfdb.build",
         "--seed", "5", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
