"""The control of a cell's comparison: the reference one precision lower.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--units N]

puts the plain reference, computed with its heat and cost arithmetic in
float32 instead of the configuration's float64, where the program's
outputs would be, and runs the cell's own comparison against the float64
reference, at the cell's sizes, for each seed: the sizes (or records)
compared are those a run with that seed compares, over ``--units``
simulated intervals (sweep cells) or records (perf-database cells), as
many as a run's window reaches. Each seed's compared numbers and limits
are printed as one JSON line; a control that the comparison does not
reject is a comparison that cannot tell the lower precision apart.
Runs on the host alone; the benchmark's own runs never call it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def control(cell: dict, cfg: dict, traffic: dict, seed: int, units: int) -> dict:
    """The compared numbers of the float32 control for one seed."""
    import importlib

    from bench import generate

    driver = importlib.import_module(f"bench.drivers.{cfg['driver']}")
    run = driver.Cell(cfg, traffic, seed)
    if cfg["driver"] == "sweep":
        run.trace = driver.trace_for(cfg, traffic, seed)
        idx = run.compared_sizes()
        n = min(units, len(run.trace))
        want = run.reference(idx, n)
        got = run.reference(idx, n, dtype=np.float32)
        compared, _ = driver.compare(got, want, n)
    else:
        vecs = generate.perfdb_vectors(cfg, traffic, seed)
        run.records = [(vecs[i % len(vecs)], None) for i in range(units)]
        idx = run.compared_records()
        cvs = [run.records[i][0] for i in idx]
        compared, _ = driver.compare([run.reference(cv, np.float32) for cv in cvs],
                                     [run.reference(cv) for cv in cvs])
    return {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}


def rejected(compared: dict) -> bool:
    return any(c["value"] > c["limit"] for c in compared.values())


def main(argv=None) -> int:
    from bench import generate

    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--units", type=int, required=True)
    args = p.parse_args(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == args.workload)
    cfg = generate.load("configs", cell["config"])
    traffic = generate.load("traffic", cell["traffic"])
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        compared = control(cell, cfg, traffic, seed, args.units)
        ok &= rejected(compared)
        print(json.dumps({"workload": args.workload, "seed": seed, "units": args.units,
                          "rejected": rejected(compared), "seconds": time.perf_counter() - t0,
                          "compared": compared}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
