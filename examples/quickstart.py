"""Quickstart: the paper's pipeline end to end on one workload.

1. Generate a real XSBench page-access trace.
2. Profile it, build a (small) Tuna performance database offline.
3. Run XSBench with TPP alone vs TPP+Tuna — one declarative
   `Experiment`, executed as a single batched tuned sweep — and compare
   fast-memory saving and performance loss against the 5% target.

Everything goes through the unified experiment API
(`repro.sim.api.Scenario` / `Experiment` / `run`): runs are described as
data, tuners are constructed inside the run from their `TunerSpec`, and
results come back as a serializable `RunSet` (try `rs.to_json()`).

Run:  PYTHONPATH=src python examples/quickstart.py

To check an engine change against the frozen seed pool, run the same
scenario as `Scenario(trace=..., pool_factory=ReferencePagePool)`
(`repro.tiering.reference_pool`) and compare `stats` and `interval_times`
for exact equality. Speed is measured on the chip by the `bench/` cells.
"""

import functools
from pathlib import Path

import numpy as np

from repro.core.tuner import build_database
from repro.fleet import ArbiterSpec, FleetScenario, TenantSpec
from repro.sim.api import (
    Experiment,
    FaultSpec,
    PolicySpec,
    Scenario,
    TunerSpec,
    run,
)
from repro.sim.costmodel import OPTANE_LIKE
from repro.sim.workloads import arrivals_trace, xsbench_trace
from repro.timing import calibrate, timing_runner
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache(Path(__file__).resolve().parents[1])

print("== generating XSBench trace (real MC lookup kernel, page-instrumented)")
trace = xsbench_trace(n_intervals=36, lookups=80_000)
print(f"   rss={trace.rss_pages} pages, {len(trace)} profiling intervals")

print("== profiling + building the performance database (offline)")
probe = run(
    Experiment(
        name="profile",
        scenarios=[Scenario(trace=trace)],
        fm_fracs=(0.9,),
        collect_configs=True,
    )
)
cvs = probe.record().result.configs
configs = [c for c in cvs[3:] if c.pacc_f + c.pacc_s >= 500][::3][:10]
db = build_database(configs, fm_fracs=np.arange(1.0, 0.28, -0.06),
                    n_intervals=8)
print(f"   {len(db.records)} execution records")

print("== TPP alone vs TPP + Tuna (5% loss target): one tuned sweep")
rs = run(
    Experiment(
        name="quickstart",
        scenarios=[Scenario(trace=trace)],
        fm_fracs=(1.0,),
        policies=[
            PolicySpec(label="tpp"),
            PolicySpec(label="tpp+tuna",
                       tuner=TunerSpec(target_loss=0.05, tune_every=5,
                                       max_step_frac=0.05)),
        ],
    ),
    db=db,
)
base = rs.result(policy="tpp")
tuned = rs.result(policy="tpp+tuna")
print(f"   TPP alone: runtime {base.total_time*1e3:.1f} ms "
      f"(fast memory = peak RSS)")
saving = 1 - tuned.fm_sizes.mean() / trace.rss_pages
loss = (tuned.total_time - base.total_time) / base.total_time
moves = len(rs.record(policy="tpp+tuna").watermark_log)
print(f"   TPP+Tuna:  runtime {tuned.total_time*1e3:.1f} ms "
      f"(loss {loss*100:.2f}% vs 5% target), "
      f"avg fast-memory saving {saving*100:.1f}%, "
      f"max saving {(1 - tuned.fm_sizes.min()/trace.rss_pages)*100:.1f}%, "
      f"{moves} watermark moves")
print(f"   backends={list(rs.backends)}, "
      f"chunked_step_count={rs.chunked_step_count}, "
      f"runset_json={len(rs.to_json())} bytes")

print("== the same tuned run under injected faults (resilience probe)")
# Scenario(faults=...) turns on the seeded deterministic fault layer:
# transient promotion failures with bounded retry + backoff, telemetry
# dropouts, and PerfDB outages. The tuner degrades gracefully (holds /
# freezes watermarks) instead of crashing; every injected event lands in
# the RunSet provenance.
rs_f = run(
    Experiment(
        name="quickstart_faults",
        scenarios=[
            Scenario(
                trace=trace,
                name=f"{trace.name}@faults",
                faults=FaultSpec(
                    seed=7,
                    promote_fail_rate=0.2,
                    max_retries=2,
                    telemetry_drop_rate=0.15,
                    db_outage_rate=0.2,
                ),
            )
        ],
        fm_fracs=(1.0,),
        policies=[
            PolicySpec(label="tpp+tuna",
                       tuner=TunerSpec(target_loss=0.05, tune_every=5,
                                       max_step_frac=0.05)),
        ],
    ),
    db=db,
)
rec_f = rs_f.record(policy="tpp+tuna")
faulted = rec_f.result
degraded = [d.degraded for d in rec_f.decisions if d.degraded is not None]
floss = (faulted.total_time - base.total_time) / base.total_time
print(f"   under faults: runtime {faulted.total_time*1e3:.1f} ms "
      f"(loss {floss*100:.2f}%), "
      f"pgpromote_fail={faulted.stats['pgpromote_fail']}, "
      f"{len(rec_f.fault_events)} injected events, "
      f"{len(degraded)} degraded tuner decisions {sorted(set(degraded))}")

print("== three tenants sharing one fast-memory budget (fleet arbitration)")
# A FleetScenario maps N tenants onto disjoint page ranges of one batched
# sweep pass; per-tenant Tuna tuners report demand and a fleet arbiter
# water-fills the shared budget between them every `every` intervals, so
# fast memory stranded at an over-provisioned tenant flows to a starved
# one. TenantSpec traces ship as picklable callables (spawn-safe fan-out).
tenants = tuple(
    TenantSpec(
        trace=functools.partial(
            arrivals_trace, n_intervals=18, rss_pages=3_000,
            pages_per_session=300, base_rate=rate, seed=seed,
        ),
        name=name,
    )
    for name, rate, seed in
    (("web", 0.3, 11), ("batch", 0.5, 23), ("cache", 0.7, 37))
)
rs_fleet = run(
    Experiment(
        name="quickstart_fleet",
        scenarios=[
            FleetScenario(tenants=tenants, name="fleet", budget_frac=0.7,
                          arbiter=ArbiterSpec(every=2)),
        ],
        fm_fracs=(1.0,),
        policies=[
            PolicySpec(label="fleet_tuna",
                       tuner=TunerSpec(target_loss=0.2, tune_every=2,
                                       k_neighbors=1, cooldown_windows=3,
                                       max_step_frac=0.08)),
        ],
    ),
    db=db,
)
arb_log = rs_fleet.record(scenario="fleet/web").arbiter_log
modes = sorted({e["mode"] for e in arb_log})
for t in tenants:
    res_t = rs_fleet.result(scenario=f"fleet/{t.name}")
    print(f"   tenant {t.name:>5}: runtime {res_t.total_time*1e3:8.1f} ms, "
          f"fast memory {res_t.fm_sizes.min()}..{res_t.fm_sizes.max()} "
          f"of 3000 pages")
print(f"   {len(arb_log)} arbitration events, modes={modes}, "
      f"backend={rs_fleet.record(scenario='fleet/web').backend}")

print("== second oracle: address-level timing engine vs the interval model")
# Every time above comes from the interval roofline cost model.
# `repro.timing` is an independent second clock: it replays the *same*
# deterministic migration schedule at event level (per-access
# latencies, per-tier bandwidth occupancy, a bounded MLP window) and is
# plugged in purely as a `Scenario.runner` — zero planner changes. Where
# the clocks agree the model is corroborated; where they diverge
# (skewed-participation / migration-heavy intervals) is the paper's own
# stated model limitation, now measurable.
cal = calibrate(OPTANE_LIKE)  # fit the engine to the analytic best case
fracs = (1.0, 0.7, 0.4)
rs_clock = run(
    Experiment(
        name="clock_model",
        scenarios=[Scenario(trace=trace)],
        fm_fracs=fracs,
    )
)
rs_oracle = run(
    Experiment(
        name="clock_timing",
        scenarios=[
            Scenario(
                trace=trace,
                runner=functools.partial(
                    timing_runner, calibration=cal.to_dict()
                ),
            )
        ],
        fm_fracs=fracs,
    )
)
tm = rs_clock.total_times()
tt = rs_oracle.total_times()  # via the interval-times payload protocol
for f, m, t in zip(fracs, tm, tt):
    print(f"   fm={f:.1f}: interval model {m*1e3:7.2f} ms, "
          f"timing oracle {t*1e3:7.2f} ms, "
          f"divergence {(t - m)/m*100:+.1f}%")
print("done.")
