"""Program spans and counters (:mod:`repro.runtime.tracing`): nesting and
self time, the no-op when inactive, the layers of the device sweep and the
database build, and the benchmark's readers of them."""

from __future__ import annotations

import importlib.util
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.trace import IntervalAccess, Trace
from repro.runtime import tracing

ROOT = Path(__file__).resolve().parents[1]

SWEEP_SPANS = {
    "experiment", "scenario", "scenario.trace",
    "sweep", "sweep.eligibility", "sweep.setup", "sweep.interval", "sweep.import",
    "interval.prep", "interval.schedule", "interval.pull", "interval.rank",
    "interval.commit", "interval.fixup", "interval.account", "interval.fold",
    "fixup.pull", "fixup.merge", "fixup.patch",
}
COUNTERS = {
    "sweep.intervals", "sweep.interfering_sizes",
    "xfer.h2d_bytes", "xfer.d2h_bytes", "device.dispatches",
}


@pytest.fixture(autouse=True)
def clean_table():
    tracing.reset()
    yield
    tracing.reset()


def pressure_trace(seed=0, rss=1_500, n_intervals=4):
    """A rotating hot window over most of the RSS: every size below the
    hot set's share interferes (the thrash regime)."""
    rng = np.random.default_rng(seed)
    tr = Trace(name=f"press{seed}", rss_pages=rss)
    hot_n = int(rss * 0.7)
    for i in range(n_intervals):
        hot = (np.arange(hot_n) + i * (hot_n // 3)) % rss
        pages = np.unique(np.concatenate([hot, rng.choice(rss, size=rss // 10, replace=False)]))
        tr.append(IntervalAccess(pages=pages, counts=rng.integers(4, 9, size=pages.size),
                                 ops=1000.0))
    return tr


def jax_sweep(tr, fracs=(0.9, 0.6, 0.4, 0.2)):
    from repro.sim.api import Experiment, Scenario, run

    return run(Experiment(name="traced", scenarios=[Scenario(trace=tr, engine="jax")],
                          fm_fracs=fracs, collect_configs=True))


# ------------------------------------------------------------------ module


def test_nesting_self_time_and_calls():
    with tracing.recording():
        with tracing.span("outer"):
            time.sleep(0.002)
            for k in range(3):
                with tracing.span("inner", k=k):
                    time.sleep(0.001)
    spans = tracing.snapshot()["spans"]
    outer, inner = spans["outer"], spans["inner"]
    assert (outer["calls"], inner["calls"]) == (1, 3)
    assert inner["seconds"] >= 0.003 and inner["self_seconds"] == pytest.approx(inner["seconds"])
    assert outer["seconds"] >= inner["seconds"] + 0.002
    assert outer["self_seconds"] == pytest.approx(outer["seconds"] - inner["seconds"])


def test_counters_add_while_active():
    with tracing.recording():
        tracing.count("a")
        tracing.count("a", 4)
        tracing.count("b", np.int64(7))
    assert tracing.snapshot()["counters"] == {"a": 5, "b": 7}
    assert all(type(v) is int for v in tracing.snapshot()["counters"].values())


def test_inactive_is_a_shared_noop():
    assert not tracing.active()
    first, second = tracing.span("a"), tracing.span("b", interval=3)
    assert first is second
    with first:
        tracing.count("c", 3)
    assert tracing.traced("d")(lambda: 1)() == 1
    assert tracing.snapshot() == {"spans": {}, "counters": {}}


def test_traced_decorator_keeps_the_function():
    @tracing.traced("f")
    def f(x, *, y=1):
        """doc"""
        return x + y

    assert f.__name__ == "f" and f.__doc__ == "doc" and f.__wrapped__(1) == 2
    with tracing.recording():
        assert f(2, y=3) == 5
    assert tracing.snapshot()["spans"]["f"]["calls"] == 1


def test_recording_and_reset_leave_nothing_behind():
    with pytest.raises(RuntimeError):
        with tracing.recording():
            with tracing.recording():
                pass
            assert tracing.active()
            with tracing.span("a"):
                with tracing.span("b"):
                    raise RuntimeError("inside two spans")
    assert not tracing.active()
    assert tracing._open == []  # no span left open by the raise
    snap = tracing.snapshot()
    assert snap["spans"]["a"]["calls"] == snap["spans"]["b"]["calls"] == 1
    assert snap["spans"]["a"]["self_seconds"] <= snap["spans"]["a"]["seconds"]
    tracing.reset()
    assert tracing.snapshot() == {"spans": {}, "counters": {}}
    tracing.count("x")
    assert tracing.snapshot() == {"spans": {}, "counters": {}}


def test_import_does_not_load_jax():
    import subprocess

    code = ("import sys; import repro.runtime.tracing, repro.sim.api, repro.core.tuner; "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         cwd=ROOT, env={"PYTHONPATH": str(ROOT / "src")}, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"


def test_profiler_session_activates_spans(tmp_path):
    jax = pytest.importorskip("jax")
    assert not tracing.profiling()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.active()
        with tracing.span("under.profiler", size=2):
            tracing.count("n")
    finally:
        jax.profiler.stop_trace()
    assert not tracing.active()
    snap = tracing.snapshot()
    assert snap["spans"]["under.profiler"]["calls"] == 1 and snap["counters"] == {"n": 1}


# -------------------------------------------------------------- the sweep


def test_recording_leaves_the_runset_bit_identical():
    pytest.importorskip("jax")
    tr = pressure_trace()
    off = jax_sweep(tr)
    with tracing.recording():
        on = jax_sweep(tr)
    assert tracing.snapshot()["counters"]["sweep.interfering_sizes"] > 0
    assert on.to_json() == off.to_json()


def test_sweep_records_every_layer(monkeypatch):
    pytest.importorskip("jax")
    from repro.sim import jax_engine

    resolver = jax_engine._resolve_step_victims
    calls = []

    def counted(*args):
        calls.append(1)
        return resolver(*args)

    monkeypatch.setattr(jax_engine, "_resolve_step_victims", counted)
    tr = pressure_trace(seed=1)
    with tracing.recording():
        jax_sweep(tr)
    snap = tracing.snapshot()
    assert SWEEP_SPANS <= set(snap["spans"])
    assert COUNTERS <= set(snap["counters"])
    c, s = snap["counters"], snap["spans"]
    assert c["sweep.intervals"] == len(tr) == s["sweep.interval"]["calls"]
    assert c["sweep.interfering_sizes"] == len(calls) > 0
    assert s["fixup.merge"]["calls"] == s["fixup.pull"]["calls"] == len(calls)
    assert s["sweep"]["calls"] == 1 and s["experiment"]["calls"] == 1
    # every interval pulls the counters and the sums, and the interference
    # flags where it commits; two dispatches at least per interval
    assert s["interval.pull"]["calls"] >= 2 * len(tr)
    assert c["device.dispatches"] >= 2 * len(tr) + 3 * len(calls)
    assert c["xfer.d2h_bytes"] > 0 and c["xfer.h2d_bytes"] > 0
    for row in s.values():
        assert 0 <= row["self_seconds"] <= row["seconds"] + 1e-9
    # the self times of everything inside the intervals add up to the
    # intervals' time less their own
    inner = sum(s[k]["self_seconds"] for k in s if k.startswith(("interval.", "fixup.")))
    iv = s["sweep.interval"]
    assert inner == pytest.approx(iv["seconds"] - iv["self_seconds"], rel=1e-9, abs=1e-9)


def test_database_build_and_tuned_sweep_spans():
    pytest.importorskip("jax")
    from repro.core.microbench import generate_microbench
    from repro.core.telemetry import ConfigVector
    from repro.core.tuner import build_database
    from repro.sim.api import Experiment, PolicySpec, Scenario, TunerSpec, run

    cv = ConfigVector(pacc_f=20_000, pacc_s=2_000, pm_de=60, pm_pr=60, ai=6.0,
                      rss_pages=1_200, hot_thr=4, num_threads=1)
    with tracing.recording():
        db = build_database([cv], fm_fracs=(1.0, 0.6, 0.3), n_intervals=4,
                            max_rss_pages=1_200, engine="jax")
    spans = tracing.snapshot()["spans"]
    assert {"perfdb.build", "perfdb.index", "experiment", "scenario", "scenario.trace",
            "sweep"} <= set(spans)
    assert spans["sweep"]["calls"] == 2  # the fast-only full size, then the rest
    assert spans["perfdb.build"]["seconds"] >= spans["sweep"]["seconds"]

    tracing.reset()
    tr = generate_microbench(cv, n_intervals=6)
    with tracing.recording():
        run(Experiment(name="tuned", scenarios=[Scenario(trace=tr, engine="jax")],
                       fm_fracs=(1.0,), collect_configs=True,
                       policies=[PolicySpec(tuner=TunerSpec(tune_every=2))]), db=db)
    assert tracing.snapshot()["spans"]["interval.tune"]["calls"] == len(tr)


# ------------------------------------------------------------- the readers


def reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def row(seconds, self_seconds=None, calls=1):
    own = seconds if self_seconds is None else self_seconds
    return {"seconds": seconds, "self_seconds": own, "calls": calls}


SNAPSHOT = {
    "spans": {
        "perfdb.build": row(10.0, 0.5), "perfdb.index": row(0.25),
        "experiment": row(9.0, 0.25), "scenario": row(8.75, 1.0),
        "scenario.trace": row(1.5), "sweep": row(6.25, 0.05, calls=2),
        "sweep.eligibility": row(0.4, calls=2), "sweep.setup": row(0.6, calls=2),
        "sweep.import": row(1.0, calls=2), "sweep.interval": row(4.0, 0.1, calls=4),
        "interval.prep": row(0.8, calls=4), "interval.pull": row(0.6, calls=12),
        "interval.account": row(1.2, 1.0, calls=4), "interval.fixup": row(2.0, 0.2, calls=4),
        "fixup.pull": row(1.0, calls=180),
    },
    "counters": {"sweep.intervals": 4, "sweep.interfering_sizes": 180,
                 "xfer.h2d_bytes": 3_000_000, "xfer.d2h_bytes": 5_000_000,
                 "device.dispatches": 552},
}
# metric -> (what the snapshot above gives, span or counter it needs)
EXPECTED = {
    "fixup_ms.sweep": (500.0, "interval.fixup"),
    "interfering_sizes.sweep": (45.0, "sweep.interfering_sizes"),
    "device_wait_ms.sweep": (400.0, "interval.pull"),
    "host_prep_ms.sweep": (200.0, "interval.prep"),
    "account_ms.sweep": (250.0, "interval.account"),
    "sweep_edges_ms.sweep": (1000.0, "sweep"),
    "transfer_mb.sweep": (2.0, "sweep.intervals"),
    "db_overhead_ms.perfdb": (1000.0, "perfdb.build"),
    "interval_host_ms.perfdb": (450.0, "interval.account"),
    "device_wait_ms.perfdb": (400.0, "interval.pull"),
    "dispatches.perfdb": (138.0, "device.dispatches"),
}


def without(name):
    snap = {"spans": dict(SNAPSHOT["spans"]), "counters": dict(SNAPSHOT["counters"])}
    snap["spans"].pop(name, None)
    snap["counters"].pop(name, None)
    if name == "interval.pull":  # the pulls are two spans
        snap["spans"].pop("fixup.pull")
    return snap


def ctx():
    return SimpleNamespace(window={"work": {"records": 2, "intervals": 4}}, trace=None)


def test_every_new_metric_has_a_reader_and_an_entry():
    import json

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in EXPECTED:
        assert entries[name]["source"] == "program_span"
        assert len(entries[name]["workloads"]) == 1
        assert (ROOT / "bench" / "metrics" / f"{name}.py").exists()


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reads_the_snapshot(monkeypatch, name):
    monkeypatch.setattr(tracing, "snapshot", lambda: SNAPSHOT)
    assert reader(name).read(ctx()) == pytest.approx(EXPECTED[name][0])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_nothing_where_its_source_is_absent(monkeypatch, name):
    snap = without(EXPECTED[name][1])
    monkeypatch.setattr(tracing, "snapshot", lambda: snap)
    assert reader(name).read(ctx()) is None
    monkeypatch.setattr(tracing, "snapshot", lambda: {"spans": {}, "counters": {}})
    assert reader(name).read(ctx()) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_nothing_without_the_tracing_module(monkeypatch, name):
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)  # import fails
    assert reader(name).read(ctx()) is None
