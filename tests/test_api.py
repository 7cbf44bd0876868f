"""The unified experiment API is pinned bit-exact against the engine
functions it dispatches to.

``repro.sim.api.run`` is a planner over the execution backends, so every
cell of a ``RunSet`` must reproduce ``_simulate`` / ``_sweep_fm_fracs`` /
``_sweep_tuned`` called directly: migration counters, interval times,
config vectors, per-interval fm sizes, tuner decision lists, and watermark
event logs. On top of that: backend selection, chunked-loop-free sweep
provenance, process fan-out determinism, and lossless ``RunSet`` JSON
round-trips.
"""

import functools

import numpy as np
import pytest

from repro.core.perfdb import PerfDB, PerfRecord
from repro.core.telemetry import ConfigVector
from repro.core.trace import IntervalAccess, Trace
from repro.core.tuner import TunaTuner, TunerConfig
from repro.core.watermark import WatermarkController
from repro.sim.api import (
    Experiment,
    PolicySpec,
    RunSet,
    Scenario,
    ScenarioExecutionError,
    TunerSpec,
    run,
)
from repro.sim.engine import _simulate
from repro.tiering.page_pool import TieredPagePool
from repro.tiering.policy import (
    POLICIES,
    AdmissionTPPPolicy,
    FirstTouchPolicy,
    ThrashGuardPolicy,
    TPPPolicy,
    register_policy,
)
from repro.tiering.reference_pool import ReferencePagePool


def random_trace(seed, rss=4_000, n_intervals=10):
    rng = np.random.default_rng(seed)
    tr = Trace(name=f"rand{seed}", rss_pages=rss)
    for _ in range(n_intervals):
        k = int(rng.integers(300, 1600))
        pages = rng.choice(rss, size=k, replace=False)
        tr.append(
            IntervalAccess(
                pages=pages, counts=rng.integers(1, 9, size=k), ops=1000.0
            )
        )
    return tr


def _trace_off_jax(seed, parent_pid):
    """Fan-out trace factory that fails unless it runs in a worker
    process that has not loaded JAX."""
    import os
    import sys

    if os.getpid() == parent_pid:
        raise RuntimeError("scenario ran in the parent, not a fan-out worker")
    if "jax" in sys.modules:
        raise RuntimeError("fan-out worker loaded JAX")
    return random_trace(seed, n_intervals=3)


def pressure_trace(seed, rss=3_000, n_intervals=8):
    """Rotating hot window over most of the RSS: the thrash regime."""
    rng = np.random.default_rng(seed)
    tr = Trace(name=f"press{seed}", rss_pages=rss)
    hot_n = int(rss * 0.7)
    for i in range(n_intervals):
        hot = (np.arange(hot_n) + i * (hot_n // 3)) % rss
        pages = np.unique(
            np.concatenate([hot, rng.choice(rss, size=rss // 10, replace=False)])
        )
        tr.append(
            IntervalAccess(
                pages=pages,
                counts=rng.integers(4, 9, size=pages.size),
                ops=1000.0,
            )
        )
    return tr


def synthetic_db(rss=4_000, max_loss=0.4):
    grid = np.round(np.arange(1.0, 0.19, -0.05), 3)
    cv = ConfigVector(
        pacc_f=10_000, pacc_s=500, pm_de=20, pm_pr=20, ai=6.0,
        rss_pages=rss, hot_thr=4, num_threads=1,
    )
    db = PerfDB()
    db.add(
        PerfRecord(
            config=cv, fm_fracs=grid,
            times=1.0 + np.linspace(0.0, max_loss, grid.size),
        )
    )
    db.build()
    return db


TUNER_SPEC = TunerSpec(target_loss=0.05, tune_every=2, max_step_frac=0.08)


def live_tuner(db, spec=TUNER_SPEC) -> TunaTuner:
    """The pre-redesign construction the spec must reproduce."""
    return TunaTuner(
        db,
        WatermarkController(
            max_step_frac=spec.max_step_frac,
            deadband_frac=spec.deadband_frac,
        ),
        TunerConfig(
            target_loss=spec.target_loss,
            k_neighbors=spec.k_neighbors,
            cooldown_windows=spec.cooldown_windows,
        ),
    )


def _const_payload_runner(sc, f, spec, db):
    # module-level (not a lambda) so the scenario stays picklable across
    # the run() process fan-out — TUNA008
    return {"p99": 1.25, "n": 3}


def assert_result_equal(got, want, configs=True, fm_sizes=True):
    assert got.stats == want.stats
    assert np.array_equal(got.interval_times, want.interval_times)
    assert got.total_time == want.total_time
    assert got.costs == want.costs  # IntervalCosts, every backend
    if fm_sizes:
        assert np.array_equal(got.fm_sizes, want.fm_sizes)
    if configs:
        assert got.configs == want.configs


class TestPlannerEquivalence:
    """run() == the pre-redesign per-entry-point paths, bit for bit."""

    @pytest.mark.parametrize("seed", [0, 1])
    def test_untuned_matches_per_size_simulate(self, seed):
        tr = random_trace(seed)
        fracs = (1.0, 0.7, 0.4, 0.15)
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr)],
                fm_fracs=fracs,
                collect_configs=True,
            )
        )
        assert rs.backends == ("sweep",)
        for f in fracs:
            rec = rs.record(fm_frac=f)
            assert rec.backend == "sweep"
            assert_result_equal(rec.result, _simulate(tr, fm_frac=f))

    def test_tuned_matches_pre_sweep_simulate(self):
        tr = random_trace(3, n_intervals=24)
        db = synthetic_db()
        ref_tuner = live_tuner(db)
        want = _simulate(
            tr, fm_frac=1.0, tuner=ref_tuner,
            tune_every=TUNER_SPEC.tune_every,
        )
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr)],
                fm_fracs=(1.0,),
                policies=[
                    PolicySpec(label="base"),
                    PolicySpec(label="tuned", tuner=TUNER_SPEC),
                ],
            ),
            db=db,
        )
        rec = rs.record(policy="tuned")
        assert rec.backend == "tuned_sweep"
        assert_result_equal(rec.result, want)
        # the tuner was constructed *inside* the run; its decision list
        # and watermark event log must replay the pre-bound tuner exactly
        assert [d.__dict__ for d in rec.decisions] == [
            d.__dict__ for d in ref_tuner.decisions
        ]
        assert [e.__dict__ for e in rec.watermark_log] == [
            e.__dict__ for e in ref_tuner.controller.log
        ]
        assert len(rec.watermark_log) > 0  # the scenario must actuate
        # the untuned spec rode the same tuned sweep as a plain slice
        base = rs.record(policy="base")
        assert base.backend == "tuned_sweep"
        assert base.decisions is None
        assert_result_equal(base.result, _simulate(tr, fm_frac=1.0))

    def test_reference_pool_forces_simulate_backend(self):
        tr = random_trace(4)
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr, pool_factory=ReferencePagePool)],
                fm_fracs=(0.6, 0.3),
            )
        )
        for f in (0.6, 0.3):
            rec = rs.record(fm_frac=f)
            assert rec.backend == "simulate"
            assert_result_equal(
                rec.result,
                _simulate(tr, fm_frac=f, pool_factory=ReferencePagePool),
            )

    def test_first_touch_forces_simulate_backend(self):
        tr = random_trace(5)
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr)],
                fm_fracs=(0.5,),
                policies=[
                    PolicySpec(label="tpp"),
                    PolicySpec(kind="first_touch", label="ft"),
                ],
            )
        )
        assert rs.record(policy="tpp").backend == "sweep"
        ft = rs.record(policy="ft")
        assert ft.backend == "simulate"
        assert_result_equal(
            ft.result, _simulate(tr, fm_frac=0.5, policy=FirstTouchPolicy())
        )

    def test_fast_only_at_full(self):
        tr = random_trace(6)
        tr.slow_pages = np.arange(0, tr.rss_pages, 3, dtype=np.int64)
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr, fast_only_at_full=True)],
                fm_fracs=(1.0, 0.5),
            )
        )
        assert_result_equal(
            rs.record(fm_frac=1.0).result,
            _simulate(tr.fast_only(), fm_frac=1.0),
            configs=False,
        )
        assert_result_equal(
            rs.record(fm_frac=0.5).result,
            _simulate(tr, fm_frac=0.5),
            configs=False,
        )

    def test_fast_only_at_full_on_tuned_backend(self):
        # the NP_slow = 0 substitution must hold on the tuned sweep too:
        # full-size slices run trace.fast_only(), others the raw trace
        tr = random_trace(13, n_intervals=16)
        tr.slow_pages = np.arange(0, tr.rss_pages, 4, dtype=np.int64)
        db = synthetic_db()
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr, fast_only_at_full=True)],
                fm_fracs=(1.0, 0.6),
                policies=[
                    PolicySpec(label="base"),
                    PolicySpec(label="tuned", fm_frac=1.0, tuner=TUNER_SPEC),
                ],
            ),
            db=db,
        )
        assert rs.record(policy="tuned").backend == "tuned_sweep"
        ref_tuner = live_tuner(db)
        assert_result_equal(
            rs.record(policy="tuned").result,
            _simulate(
                tr.fast_only(), fm_frac=1.0, tuner=ref_tuner,
                tune_every=TUNER_SPEC.tune_every,
            ),
        )
        assert_result_equal(
            rs.record(policy="base", fm_frac=1.0).result,
            _simulate(tr.fast_only(), fm_frac=1.0),
        )
        assert_result_equal(
            rs.record(policy="base", fm_frac=0.6).result,
            _simulate(tr, fm_frac=0.6),
        )

    def test_policy_fm_frac_override(self):
        tr = random_trace(7)
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr)],
                fm_fracs=(1.0, 0.5),
                policies=[
                    PolicySpec(label="curve"),
                    PolicySpec(label="pinned", fm_frac=0.3),
                ],
                collect_configs=True,
            )
        )
        assert [r.fm_frac for r in rs.select(policy="curve")] == [1.0, 0.5]
        assert [r.fm_frac for r in rs.select(policy="pinned")] == [0.3]
        assert_result_equal(
            rs.record(policy="pinned").result, _simulate(tr, fm_frac=0.3)
        )

    def test_sweeps_are_chunked_loop_free(self):
        # the thrash regime must stay on the bulk policy step; the RunSet
        # surfaces the count as provenance
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=pressure_trace(0), kswapd_batch=16)],
                fm_fracs=(0.6, 0.3, 0.12),
            )
        )
        assert rs.chunked_step_count == 0
        assert rs.backends == ("sweep",)

    def test_scenario_fanout_matches_serial(self):
        traces = [random_trace(s, n_intervals=6) for s in (8, 9, 10)]
        exp = Experiment(
            scenarios=[Scenario(trace=tr) for tr in traces],
            fm_fracs=(0.8, 0.4),
            collect_configs=True,
        )
        serial = run(exp, parallelism=1)
        fanned = run(exp, parallelism=2)  # falls back serial if sandboxed
        assert [r.scenario for r in serial.runs] == [
            r.scenario for r in fanned.runs
        ]
        for a, b in zip(serial.runs, fanned.runs):
            assert (a.policy, a.fm_frac) == (b.policy, b.fm_frac)
            assert_result_equal(a.result, b.result)

    def test_start_method_resolution(self):
        # numpy fan-outs keep the fork preference; a parent that has loaded
        # JAX spawns instead (forking an XLA-initialized parent is unsafe)
        from repro.sim.api import _resolve_start_method

        avail = ["fork", "spawn", "forkserver"]
        assert _resolve_start_method(None, avail, False) == "fork"
        assert _resolve_start_method(None, avail, True) == "spawn"
        # an explicit request always wins
        assert _resolve_start_method("spawn", avail, False) == "spawn"
        assert _resolve_start_method("fork", avail, True) == "fork"
        # degraded platforms: fall back to the platform default
        assert _resolve_start_method(None, ["spawn"], False) is None
        assert _resolve_start_method(None, ["fork"], True) is None
        with pytest.raises(ValueError, match="not available"):
            _resolve_start_method("forkserver", ["fork", "spawn"], False)

    def test_jax_engine_runs_in_calling_process(self, monkeypatch):
        # the accelerator belongs to one process: engine="jax" scenarios
        # never reach the process fan-out, whatever the parallelism
        import repro.sim.api as api

        def no_fanout(*a, **k):
            raise AssertionError("engine='jax' scenario was fanned out")

        monkeypatch.setattr(api, "_fanout", no_fanout)
        traces = [pressure_trace(s, rss=1_000, n_intervals=3) for s in (1, 2)]

        def exp(engine):
            return Experiment(
                scenarios=[
                    Scenario(trace=tr, name=f"s{i}", engine=engine)
                    for i, tr in enumerate(traces)
                ],
                fm_fracs=(0.5,),
            )

        jx = run(exp("jax"), parallelism=2)
        base = run(exp("numpy"), parallelism=1)
        assert jx.backends == ("jax_sweep",)
        for a, b in zip(jx.runs, base.runs):
            assert_result_equal(a.result, b.result)

    def test_numpy_fanout_after_jax_keeps_workers_off_jax(self):
        # a parent that has run JAX fans numpy scenarios out to workers
        # that never load JAX, and the fan-out finishes (no fork hang)
        import os

        import jax.numpy as jnp

        jnp.zeros(()).block_until_ready()
        exp = Experiment(
            scenarios=[
                Scenario(
                    trace=functools.partial(_trace_off_jax, s, os.getpid()),
                    name=f"w{s}",
                )
                for s in (3, 4)
            ],
            fm_fracs=(0.5,),
        )
        rs = run(exp, parallelism=2, scenario_timeout=300)
        assert len(rs.runs) == 2

    def test_fanout_spawn_matches_serial(self):
        # the spawn context re-imports repro in each worker; results must
        # be bit-identical to serial (and to the default fork fan-out)
        traces = [random_trace(s, n_intervals=4) for s in (8, 9)]
        exp = Experiment(
            scenarios=[Scenario(trace=tr) for tr in traces],
            fm_fracs=(0.6,),
        )
        serial = run(exp, parallelism=1)
        spawned = run(exp, parallelism=2, mp_start_method="spawn")
        for a, b in zip(serial.runs, spawned.runs):
            assert (a.scenario, a.policy, a.fm_frac) == (
                b.scenario, b.policy, b.fm_frac
            )
            assert_result_equal(a.result, b.result)

    def test_fanout_rejects_unpicklable_spec_upfront(self):
        # a lambda trace dies inside the worker pool with an opaque
        # PicklingError; run() must fail fast and name the field instead
        exp = Experiment(
            scenarios=[
                # tuna: ignore[TUNA008] the lint's target, used here to
                # prove the runtime guard catches what slips past it
                Scenario(name="s0", trace=lambda: random_trace(1)),
                Scenario(trace=random_trace(2, n_intervals=3)),
            ],
            fm_fracs=(0.5,),
        )
        with pytest.raises(ScenarioExecutionError, match=r"'s0'.*trace"):
            run(exp, parallelism=2)
        # serial execution never pickles, so the same spec is allowed
        rs = run(exp, parallelism=1)
        assert len(rs.runs) == 2

    def test_workload_name_and_callable_scenarios(self):
        tr = random_trace(11, n_intervals=4)

        def factory():
            return random_trace(11, n_intervals=4)

        rs_obj = run(
            Experiment(scenarios=[Scenario(trace=tr)], fm_fracs=(0.5,))
        )
        rs_fn = run(
            Experiment(
                scenarios=[Scenario(trace=factory, name="rand11")],
                fm_fracs=(0.5,),
            )
        )
        assert_result_equal(
            rs_fn.record().result, rs_obj.record().result, configs=False
        )

    def test_validation_errors(self):
        tr = random_trace(12, n_intervals=3)
        with pytest.raises(ValueError, match="at least one scenario"):
            run(Experiment(scenarios=[]))
        with pytest.raises(ValueError, match="duplicate policy labels"):
            run(
                Experiment(
                    scenarios=[Scenario(trace=tr)],
                    policies=[PolicySpec(label="x"), PolicySpec(label="x")],
                )
            )
        with pytest.raises(ValueError, match="no performance database"):
            run(
                Experiment(
                    scenarios=[Scenario(trace=tr)],
                    policies=[PolicySpec(tuner=TunerSpec())],
                )
            )
        with pytest.raises(ValueError, match="neither trace nor runner"):
            run(Experiment(scenarios=[Scenario()]))
        # unknown kinds must list every registered alternative
        with pytest.raises(
            ValueError, match="registered kinds:.*admission.*tpp"
        ):
            PolicySpec(kind="numa")
        # tuner rejection is keyed on the registry's tunable flag
        with pytest.raises(ValueError, match="tunable=False"):
            PolicySpec(kind="first_touch", tuner=TunerSpec())
        # hot_thr must go through the dedicated field (it keys the
        # planner's sweep grouping), never through params
        with pytest.raises(ValueError, match="hot_thr"):
            PolicySpec(kind="admission", params={"hot_thr": 8})
        # typo'd params fail at spec construction with the accepted set,
        # not as a bare TypeError deep inside a fan-out worker
        with pytest.raises(
            ValueError, match="admit_margn.*accepts.*admit_margin"
        ):
            PolicySpec(kind="admission", params={"admit_margn": 2.0})
        with pytest.raises(ValueError, match="non-JSON-serializable params"):
            run(
                Experiment(
                    scenarios=[Scenario(trace=tr)],
                    # accepted param name, unserializable value: passes
                    # the signature check, must die in run()'s JSON check
                    policies=[PolicySpec(params={"promote_batch": object()})],
                )
            )
        with pytest.raises(
            ValueError, match="non-JSON-serializable params"
        ):
            run(
                Experiment(
                    scenarios=[Scenario(trace=tr, params={"n": object()})],
                )
            )

    def test_custom_runner_backend(self):
        def runner(scenario, fm_frac, spec, db):
            return {
                "fm_frac": fm_frac,
                "knob": scenario.params["knob"],
                "policy": spec.name,
            }

        rs = run(
            Experiment(
                scenarios=[
                    Scenario(name="svc", runner=runner, params={"knob": 7})
                ],
                fm_fracs=(1.0, 0.5),
            )
        )
        assert rs.backends == ("custom",)
        assert rs.result(fm_frac=0.5) == {
            "fm_frac": 0.5, "knob": 7, "policy": "tpp",
        }
        # total_times is a simulator-result helper; custom payloads have
        # no total_time and must be rejected explicitly
        with pytest.raises(TypeError, match="backend='custom'"):
            rs.total_times()


class TestRunSetSerialization:
    """to_json/from_json is lossless, including ConfigVectors, stats
    snapshots, costs, tuner decisions, and watermark logs."""

    def _tuned_runset(self):
        tr = random_trace(20, n_intervals=18)
        db = synthetic_db()
        return run(
            Experiment(
                name="roundtrip",
                scenarios=[Scenario(trace=tr)],
                fm_fracs=(1.0,),
                policies=[
                    PolicySpec(label="base"),
                    PolicySpec(label="tuned", tuner=TUNER_SPEC),
                ],
            ),
            db=db,
        )

    def test_round_trip(self):
        rs = self._tuned_runset()
        text = rs.to_json()
        back = RunSet.from_json(text)
        assert back.name == rs.name
        assert back.spec == rs.spec
        assert back.chunked_step_count == rs.chunked_step_count
        assert back.backends == rs.backends
        assert len(back.runs) == len(rs.runs)
        for a, b in zip(rs.runs, back.runs):
            assert (a.scenario, a.policy, a.fm_frac, a.backend) == (
                b.scenario, b.policy, b.fm_frac, b.backend
            )
            # bit-exact: counters, times, fm trajectories, config vectors
            assert b.result.stats == a.result.stats
            assert np.array_equal(b.result.interval_times, a.result.interval_times)
            assert b.result.interval_times.dtype == a.result.interval_times.dtype
            assert np.array_equal(b.result.fm_sizes, a.result.fm_sizes)
            assert b.result.configs == a.result.configs
            assert b.result.costs == a.result.costs
            if a.decisions is None:
                assert b.decisions is None
            else:
                assert [d.__dict__ for d in b.decisions] == [
                    d.__dict__ for d in a.decisions
                ]
                assert [e.__dict__ for e in b.watermark_log] == [
                    e.__dict__ for e in a.watermark_log
                ]
        # a second round trip is byte-identical (fixed point)
        assert RunSet.from_json(back.to_json()).to_json() == text

    def test_provenance_fields(self):
        rs = self._tuned_runset()
        assert rs.spec["name"] == "roundtrip"
        assert rs.spec["fm_fracs"] == [1.0]
        assert rs.spec["scenarios"][0]["seed"] == 0
        assert rs.spec["policies"][1]["tuner"]["target_loss"] == 0.05
        assert rs.spec["db_records"] == 1
        assert rs.chunked_step_count == 0
        assert "tuned_sweep" in rs.backends

    def test_schema_version_checked(self):
        rs = self._tuned_runset()
        import json

        d = json.loads(rs.to_json())
        d["schema"] = "bogus"
        with pytest.raises(ValueError, match="schema"):
            RunSet.from_json(json.dumps(d))

    def test_custom_payload_round_trip(self):
        rs = run(
            Experiment(
                scenarios=[Scenario(name="svc", runner=_const_payload_runner)],
            )
        )
        back = RunSet.from_json(rs.to_json())
        assert back.result(scenario="svc") == {"p99": 1.25, "n": 3}


class TestPolicyRegistry:
    """The registry is the only policy-routing surface: new kinds ride the
    planner via their capability flags, params round-trip losslessly, and
    third-party registrations need zero api.py edits."""

    @pytest.mark.parametrize(
        "kind,cls,params",
        [
            ("admission", AdmissionTPPPolicy, {"admit_margin": 1.5}),
            ("thrash_guard", ThrashGuardPolicy, {"reuse_window": 3}),
        ],
    )
    def test_new_kinds_ride_the_sweep(self, kind, cls, params):
        tr = pressure_trace(1)
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr, kswapd_batch=16)],
                fm_fracs=(0.6, 0.25),
                policies=[PolicySpec(kind=kind, params=params)],
                collect_configs=True,
            )
        )
        assert rs.backends == ("sweep",)
        assert rs.chunked_step_count == 0
        for f in (0.6, 0.25):
            rec = rs.record(fm_frac=f)
            want = _simulate(
                tr,
                fm_frac=f,
                policy=cls(**params),
                pool_factory=functools.partial(
                    TieredPagePool, kswapd_batch=16
                ),
            )
            assert_result_equal(rec.result, want)

    def test_params_reach_the_constructor(self):
        spec = PolicySpec(kind="admission", params={"admit_margin": 3.5})
        pol = spec.build_policy()
        assert isinstance(pol, AdmissionTPPPolicy)
        assert pol.admit_margin == 3.5
        assert PolicySpec(kind="tpp").build_policy().hot_thr == 4

    def test_params_sweep_gets_distinct_default_labels(self):
        a = PolicySpec(kind="admission", params={"admit_margin": 1.5})
        b = PolicySpec(kind="admission", params={"admit_margin": 3.0})
        assert a.name != b.name
        tr = random_trace(42, n_intervals=4)
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr)],
                fm_fracs=(0.4,),
                policies=[a, b],
            )
        )
        assert [r.policy for r in rs.runs] == [a.name, b.name]

    def test_admit_fail_flows_into_config_vectors(self):
        tr = pressure_trace(2)
        rs = run(
            Experiment(
                scenarios=[Scenario(trace=tr, kswapd_batch=16)],
                fm_fracs=(0.3,),
                policies=[
                    PolicySpec(label="tpp"),
                    PolicySpec(kind="admission", label="admission"),
                ],
                collect_configs=True,
            )
        )
        adm = sum(
            c.pm_admit_fail
            for c in rs.result(policy="admission").configs
        )
        assert adm > 0
        assert all(
            c.pm_admit_fail == 0.0 for c in rs.result(policy="tpp").configs
        )

    def test_third_party_registration_round_trips(self):
        @register_policy
        class LukewarmPolicy(TPPPolicy):
            """Promotes only every other interval (silly but stateless)."""

            kind = "test_lukewarm"

            def __init__(self, hot_thr=4, skip_odd=True):
                super().__init__(hot_thr=hot_thr)
                self.skip_odd = bool(skip_odd)
                self._i = {}

            def _admit(self, pool, cand):
                i = self._i.get(id(pool), 0)
                self._i[id(pool)] = i + 1
                if self.skip_odd and i % 2 == 1:
                    return cand[:0], int(cand.size)
                return cand, 0

        try:
            tr = random_trace(40, n_intervals=6)
            rs = run(
                Experiment(
                    name="third_party",
                    scenarios=[Scenario(trace=tr)],
                    fm_fracs=(0.5,),
                    policies=[
                        PolicySpec(
                            kind="test_lukewarm",
                            params={"skip_odd": True},
                        )
                    ],
                )
            )
            assert rs.backends == ("sweep",)
            # params echoed losslessly through the provenance + JSON
            assert rs.spec["policies"][0]["params"] == {"skip_odd": True}
            back = RunSet.from_json(rs.to_json())
            assert back.spec == rs.spec
            assert back.result().stats == rs.result().stats

            # spawn-start fan-out: a worker process re-imports repro but
            # not the registering module; _run_scenario must re-register
            # the classes shipped in the job payload before resolving
            from repro.sim.api import _run_scenario

            spec = PolicySpec(kind="test_lukewarm")
            POLICIES.pop("test_lukewarm")  # simulate a fresh worker
            records, chunked = _run_scenario(
                Scenario(trace=tr), (0.5,), (spec,), None, False,
                policy_classes=(LukewarmPolicy,),
            )
            assert len(records) == 1
            assert records[0].result.stats == rs.result().stats
        finally:
            POLICIES.pop("test_lukewarm", None)

    def test_registry_rejects_duplicates_and_anonymous(self):
        with pytest.raises(ValueError, match="already registered"):

            @register_policy
            class Impostor(TPPPolicy):
                kind = "tpp"

        with pytest.raises(ValueError, match="kind"):

            @register_policy
            class Nameless(TPPPolicy):
                kind = ""

    def test_schema_v4_with_v1_v2_v3_compat(self):
        import json as json_mod

        from repro.sim.api import RUNSET_SCHEMA

        assert RUNSET_SCHEMA == "tuna-runset-v4"
        tr = random_trace(41, n_intervals=4)
        rs = run(
            Experiment(scenarios=[Scenario(trace=tr)], fm_fracs=(0.5,))
        )
        d = json_mod.loads(rs.to_json())
        assert d["schema"] == "tuna-runset-v4"
        # a v3 document (no arbiter_log) still loads: missing keys default
        for r in d["runs"]:
            r.pop("arbiter_log")
        d["schema"] = "tuna-runset-v3"
        back3 = RunSet.from_json(json_mod.dumps(d))
        assert back3.result().stats == rs.result().stats
        assert back3.runs[0].arbiter_log is None
        # a v2 document (no fault_events / faults echo either) still loads
        for r in d["runs"]:
            r.pop("fault_events")
        for sc in d["spec"]["scenarios"]:
            sc.pop("faults")
        d["schema"] = "tuna-runset-v2"
        back2 = RunSet.from_json(json_mod.dumps(d))
        assert back2.result().stats == rs.result().stats
        # a v1 document (no params echo either) still loads
        for p in d["spec"]["policies"]:
            p.pop("params")
        d["schema"] = "tuna-runset-v1"
        back = RunSet.from_json(json_mod.dumps(d))
        assert back.result().stats == rs.result().stats


class TestChunkedStepScoping:
    """chunked-loop provenance is scoped per policy instance, so
    concurrent runs cannot cross-pollute each other's counts."""

    def test_per_instance_isolation(self):
        tr = random_trace(50, n_intervals=5)
        chunked_pol = TPPPolicy()  # reference pool has no bulk path
        _simulate(
            tr, fm_frac=0.4, policy=chunked_pol,
            pool_factory=ReferencePagePool,
        )
        bulk_pol = TPPPolicy()
        _simulate(tr, fm_frac=0.4, policy=bulk_pol)
        assert chunked_pol.chunked_steps > 0
        assert bulk_pol.chunked_steps == 0

    def test_runset_provenance_untouched_by_other_instances(self):
        tr = random_trace(51, n_intervals=5)
        # a chunked-looping run in flight must not leak into the RunSet
        # provenance of an unrelated sweep (the old process-wide global
        # did exactly that across fan-out workers)
        noisy = TPPPolicy()
        _simulate(
            tr, fm_frac=0.4, policy=noisy, pool_factory=ReferencePagePool
        )
        assert noisy.chunked_steps > 0
        rs = run(
            Experiment(scenarios=[Scenario(trace=tr)], fm_fracs=(0.5, 0.3))
        )
        assert rs.chunked_step_count == 0

    def test_thread_local_aggregate_isolation(self):
        """A reference-pool run that loops chunked on another thread
        leaves a concurrent sweep's provenance at 0, and each thread's
        policy instance counts only its own loops."""
        import threading

        tr = random_trace(52, n_intervals=4)
        solo = TPPPolicy()
        _simulate(
            tr, fm_frac=0.4, policy=solo, pool_factory=ReferencePagePool
        )
        assert solo.chunked_steps > 0
        start = threading.Barrier(2, timeout=60)
        counts = {}

        def worker():
            pol = TPPPolicy()
            start.wait()
            _simulate(
                tr, fm_frac=0.4, policy=pol, pool_factory=ReferencePagePool
            )
            counts["worker"] = pol.chunked_steps

        t = threading.Thread(target=worker)
        t.start()
        start.wait()
        mine = TPPPolicy()
        _simulate(
            tr, fm_frac=0.4, policy=mine, pool_factory=ReferencePagePool
        )
        rs = run(
            Experiment(scenarios=[Scenario(trace=tr)], fm_fracs=(0.5, 0.3))
        )
        t.join()
        assert counts["worker"] == solo.chunked_steps
        assert mine.chunked_steps == solo.chunked_steps
        assert rs.chunked_step_count == 0


class TestResultCache:
    """run(cache_dir=...) memoizes the whole RunSet keyed on the spec
    echo + schema version."""

    def _exp(self, fracs=(0.6, 0.3)):
        return Experiment(
            name="cached",
            scenarios=[Scenario(trace=random_trace(60, n_intervals=5))],
            fm_fracs=fracs,
            collect_configs=True,
        )

    def test_second_run_is_served_from_cache(self, tmp_path):
        rs1 = run(self._exp(), cache_dir=tmp_path)
        files = sorted(tmp_path.glob("runset_*.json"))
        assert len(files) == 1
        # prove the second call reads the file, not the engine: mutate it
        doc = files[0].read_text().replace('"cached"', '"tampered"', 1)
        files[0].write_text(doc)
        rs2 = run(self._exp(), cache_dir=tmp_path)
        assert rs2.name == "tampered"
        for a, b in zip(rs1.runs, rs2.runs):
            assert a.result.stats == b.result.stats
            assert np.array_equal(
                a.result.interval_times, b.result.interval_times
            )
            assert a.result.configs == b.result.configs

    def test_spec_change_misses(self, tmp_path):
        run(self._exp(), cache_dir=tmp_path)
        run(self._exp(fracs=(0.5,)), cache_dir=tmp_path)
        assert len(list(tmp_path.glob("runset_*.json"))) == 2

    def test_partial_factory_bound_args_are_cache_identity(self, tmp_path):
        # the blessed lazy-trace pattern (build_database): two partials
        # over the same factory with different bound args must not share
        # a cache entry
        def exp(n):
            return Experiment(
                name="partial",
                scenarios=[
                    Scenario(
                        trace=functools.partial(
                            random_trace, 61, n_intervals=n
                        ),
                        name="p",
                    )
                ],
                fm_fracs=(0.5,),
            )

        rs4 = run(exp(4), cache_dir=tmp_path)
        rs6 = run(exp(6), cache_dir=tmp_path)
        assert len(list(tmp_path.glob("runset_*.json"))) == 2
        assert len(rs4.result().interval_times) == 4
        assert len(rs6.result().interval_times) == 6

    def test_pool_factory_bound_args_are_cache_identity(self, tmp_path):
        tr = random_trace(62, n_intervals=4)

        def exp(halflife):
            return Experiment(
                name="pf",
                scenarios=[
                    Scenario(
                        trace=tr,
                        pool_factory=functools.partial(
                            TieredPagePool, hotness_halflife=halflife
                        ),
                    )
                ],
                fm_fracs=(0.4,),
            )

        a = run(exp(2.0), cache_dir=tmp_path)
        b = run(exp(8.0), cache_dir=tmp_path)
        # the bound halflife is identity: two entries, no collision
        assert len(list(tmp_path.glob("runset_*.json"))) == 2
        assert a.spec != b.spec

    def test_ndarray_bound_args_hash_full_contents(self):
        # repr() truncates large arrays; the spec echo must not
        from repro.sim.api import _arg_ref

        x = np.arange(5000)
        y = x.copy()
        y[2500] += 1  # interior element repr() would elide
        assert _arg_ref(x) != _arg_ref(y)
        assert _arg_ref(x) == _arg_ref(x.copy())
        # default-repr objects must not leak memory addresses
        class Blob:
            pass

        ref = _arg_ref(Blob())
        assert "0x" not in str(ref)
        assert ref == _arg_ref(Blob())

    def test_refuses_to_cache_unidentifiable_factory_args(self, tmp_path):
        # a bound object with a default (address-bearing) repr has no
        # stable identity: caching it could silently serve another
        # experiment's results, so run() must refuse loudly
        class Cfg:
            pass

        exp = Experiment(
            name="unid",
            scenarios=[
                Scenario(
                    trace=functools.partial(random_trace, 63, rss=Cfg())
                )
            ],
            fm_fracs=(0.5,),
        )
        with pytest.raises(ValueError, match="stable identity"):
            run(exp, cache_dir=tmp_path)

    def test_cache_round_trip_is_lossless(self, tmp_path):
        rs1 = run(self._exp(), cache_dir=tmp_path)
        rs2 = run(self._exp(), cache_dir=tmp_path)
        assert rs2.to_json() == rs1.to_json()

    def test_corrupted_entry_recomputes_and_heals(self, tmp_path):
        rs1 = run(self._exp(), cache_dir=tmp_path)
        (f,) = tmp_path.glob("runset_*.json")
        f.write_text(rs1.to_json()[: len(rs1.to_json()) // 2])  # truncated
        rs2 = run(self._exp(), cache_dir=tmp_path)
        assert rs2.to_json() == rs1.to_json()
        # the entry was rewritten, so the next call is a clean hit again
        assert RunSet.from_json(f.read_text()).to_json() == rs1.to_json()


class TestBuildDatabaseOnPlanner:
    """build_database constructs its runs exclusively through run()."""

    def test_fanout_workers_match_serial(self):
        from repro.core.tuner import build_database

        cvs = [
            ConfigVector(
                pacc_f=20_000 + 1_000 * i, pacc_s=1_000, pm_de=30, pm_pr=30,
                ai=8.0, rss_pages=6_000, hot_thr=4, num_threads=1,
            )
            for i in range(3)
        ]
        fracs = np.array([1.0, 0.6, 0.3])
        db1 = build_database(cvs, fm_fracs=fracs, n_intervals=5,
                             max_rss_pages=6_000, workers=1)
        db2 = build_database(cvs, fm_fracs=fracs, n_intervals=5,
                             max_rss_pages=6_000, workers=2)
        for r1, r2 in zip(db1.records, db2.records):
            assert np.array_equal(r1.times, r2.times)
            assert r1.config == r2.config
