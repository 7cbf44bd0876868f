"""Workload traces and simulator invariants (property-style)."""

import functools
import pickle

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAS_HYPOTHESIS = True
except ImportError:  # test extra: only the property tests skip without it
    HAS_HYPOTHESIS = False

    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None

    def _decorator_stub(*a, **k):
        return lambda fn: fn

    given = settings = _decorator_stub
    st = _StrategyStub()

needs_hypothesis = pytest.mark.skipif(
    not HAS_HYPOTHESIS, reason="hypothesis not installed (test extra)"
)

from repro.core.microbench import generate_microbench, spec_from_config
from repro.core.telemetry import ConfigVector
from repro.core.trace import load_trace, save_trace
from repro.sim.engine import _simulate, run_trace
from repro.sim.workloads import WORKLOADS, arrivals_trace, bfs_trace
from repro.sim.workloads.arrivals import (
    modulated_rates,
    open_arrivals,
    session_lengths,
)
from repro.tiering.policy import FirstTouchPolicy


@pytest.fixture(scope="module")
def small_traces():
    return {
        "bfs": bfs_trace(n=60_000, n_sources=4),
        "xsbench": WORKLOADS["xsbench"](n_intervals=8, lookups=30_000),
        "btree": WORKLOADS["btree"](n_intervals=8, queries=30_000),
        "thrash": WORKLOADS["thrash"](n_intervals=8, rss_pages=4_000),
    }


class TestTraces:
    def test_all_workloads_produce_valid_traces(self, small_traces):
        for name, tr in small_traces.items():
            assert len(tr) > 2, name
            assert tr.rss_pages > 100, name
            for ia in tr:
                assert ia.pages.size == np.unique(ia.pages).size
                assert (ia.pages >= 0).all() and (ia.pages < tr.rss_pages).all()
                assert (ia.counts >= 1).all()
                assert 0.0 <= ia.rand_frac <= 1.0

    def test_trace_roundtrip(self, small_traces, tmp_path):
        tr = small_traces["bfs"]
        save_trace(tr, tmp_path / "t.npz")
        tr2 = load_trace(tmp_path / "t.npz")
        assert tr2.rss_pages == tr.rss_pages
        assert len(tr2) == len(tr)
        np.testing.assert_array_equal(tr2.intervals[3].pages, tr.intervals[3].pages)
        np.testing.assert_array_equal(tr2.intervals[3].counts, tr.intervals[3].counts)

    def test_loss_monotone_in_shrink(self, small_traces):
        for name, tr in small_traces.items():
            times = [run_trace(tr, f) for f in (1.0, 0.8, 0.5, 0.3)]
            assert times == sorted(times), name

    def test_migration_moves_traffic_off_the_slow_tier(self, small_traces):
        # The mechanism Fig. 1 relies on, scale-independent: with hot pages
        # spilled, TPP's promotions shrink steady-state slow-tier traffic
        # vs first-touch. (Wall-clock ordering needs long runs to amortize
        # the one-time migration cost; benchmarks/fig1 covers it at full
        # scale and run length.)
        tr = small_traces["bfs"]
        tpp = _simulate(tr, fm_frac=0.6)
        ft = _simulate(tr, fm_frac=0.6, policy=FirstTouchPolicy())
        slow_tpp = sum(c.pacc_s for c in tpp.configs[len(tpp.configs) // 2:])
        slow_ft = sum(c.pacc_s for c in ft.configs[len(ft.configs) // 2:])
        assert tpp.migrations > 0
        assert slow_tpp < slow_ft


class TestMicrobenchProperties:
    @needs_hypothesis
    @settings(max_examples=15, deadline=None)
    @given(
        pacc_f=st.integers(5_000, 80_000),
        pacc_s=st.integers(0, 5_000),
        pm=st.integers(0, 200),
        hot_thr=st.sampled_from([2, 4, 8]),
    )
    def test_layout_fits_rss_and_counts(self, pacc_f, pacc_s, pm, hot_thr):
        cv = ConfigVector(
            pacc_f=pacc_f, pacc_s=pacc_s, pm_de=pm, pm_pr=pm, ai=4.0,
            rss_pages=50_000, hot_thr=hot_thr, num_threads=4,
        )
        spec = spec_from_config(cv)
        assert spec.np_fast * hot_thr <= pacc_f + 1
        tr = generate_microbench(cv, n_intervals=5)
        for ia in tr:
            assert (ia.pages < tr.rss_pages).all()
            assert (ia.touches <= max(hot_thr, spec.tail_touches)).all()

    def test_intensity_scales_bytes_not_structure(self):
        base = ConfigVector(pacc_f=20_000, pacc_s=1_000, pm_de=20, pm_pr=20,
                            ai=4.0, rss_pages=20_000, hot_thr=4, num_threads=1)
        import dataclasses

        hi = dataclasses.replace(base, intensity=8.0)
        t1 = generate_microbench(base, n_intervals=4)
        t2 = generate_microbench(hi, n_intervals=4)
        ia1, ia2 = t1.intervals[-1], t2.intervals[-1]
        np.testing.assert_array_equal(ia1.pages, ia2.pages)
        np.testing.assert_array_equal(ia1.touches, ia2.touches)
        assert ia2.counts.sum() > 6 * ia1.counts.sum()


class TestArrivals:
    """Fleet traffic shape: seeded arrival-driven session workload."""

    def test_same_seed_bit_identical(self):
        a = arrivals_trace(n_intervals=10, rss_pages=3_000, seed=5)
        b = arrivals_trace(n_intervals=10, rss_pages=3_000, seed=5)
        assert len(a) == len(b)
        for ia, ib in zip(a, b):
            np.testing.assert_array_equal(ia.pages, ib.pages)
            np.testing.assert_array_equal(ia.counts, ib.counts)
            np.testing.assert_array_equal(ia.touches, ib.touches)
            assert ia.ops == ib.ops and ia.rand_frac == ib.rand_frac

    def test_different_seed_differs(self):
        a = arrivals_trace(n_intervals=10, rss_pages=3_000, seed=5)
        b = arrivals_trace(n_intervals=10, rss_pages=3_000, seed=6)
        assert any(
            ia.pages.size != ib.pages.size
            or not np.array_equal(ia.pages, ib.pages)
            for ia, ib in zip(a, b)
        )

    def test_modulated_rates_shape(self):
        flat = modulated_rates(96, base_rate=2.0, diurnal_amp=0.5,
                               diurnal_period=48, flash_crowds=0)
        # diurnal sinusoid: peak at a quarter period, trough at three
        i = np.arange(96)
        np.testing.assert_allclose(
            flat, 2.0 * (1.0 + 0.5 * np.sin(2 * np.pi * i / 48)),
            rtol=1e-12,
        )
        burst = modulated_rates(96, base_rate=2.0, diurnal_amp=0.5,
                                diurnal_period=48, flash_crowds=2,
                                flash_mult=6.0, flash_len=3, seed=7)
        boosted = burst > flat * 1.5
        assert 3 <= boosted.sum() <= 6  # 2 windows of 3 (may overlap)
        np.testing.assert_array_equal(burst[~boosted], flat[~boosted])
        assert (modulated_rates(96, base_rate=0.01) >= 0.05).all()

    def test_open_arrivals_poisson_mean(self):
        rates = np.full(4_000, 3.0)
        draws = open_arrivals(rates, seed=11)
        # mean of 4000 Poisson(3) draws: sigma = sqrt(3/4000) ~ 0.027
        assert abs(draws.mean() - 3.0) < 0.15
        assert (draws >= 0).all()

    def test_session_lengths_long_tail(self):
        rng = np.random.default_rng(3)
        ln = session_lengths(5_000, session_mean=4.0, session_tail=1.6, rng=rng)
        assert ln.dtype == np.int64 and (ln >= 1).all()
        # Pareto(1.6) long tail: some sessions far beyond the mean scale
        assert ln.max() > 10 * np.median(ln)
        assert session_lengths(0, 4.0, 1.6, rng).size == 0

    @needs_hypothesis
    @settings(max_examples=10, deadline=None)
    @given(
        mode=st.sampled_from(["open", "closed"]),
        n_intervals=st.integers(3, 8),
        seed=st.integers(0, 2**16),
    )
    def test_trace_valid_any_seed(self, mode, n_intervals, seed):
        tr = arrivals_trace(
            n_intervals=n_intervals, rss_pages=1_500, mode=mode,
            pages_per_session=120, seed=seed,
        )
        assert tr.rss_pages == 1_500
        # init interval + one per arrival interval
        assert len(tr) == n_intervals + 1
        for ia in tr:
            assert ia.pages.size == np.unique(ia.pages).size
            assert (ia.pages >= 0).all() and (ia.pages < tr.rss_pages).all()
            assert (ia.counts >= 1).all()
            assert 0.0 <= ia.rand_frac <= 1.0

    def test_bad_mode_rejected(self):
        with pytest.raises(ValueError, match="mode"):
            arrivals_trace(n_intervals=3, rss_pages=1_000, mode="batch")

    def test_registry_round_trip(self):
        assert WORKLOADS["arrivals"] is arrivals_trace

    def test_partial_picklable(self):
        # fleet TenantSpec traces ship as callables to spawn workers
        # (TUNA008): a partial over the module-level generator must
        # round-trip through pickle and regenerate the identical trace
        fn = functools.partial(
            arrivals_trace, n_intervals=6, rss_pages=1_500, seed=9
        )
        fn2 = pickle.loads(pickle.dumps(fn))
        a, b = fn(), fn2()
        assert len(a) == len(b)
        for ia, ib in zip(a, b):
            np.testing.assert_array_equal(ia.pages, ib.pages)
            np.testing.assert_array_equal(ia.counts, ib.counts)


class TestHLOStats:
    def test_collective_parse_with_wrapped_headers(self):
        from repro.roofline.hlo_stats import parse_hlo_collectives

        hlo = """HloModule m

%body.1 (arg: (f32[8]))
  -> (f32[8]) {
  %x = f32[1024,64]{1,0} all-gather(%a), replica_groups={}
  ROOT %t = (f32[8]) tuple(%x)
}

%cond.1 (arg: (f32[8])) -> pred[] {
  ROOT %p = pred[] constant(true)
}

ENTRY %main (p0: f32[8]) -> f32[8] {
  %w = (f32[8]) while((f32[8]) %t0), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"7"}}
  %g = f32[256]{0} all-reduce(%z), replica_groups={}
  ROOT %r = f32[8] get-tuple-element(%w), index=0
}
"""
        out = parse_hlo_collectives(hlo, default_trip=99)
        # body all-gather multiplied by the known trip count (7), not 99
        assert out["all-gather"] == 7 * 1024 * 64 * 4
        assert out["all-reduce"] == 256 * 4

    def test_wire_factors(self):
        from repro.roofline.hlo_stats import wire_factor

        assert wire_factor("all-reduce", 16) == pytest.approx(2 * 15 / 16)
        assert wire_factor("all-gather", 16) == pytest.approx(15 / 16)
        assert wire_factor("collective-permute", 16) == 1.0
        assert wire_factor("all-reduce", 1) == 0.0


class TestWriteChannel:
    """Optional per-access is_write channel (tracegen-style): default
    all-reads is bit-exact with the pre-channel format; write_frac knobs
    produce a conserved store subset that survives the npz round trip."""

    def test_default_traces_have_no_writes(self, small_traces):
        for name, tr in small_traces.items():
            assert all(ia.writes is None for ia in tr), name

    def test_write_frac_zero_is_bit_exact(self):
        base = WORKLOADS["thrash"](n_intervals=6, rss_pages=3_000)
        knob = WORKLOADS["thrash"](n_intervals=6, rss_pages=3_000,
                                   write_frac=0.0)
        assert len(base) == len(knob)
        for a, b in zip(base, knob):
            np.testing.assert_array_equal(a.pages, b.pages)
            np.testing.assert_array_equal(a.counts, b.counts)
            np.testing.assert_array_equal(a.touches, b.touches)
            assert a.writes is None and b.writes is None
            assert a.rand_frac == b.rand_frac and a.ops == b.ops

    def test_write_frac_emits_conserved_stores(self):
        tr = WORKLOADS["thrash"](n_intervals=6, rss_pages=3_000,
                                 write_frac=0.5)
        wrote = 0
        for ia in tr.intervals[1:]:  # skip the allocation interval
            assert ia.writes is not None
            assert (ia.writes >= 0).all()
            assert (ia.writes <= ia.counts).all()
            wrote += int(ia.writes.sum())
        assert wrote > 0
        # identical access structure: only the read/write split changes
        base = WORKLOADS["thrash"](n_intervals=6, rss_pages=3_000)
        for a, b in zip(base, tr):
            np.testing.assert_array_equal(a.pages, b.pages)
            np.testing.assert_array_equal(a.counts, b.counts)

    @pytest.mark.parametrize("name,kwargs", [
        ("thrash", dict(n_intervals=5, rss_pages=2_000, write_frac=0.3)),
        ("bfs", dict(n=40_000, n_sources=2, write_frac=0.4)),
        ("sssp", dict(n=40_000, n_sources=2, write_frac=0.4)),
        ("pagerank", dict(n=40_000, iters=2, write_frac=0.4)),
    ])
    def test_registry_roundtrip_with_writes(self, name, kwargs, tmp_path):
        tr = WORKLOADS[name](**kwargs)
        assert any(ia.writes is not None for ia in tr), name
        save_trace(tr, tmp_path / "t.npz")
        tr2 = load_trace(tmp_path / "t.npz")
        assert len(tr2) == len(tr)
        for a, b in zip(tr, tr2):
            np.testing.assert_array_equal(a.pages, b.pages)
            np.testing.assert_array_equal(a.counts, b.counts)
            if a.writes is None:
                assert b.writes is None
            else:
                np.testing.assert_array_equal(a.writes, b.writes)

    def test_load_pre_channel_npz(self, tmp_path):
        # caches written before the channel existed load as all-reads
        tr = WORKLOADS["thrash"](n_intervals=4, rss_pages=2_000)
        save_trace(tr, tmp_path / "t.npz")
        z = dict(np.load(tmp_path / "t.npz", allow_pickle=False))
        z.pop("writes")
        z.pop("has_writes")
        np.savez_compressed(tmp_path / "old.npz", **z)
        tr2 = load_trace(tmp_path / "old.npz")
        assert len(tr2) == len(tr)
        assert all(ia.writes is None for ia in tr2)

    def test_writes_validation(self):
        from repro.core.trace import IntervalAccess

        with pytest.raises(ValueError, match="writes"):
            IntervalAccess(
                pages=np.array([1, 2]), counts=np.array([4, 4]),
                ops=0.0, writes=np.array([5, 0]),
            )
        with pytest.raises(ValueError, match="writes"):
            IntervalAccess(
                pages=np.array([1, 2]), counts=np.array([4, 4]),
                ops=0.0, writes=np.array([1]),
            )
