"""YCSB core workload C (:mod:`repro.sim.workloads.ycsb`): the generator's
layout and key draws, and the device sweep over it, bit for bit against
the numpy sweep and the frozen ``ReferencePagePool``, with the sweep's
regime counters."""

from __future__ import annotations

import functools

import numpy as np
import pytest

from repro.runtime import tracing
from repro.sim.workloads import WORKLOADS
from repro.sim.workloads import ycsb
from repro.tiering.reference_pool import ReferencePagePool

SMALL = dict(records=20_000, reads_per_interval=4_000, n_intervals=6)
FRACS = tuple(round(1.0 - 0.02 * i, 3) for i in range(46))  # the perf database's vector


def fnv1a_64(data: bytes) -> int:
    """FNV-1a-64, byte by byte."""
    h = ycsb.FNV_OFFSET_BASIS_64
    for b in data:
        h = ((h ^ b) * ycsb.FNV_PRIME_64) % 2**64
    return h


def test_registered_and_small_by_default():
    assert WORKLOADS["ycsb_c"] is ycsb.ycsb_trace
    tr = WORKLOADS["ycsb_c"]()
    assert tr.rss_pages < 30_000 and len(tr) == 17


def test_trace_is_a_function_of_the_seed():
    a = ycsb.ycsb_trace(**SMALL, seed=2**31 + 5)
    b = ycsb.ycsb_trace(**SMALL, seed=2**31 + 5)
    c = ycsb.ycsb_trace(**SMALL, seed=2**31 + 6)
    for x, y in zip(a, b):
        assert np.array_equal(x.pages, y.pages) and np.array_equal(x.touches, y.touches)
    assert not np.array_equal(a.intervals[1].touches, c.intervals[1].touches)


def test_layout_and_read_counts():
    n = SMALL["records"]
    reads = SMALL["reads_per_interval"]
    tr = ycsb.ycsb_trace(**SMALL, seed=9)
    index_pages = 2**15 * 8 // 4096  # the next power of two of buckets, 8 B each
    assert tr.rss_pages == index_pages + n // 4
    load, *run = tr.intervals
    assert len(run) == SMALL["n_intervals"]
    assert np.array_equal(load.pages, np.arange(tr.rss_pages))  # first touch, index first
    assert load.touches[:index_pages].sum() == n  # one bucket write per insert
    assert np.all(load.touches[index_pages:] == 1) and np.all(load.counts[index_pages:] == 64)
    for ia in run:
        idx = ia.pages < index_pages
        assert np.unique(ia.pages).size == ia.pages.size
        assert ia.touches[idx].sum() == reads and ia.touches[~idx].sum() == reads
        assert np.array_equal(ia.counts[idx], ia.touches[idx])
        assert np.array_equal(ia.counts[~idx], 16 * ia.touches[~idx])
        assert ia.rand_frac == 2 / 17 and ia.ops == ycsb.OPS_PER_REQUEST * reads


@pytest.mark.parametrize("data,want", [
    (b"", 0xCBF29CE484222325), (b"a", 0xAF63DC4C8601EC8C), (b"foobar", 0x85944171F73967E8),
])
def test_fnv1a_64_reference_vectors(data, want):
    assert fnv1a_64(data) == want


def test_fnvhash64_is_fnv1a_of_the_little_endian_long():
    vals = np.array([0, 1, 255, 256, 9_999_999, 10**10, 2**40 + 7, 2**62 + 3], dtype=np.int64)
    got = ycsb.fnvhash64(vals)
    for v, g in zip(vals.tolist(), got.tolist()):
        signed = int.from_bytes(fnv1a_64(v.to_bytes(8, "little")).to_bytes(8, "little"),
                                "little", signed=True)
        assert g == abs(signed)


@pytest.mark.parametrize("n", [10, 100, 1000])
def test_zipfian_draws_match_the_inverse_cdf(n):
    """Gray et al.'s closed form is the inverse CDF of Zipf(0.99) exactly
    for ranks 0 and 1 and close to it beyond."""
    zetan = float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -0.99))
    u = (np.arange(10**6) + 0.5) / 10**6  # evenly spaced: the laws, not a sample
    got = ycsb.zipfian(u, items=n, zetan=zetan)
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -0.99) / zetan
    want = np.searchsorted(cdf, u, side="right")
    assert got.min() >= 0 and got.max() < n
    head = want <= 1
    assert np.array_equal(got[head], want[head])
    p_got = np.bincount(got, minlength=n) / u.size
    p_want = np.bincount(want, minlength=n) / u.size
    assert 0.5 * np.abs(p_got - p_want).sum() < 0.025  # total variation


def test_zetan_is_zeta_of_ycsb_item_count():
    """YCSB's precomputed constant is zeta(10^10, 0.99) (Euler-Maclaurin
    from the millionth term on)."""
    th, m, n = 0.99, 10**6, 10**10
    head = np.sum(np.arange(1, m, dtype=np.float64) ** -th)
    tail = (n ** (1 - th) - m ** (1 - th)) / (1 - th) + (m**-th + n**-th) / 2
    tail += th * (m ** (-th - 1) - n ** (-th - 1)) / 12
    assert abs(head + tail - ycsb.ZETAN) < 1e-9 * ycsb.ZETAN


def _run(engine, trace, **kw):
    from repro.sim.api import Experiment, Scenario, run

    return run(Experiment(name=f"ycsb_{engine}", scenarios=[Scenario(trace=trace, engine=engine, **kw)],
                          fm_fracs=FRACS, collect_configs=True))


def test_device_sweep_matches_numpy_and_reference_pool(monkeypatch):
    """At every size of the 46-size vector: the jax sweep (kernel in
    interpret mode) == the numpy sweep == ``ReferencePagePool``. The hot
    set fits every size, so no size interferes; the regime counters are
    what the numpy sweep's own records imply."""
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    make = functools.partial(ycsb.ycsb_trace, **SMALL, seed=2**31 + 11)
    tracing.reset()
    try:
        with tracing.recording():
            jx = _run("jax", make)
        counters = tracing.snapshot()["counters"]
    finally:
        tracing.reset()
    base = _run("numpy", make)
    ref = _run("auto", make, pool_factory=ReferencePagePool)
    assert [r.backend for r in jx.runs] == ["jax_sweep"] * len(FRACS)
    for rj, rn, rr in zip(jx.runs, base.runs, ref.runs):
        for other in (rn, rr):
            assert rj.result.stats == other.result.stats, rj.fm_frac
            assert np.array_equal(rj.result.interval_times, other.result.interval_times)
            assert rj.result.configs == other.result.configs

    tr = make()
    migrating = np.array([[c.pm_pr + c.pm_de > 0 for c in r.result.configs] for r in base.runs])
    assert counters["sweep.intervals"] == len(tr)
    assert counters.get("sweep.interfering_sizes", 0) == 0
    assert counters["interval.touched_pages"] == sum(ia.pages.size for ia in tr)
    assert counters["interval.hot_pages"] == sum(int(np.count_nonzero(ia.touches >= 4)) for ia in tr)
    assert counters["sweep.migrating_sizes"] == int(migrating.sum())
    assert counters["sweep.commit_intervals"] == int(migrating.any(axis=0).sum())
    assert 0 < counters["sweep.commit_intervals"] < len(tr)  # the load interval migrates nothing


@pytest.mark.parametrize("on_device", [True, False], ids=["device", "host"])
def test_device_sweep_rank_paths(monkeypatch, on_device):
    """The demotion ranking on the device (forced here on the CPU) and on
    the host: the jax sweep == the numpy sweep == ``ReferencePagePool`` at
    every size, and every demoting interval ranks once, on the forced path."""
    from repro.sim import jax_engine

    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    monkeypatch.setattr(jax_engine, "_rank_on_device", lambda: on_device)
    make = functools.partial(ycsb.ycsb_trace, **SMALL, seed=2**31 + 12)
    tracing.reset()
    try:
        with tracing.recording():
            jx = _run("jax", make)
        counters = tracing.snapshot()["counters"]
    finally:
        tracing.reset()
    base = _run("numpy", make)
    ref = _run("auto", make, pool_factory=ReferencePagePool)
    for rj, rn, rr in zip(jx.runs, base.runs, ref.runs):
        for other in (rn, rr):
            assert rj.result.stats == other.result.stats, rj.fm_frac
            assert np.array_equal(rj.result.interval_times, other.result.interval_times)
            assert rj.result.configs == other.result.configs

    demoting = np.any([[c.pm_de > 0 for c in r.result.configs] for r in base.runs], axis=0)
    taken, other = ("rank.device", "rank.host")[:: 1 if on_device else -1]
    assert counters[taken] == int(demoting.sum()) > 0
    assert counters.get(other, 0) == 0
