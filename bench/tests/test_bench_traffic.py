"""The traffic generators and the sweep driver's reading of the program."""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from bench import generate
from bench.drivers import sweep
from bench.reference import microbench

GUPS = dict(generate.load("configs", "gups-8g"), table_words=2**20)
PERFDB = generate.load("configs", "perfdb")
BUILD = generate.load("traffic", "build")


def test_gups_updates_fall_on_every_page_in_full():
    tr = generate.gups_trace(GUPS, 3, seed=2**31 + 11)
    words = GUPS["table_words"]
    assert tr.rss_pages == words * 8 // GUPS["page_bytes"]
    init, *updates = tr.intervals
    assert init.pages.size == tr.rss_pages and init.rand_frac == 0.0
    per = words * GUPS["updates_per_word"] // GUPS["intervals_per_run"]
    for ia in updates:
        assert int(ia.counts.sum()) == per
        assert np.array_equal(ia.counts, ia.touches)
        assert np.unique(ia.pages).size == ia.pages.size
        assert ia.ops == GUPS["ops_per_update"] * per


@pytest.mark.parametrize("seed", [7, 2**33 + 5])
def test_gups_trace_is_a_function_of_the_seed(seed):
    a = generate.gups_trace(GUPS, 2, seed)
    b = generate.gups_trace(GUPS, 2, seed)
    c = generate.gups_trace(GUPS, 2, seed + 1)
    assert np.array_equal(a.intervals[1].counts, b.intervals[1].counts)
    assert not np.array_equal(a.intervals[1].counts, c.intervals[1].counts)


def _work(v):
    """What sets a record's cost: everything but the arithmetic intensity."""
    return dataclasses.astuple(dataclasses.replace(v, ai=0.0))


def test_perfdb_vectors_differ_by_seed_but_not_in_work():
    a = generate.perfdb_vectors(PERFDB, BUILD, 11)
    b = generate.perfdb_vectors(PERFDB, BUILD, 3_000_000_017)
    assert len(a) == len(b) == len(BUILD["workloads"]) * len(BUILD["strata"])
    assert [_work(v) for v in a] == [_work(v) for v in b]
    assert {v.ai for v in a}.isdisjoint({v.ai for v in b})
    assert a == generate.perfdb_vectors(PERFDB, BUILD, 11)
    assert all(v.rss_pages == PERFDB["rss_pages"] for v in a + b)


def test_perfdb_vectors_reach_the_build_unscaled():
    from repro.core.tuner import scale_config

    for cv in generate.perfdb_vectors(PERFDB, BUILD, 5):
        assert scale_config(cv, PERFDB["max_rss_pages"]) is cv


def test_reference_refuses_a_vector_over_the_build_size():
    cv = dataclasses.asdict(generate.perfdb_vectors(PERFDB, BUILD, 5)[0])
    cv["rss_pages"] = PERFDB["max_rss_pages"] + 1
    with pytest.raises(ValueError, match="max_rss_pages"):
        microbench.record_curve(cv, (1.0, 0.5), None, 4, 2, PERFDB["max_rss_pages"])


@pytest.mark.parametrize("n,want", [(1, [0]), (2, [0, 1]), (3, [0, 2])])
def test_first_and_last_sweep_are_compared(n, want):
    cell = sweep.Cell(GUPS, generate.load("traffic", "sweep46"), 1)
    cell.sweeps = [None] * n
    assert cell.compared_sweeps() == want


def test_missing_private_sweep_is_named(monkeypatch):
    from repro.sim import jax_engine

    monkeypatch.delattr(jax_engine, sweep.SWEEP)
    cell = sweep.Cell(GUPS, generate.load("traffic", "sweep46"), 1)
    with pytest.raises(RuntimeError, match=sweep.SWEEP):
        cell._sweep(None)
