"""The YCSB cell: its generator against the program's, a traced rehearsal
at a small size, and the float32 control of its comparison."""

from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import generate, roofline, run
from bench.drivers import sweep, ycsb
from bench.trace_reduce import Reduced

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "ycsbc10g.sweep46"
CFG = generate.load("configs", "ycsb-c-10g")
TRAFFIC = generate.load("traffic", "ycsbc_sweep46")
# 20,000 records, their 2^15-bucket index, reads in the configuration's
# ratio to records; every width and fraction as configured
SMALL = {"recordcount": 20_000, "index_buckets": 2**15, "reads_per_interval": 4_000}


def program_trace(cfg, n_intervals, seed):
    from repro.sim.workloads import ycsb as program

    for key, const in (("ops_per_request", program.OPS_PER_REQUEST),
                       ("num_threads", program.NUM_THREADS),
                       ("zipf_items", program.ZIPF_ITEMS), ("zetan", program.ZETAN),
                       ("zipfian_constant", program.ZIPFIAN_CONSTANT),
                       ("record_slot_bytes", program.RECORD_BYTES),
                       ("index_bucket_bytes", program.BUCKET_BYTES),
                       ("page_bytes", program.PAGE_BYTES)):
        assert cfg[key] == const, key
    assert cfg["index_buckets"] == 1 << (cfg["recordcount"] - 1).bit_length()
    return program.ycsb_trace(n_intervals=n_intervals, records=cfg["recordcount"],
                              reads_per_interval=cfg["reads_per_interval"], seed=seed)


def assert_same_trace(a, b):
    assert (a.rss_pages, a.num_threads, len(a)) == (b.rss_pages, b.num_threads, len(b))
    for x, y in zip(a, b):
        assert np.array_equal(x.pages, y.pages)
        assert np.array_equal(x.counts, y.counts) and np.array_equal(x.touches, y.touches)
        assert (x.ops, x.rand_frac) == (y.ops, y.rand_frac)


@pytest.mark.parametrize("seed", [5, 3_000_000_029])
def test_bench_generator_equals_the_programs(seed):
    cfg = dict(CFG, **SMALL)
    assert_same_trace(ycsb.ycsb_c_trace(cfg, 4, seed), program_trace(cfg, 4, seed))


def test_full_layout_is_the_configured_one():
    """The load interval at full size: 2,532,768 pages, the index first."""
    tr = ycsb.ycsb_c_trace(CFG, 0, 1)
    assert tr.rss_pages == CFG["layout"]["rss_pages"] == 2_532_768
    assert tr.intervals[0].touches[: CFG["layout"]["index_pages"]].sum() == CFG["recordcount"]
    assert_same_trace(tr, program_trace(CFG, 0, 1))


@pytest.fixture
def ycsb_small(small, monkeypatch):
    """Shrinks ``ycsb-c-10g`` alone, over the ``small`` fixture's loader."""
    load = small.load

    def small_ycsb(kind, name):
        d = load(kind, name)
        if kind == "configs" and name == "ycsb-c-10g":
            d.update(SMALL)
        return d

    monkeypatch.setattr(small, "load", small_ycsb)
    return small


def test_traced_rehearsal_reports_the_new_metrics(run_cell, ycsb_small):
    from repro.runtime import tracing

    tracing.reset()  # the table holds this run's window alone
    rc, res, err = run_cell(CELL, trace=1)
    tracing.reset()
    assert rc == 0, err
    assert res["correct"] is True and res["failed"] == 0
    assert all(c["value"] == 0 for c in res["compared"].values())
    got = res["metrics"]
    # the CPU's trace has no device plane: the device_trace metrics read
    # nothing here (their readers are checked below)
    want = {m["name"] for m in SPEC["per_layer"]
            if m.get("workloads") == [CELL] and m["source"] != "device_trace"}
    assert len(want) == 4 and want <= set(got), sorted(want - set(got))
    assert got["interfering_sizes.ycsbc"]["value"] == 0
    assert 0 < got["migrating_sizes.ycsbc"]["value"] <= 45
    assert got["hot_pages_k.ycsbc"]["value"] > 0
    assert got["rank_ms.ycsbc"]["value"] > 0


def test_device_trace_readers():
    """On a reduced trace: 1 s window, 0.2 s busy; 3 schedule steps of
    30 ms, 2 commit steps of 50 ms, each with one kernel call at 4x its
    least time."""
    peaks = json.loads((run.BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    least, _ = roofline.demote_rank_least_s(46, 2_532_768, peaks)
    red = Reduced(window_us=1e6, busy_us=2e5, n_devices=1,
                  ops={"_victim_partition_pallas.3": [2 * 4 * least * 1e6, 2]},
                  modules={"jit_schedule_step(1)": [9e4, 3], "jit_commit_step(2)": [1e5, 2]})
    ctx = SimpleNamespace(trace=red, peaks=peaks,
                          window={"work": {"n_sizes": 46, "rss_pages": 2_532_768}})
    want = {"device_idle.ycsbc": 80.0, "schedule_step_ms.ycsbc": 30.0,
            "commit_step_ms.ycsbc": 50.0, "demote_rank_roofline.ycsbc": 25.0}
    for name, value in want.items():
        assert run.reader(name).read(ctx) == pytest.approx(value), name
        assert run.reader(name).read(SimpleNamespace(trace=None)) is None


@pytest.mark.parametrize("seed", [7, 3_000_000_021])
def test_float32_control_is_rejected(seed):
    cfg = dict(CFG, **SMALL)
    cell = ycsb.Cell(cfg, TRAFFIC, seed)
    cell.trace = ycsb.ycsb_c_trace(cfg, int(TRAFFIC["trace_intervals"]), seed)
    idx = cell.compared_sizes()
    n = 6
    want = cell.reference(idx, n)
    compared, failed = sweep.compare(cell.reference(idx, n, dtype=np.float32), want, n)
    assert any(v > lim for v, lim in compared.values()), compared
    assert failed > 0
    same, _ = sweep.compare(want, cell.reference(idx, n), n)
    assert all(v <= lim for v, lim in same.values()), same


def test_cell_reports_the_sweep_rate_and_setup():
    assert run.cell_metrics(SPEC, "end_to_end", CELL) == [
        m for m in SPEC["end_to_end"] if m["name"] in ("sweep_size_intervals_per_s", "setup_s")]
