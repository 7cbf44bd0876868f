"""The kernel's roofline count comes from the problem's shapes alone."""

from __future__ import annotations

import inspect
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import roofline, run
from bench.trace_reduce import Reduced

PEAKS = json.loads((Path(run.BENCH) / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def test_count_takes_only_the_problem_shape():
    for fn in (roofline.demote_rank_bytes, roofline.demote_rank_ops):
        assert list(inspect.signature(fn).parameters) == ["n_sizes", "rss_pages"]


@pytest.mark.parametrize("n_sizes,rss_pages", [(46, 2_621_440), (7, 2_621_440), (45, 20_000)])
def test_bytes_are_one_byte_in_one_bit_out_per_entry(n_sizes, rss_pages):
    assert roofline.demote_rank_bytes(n_sizes, rss_pages) == n_sizes * rss_pages * 1.125
    assert roofline.demote_rank_bytes(2 * n_sizes, rss_pages) == 2 * roofline.demote_rank_bytes(
        n_sizes, rss_pages)


def test_v5e_bound_is_memory_bandwidth():
    t, bound = roofline.demote_rank_least_s(46, 2_621_440, PEAKS)
    assert bound == "hbm"
    assert t == pytest.approx(46 * 2_621_440 * 1.125 / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        run.load_peaks("TPU v0 imaginary")


def _roofline_reader():
    return run.reader("demote_rank_roofline.sweep")


@pytest.mark.parametrize("op", ["_victim_partition_pallas.1", "_victim_partition_kernel"])
def test_roofline_share_from_kernel_time(op):
    least, _ = roofline.demote_rank_least_s(46, 2_621_440, PEAKS)
    red = Reduced(window_us=1e6, busy_us=1e5, n_devices=1,
                  ops={op: [4 * least * 1e6 * 10, 4], "fusion": [7.0, 1]})
    ctx = SimpleNamespace(trace=red, peaks=PEAKS,
                          window={"work": {"n_sizes": 46, "rss_pages": 2_621_440}})
    assert _roofline_reader().read(ctx) == pytest.approx(10.0)


def test_roofline_share_is_left_out_without_kernel_events(capsys):
    red = Reduced(window_us=1e6, busy_us=1e5, n_devices=1, ops={"fusion": [5.0, 1]})
    ctx = SimpleNamespace(trace=red, peaks=PEAKS,
                          window={"work": {"n_sizes": 46, "rss_pages": 2_621_440}})
    assert _roofline_reader().read(ctx) is None
    assert capsys.readouterr().err == ""


def test_missing_kernel_beside_a_commit_step_is_said(capsys):
    red = Reduced(window_us=1e6, busy_us=1e5, n_devices=1, ops={"fusion": [5.0, 1]},
                  modules={"jit_commit_step": [9.0, 1]})
    ctx = SimpleNamespace(trace=red, peaks=PEAKS,
                          window={"work": {"n_sizes": 46, "rss_pages": 2_621_440}})
    assert _roofline_reader().read(ctx) is None
    assert "_victim_partition_pallas" in capsys.readouterr().err
