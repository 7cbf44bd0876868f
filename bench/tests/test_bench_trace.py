"""The reduction from a profiler trace to busy time, op times and gaps."""

from __future__ import annotations

import numpy as np
import pytest

from bench import trace_reduce as tr


def _meta(pid, name, tid=None, thread=None):
    out = [{"ph": "M", "pid": pid, "name": "process_name", "args": {"name": name}}]
    if tid is not None:
        out.append({"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                    "args": {"name": thread}})
    return out


def _x(pid, tid, ts, dur, name):
    return {"ph": "X", "pid": pid, "tid": tid, "ts": ts, "dur": dur, "name": name}


def synthetic():
    """Window 0-100 us; device ops at 10-20, 15-30 (overlapping) and
    60-70; host spans resolve_victims 30-55 and cost_model 75-80."""
    ev = _meta(1, "/host:CPU", 11, "python") + _meta(2, "/device:TPU:0", 21, "XLA Ops")
    ev += _meta(2, "/device:TPU:0", 22, "XLA Modules")
    ev += [
        _x(1, 11, 0, 100, tr.WINDOW_SPAN),
        _x(1, 11, 30, 25, "resolve_victims"),
        _x(1, 11, 75, 5, "cost_model"),
        _x(2, 21, 10, 10, "fusion.1"),
        _x(2, 21, 15, 15, "_victim_partition_kernel"),
        _x(2, 21, 60, 10, "fusion.1"),
        _x(2, 22, 10, 20, "jit_commit_step(7)"),
        _x(2, 22, 60, 10, "jit_schedule_step(3)"),
        _x(2, 21, 150, 10, "fusion.1"),  # after the window: left out
    ]
    return ev


def test_interval_helpers():
    u = tr.union(np.array([[5.0, 7.0], [1.0, 3.0], [2.0, 4.0]]))
    assert u.tolist() == [[1.0, 4.0], [5.0, 7.0]]
    assert tr.complement(u, 0.0, 10.0).tolist() == [[0.0, 1.0], [4.0, 5.0], [7.0, 10.0]]
    assert tr.overlap(u, np.array([[3.0, 6.0]])) == 2.0


def test_busy_is_the_union_of_device_ops():
    red = tr.reduce(synthetic(), spans=["resolve_victims", "cost_model"])
    assert red.window_us == 100
    assert red.busy_us == 30  # 10-30 and 60-70, the overlap counted once
    assert red.n_devices == 1


def test_device_time_by_stable_name():
    red = tr.reduce(synthetic(), spans=[])
    assert red.time_of("commit_step") == (20.0, 1)
    assert red.time_of("schedule_step") == (10.0, 1)
    assert red.time_of("_victim_partition_kernel", table="ops") == (15.0, 1)
    assert red.ops["fusion.1"] == [20.0, 2]


def test_idle_gaps_go_to_the_covering_host_span():
    red = tr.reduce(synthetic(), spans=["resolve_victims", "cost_model"])
    # idle: 0-10, 30-60, 70-100 = 70 us
    assert red.idle_by_span["resolve_victims"] == 25
    assert red.idle_by_span["cost_model"] == 5
    assert red.idle_by_span[tr.OTHER] == 40
    assert sum(red.idle_by_span.values()) == pytest.approx(red.window_us - red.busy_us)
    bd = red.breakdown()
    assert bd["idle_gaps"][0][0] == tr.OTHER
    assert bd["idle_gaps"][0][1] == pytest.approx(40e-6)
    assert [n for n, _ in bd["device_ops"]] == ["fusion.1", "_victim_partition_kernel"]


def test_trace_without_window_span_is_refused():
    ev = [e for e in synthetic() if e.get("name") != tr.WINDOW_SPAN]
    with pytest.raises(ValueError):
        tr.reduce(ev)
