"""From a profiler trace to device busy time, op times and idle-gap causes.

Reads the ``perfetto_trace.json.gz`` that ``jax.profiler`` writes beside
its ``.xplane.pb``. Device processes are those named ``/device:...``;
host spans are the ``TraceAnnotation`` events the benchmark writes. All
times are on the trace's own clock (microseconds), and every quantity is
taken inside the window span that the harness writes around the
measured window.

- busy: the union of the intervals in which an operation runs on the
  device (the ops line where the device has one, else all its events),
  averaged over the devices;
- per name: summed durations and counts of the device's op and module
  events, so a step or kernel is found by its stable name;
- idle gaps: the window minus the busy union on each device, split by
  the host span that covers each part ("driver, other" where none does).
"""

from __future__ import annotations

import gzip
import json
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

WINDOW_SPAN = "bench_window"
OTHER = "driver, other"
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


def load(path) -> list:
    """The trace's events, from a ``.json.gz`` or ``.json`` file, or the
    newest ``perfetto_trace.json.gz`` under a profiler log directory."""
    path = Path(path)
    if path.is_dir():
        path = max(path.glob("**/perfetto_trace.json.gz"), key=lambda p: p.stat().st_mtime)
    opener = gzip.open if path.suffix == ".gz" else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    return doc["traceEvents"] if isinstance(doc, dict) else doc


def union(iv: np.ndarray) -> np.ndarray:
    """Sorted disjoint ``[start, end]`` rows covering the same points."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, dtype=np.float64)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]] if iv.size else iv.reshape(0, 2)


def complement(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """The parts of ``[lo, hi]`` that a disjoint sorted ``iv`` leaves free."""
    edges = np.concatenate([[lo], iv.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two sorted disjoint interval sets."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


@dataclass
class Reduced:
    window_us: float
    busy_us: float  # mean over devices
    n_devices: int
    ops: dict = field(default_factory=dict)  # name -> [microseconds, count]
    modules: dict = field(default_factory=dict)
    idle_by_span: dict = field(default_factory=dict)  # span -> microseconds

    def time_of(self, key, table: str = "modules") -> tuple[float, int]:
        """Summed microseconds and count of the events whose name holds
        ``key`` (or any of a tuple of keys), on the op or module lines."""
        keys = (key,) if isinstance(key, str) else tuple(key)
        us = n = 0
        for name, (t, c) in getattr(self, table).items():
            if any(k in name for k in keys):
                us += t
                n += c
        return us, n

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[name, t * 1e-6] for name, (t, _) in ops],
            "idle_gaps": [[name, t * 1e-6] for name, t in gaps],
        }


def reduce(events: list, spans=()) -> Reduced:
    """Reduce trace events; ``spans`` are the host span names that idle
    gaps are attributed to."""
    proc, thread = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            thread[(e["pid"], e.get("tid"))] = e["args"]["name"]
    devices = sorted(p for p, n in proc.items() if n.startswith("/device:"))
    xs = [e for e in events if e.get("ph") == "X" and "dur" in e]
    win = [e for e in xs if e.get("name") == WINDOW_SPAN and e["pid"] not in devices]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span in the trace, found {len(win)}")
    lo, hi = float(win[0]["ts"]), float(win[0]["ts"]) + float(win[0]["dur"])
    spans = set(spans)
    span_iv = defaultdict(list)
    for e in xs:
        if e["pid"] not in devices and e["name"] in spans:
            span_iv[e["name"]].append((float(e["ts"]), float(e["ts"]) + float(e["dur"])))
    span_iv = {k: union(clip(np.asarray(v), lo, hi)) for k, v in span_iv.items()}
    all_spans = union(np.concatenate(list(span_iv.values()))) if span_iv else np.zeros((0, 2))

    red = Reduced(window_us=hi - lo, busy_us=0.0, n_devices=len(devices))
    ops, modules = defaultdict(lambda: [0.0, 0]), defaultdict(lambda: [0.0, 0])
    idle = defaultdict(float)
    busy = []
    for d in devices:
        lines = {tid for (p, tid), n in thread.items() if p == d}
        op_tids = {t for t in lines if thread[(d, t)] in OPS_LINES}
        mod_tids = {t for t in lines if thread[(d, t)] in MODULE_LINES}
        dev = [e for e in xs if e["pid"] == d and lo <= float(e["ts"]) < hi]
        op_ev = [e for e in dev if e.get("tid") in op_tids] if op_tids else dev
        for e in op_ev:
            ops[e["name"]][0] += float(e["dur"])
            ops[e["name"]][1] += 1
        for e in dev:
            if e.get("tid") in mod_tids:
                modules[e["name"]][0] += float(e["dur"])
                modules[e["name"]][1] += 1
        iv = union(clip(np.asarray([(float(e["ts"]), float(e["ts"]) + float(e["dur"]))
                                    for e in op_ev]).reshape(-1, 2), lo, hi))
        busy.append(float(np.sum(iv[:, 1] - iv[:, 0])) if iv.size else 0.0)
        gaps = complement(iv, lo, hi)
        for name, s_iv in span_iv.items():
            idle[name] += overlap(gaps, s_iv) / len(devices)
        gap_total = float(np.sum(gaps[:, 1] - gaps[:, 0])) if gaps.size else 0.0
        idle[OTHER] += (gap_total - overlap(gaps, all_spans)) / len(devices)
    red.busy_us = float(np.mean(busy)) if busy else 0.0
    red.ops, red.modules, red.idle_by_span = dict(ops), dict(modules), dict(idle)
    return red
