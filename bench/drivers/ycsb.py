"""Driver of a YCSB sweep cell: a key-value store's trace swept over the sizes.

The window, the release and the comparison are those of
:class:`bench.drivers.sweep.Cell`; only the trace differs. It is YCSB
core workload C (Cooper et al., SoCC 2010; ``workloads/workloadc``) on an
in-memory hash store, generated here from the configuration and
``--seed`` with nothing taken from the program's own generators, so that
no change to the program changes the cell's work:

* the layout: the hash index's ``index_buckets`` buckets of
  ``index_bucket_bytes`` first, then ``recordcount`` records, record ``i``
  in slot ``i`` of ``record_slot_bytes``;
* the load phase, the trace's first interval: every record inserted once
  in key order, the slots written as one sequential scan (one touch per
  page, 16 cache lines per record) and each insert writing its key's
  bucket (one cache line and one touch); every page is allocated
  first-touch in page order;
* ``trace_intervals`` intervals of ``reads_per_interval`` reads each, a
  read being one random cache line of its key's bucket and the record's
  cache lines (the first random, the rest sequential), one touch each,
  summed per page. Keys are YCSB's ``ScrambledZipfianGenerator``: Gray et
  al.'s Zipfian draw over ``zipf_items`` with ``zipfian_constant`` and
  ``zetan``, then ``fnvhash64`` modulo ``recordcount``; a key's bucket is
  ``fnvhash64(key)`` modulo the bucket count.
"""

from __future__ import annotations

import time

import numpy as np

from bench.drivers import sweep
from repro.core.trace import IntervalAccess, Trace

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 0x100000001B3
CACHELINE = 64


def fnvhash64(vals: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a-64 over the eight little-endian
    bytes of the long, then ``Math.abs`` of the signed result."""
    v = np.asarray(vals, dtype=np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian(u: np.ndarray, items: int, theta: float, zetan: float) -> np.ndarray:
    """YCSB's ``ZipfianGenerator.nextLong`` for uniforms ``u`` in [0, 1)."""
    zeta2theta = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2theta / zetan)
    uz = u * zetan
    rank = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    rank[uz < 1.0 + 0.5**theta] = 1
    rank[uz < 1.0] = 0
    return rank


def ycsb_c_trace(cfg: dict, n_intervals: int, seed: int) -> Trace:
    """The configuration's load interval and ``n_intervals`` read intervals."""
    records = int(cfg["recordcount"])
    buckets = int(cfg["index_buckets"])
    page = int(cfg["page_bytes"])
    lines = int(cfg["record_slot_bytes"]) // CACHELINE
    per_page = page // int(cfg["record_slot_bytes"])
    buckets_per_page = page // int(cfg["index_bucket_bytes"])
    index_pages = -(-buckets // buckets_per_page)
    value_pages = -(-records // per_page)
    rss = index_pages + value_pages
    reads = int(cfg["reads_per_interval"])
    ops = float(cfg["ops_per_request"])
    items, theta, zetan = int(cfg["zipf_items"]), float(cfg["zipfian_constant"]), float(cfg["zetan"])
    rng = np.random.default_rng(int(seed) % 2**64)
    trace = Trace(name="ycsb_c", rss_pages=rss, num_threads=int(cfg["num_threads"]))
    bucket_page = fnvhash64(np.arange(records)) % buckets // buckets_per_page
    inserts = np.bincount(bucket_page, minlength=index_pages)
    trace.append(IntervalAccess(
        pages=np.arange(rss, dtype=np.int64),
        counts=np.concatenate([inserts, np.bincount(np.arange(records) // per_page) * lines]),
        ops=ops * records, rand_frac=1.0 / (lines + 1),
        touches=np.concatenate([inserts, np.ones(value_pages, dtype=np.int64)]),
    ))
    for _ in range(n_intervals):
        keys = fnvhash64(zipfian(rng.random(reads), items, theta, zetan)) % records
        per = np.concatenate([np.bincount(bucket_page[keys], minlength=index_pages),
                              np.bincount(keys // per_page, minlength=value_pages)])
        pages = np.flatnonzero(per)
        touches = per[pages]
        trace.append(IntervalAccess(
            pages=pages, counts=np.where(pages < index_pages, touches, touches * lines),
            ops=ops * reads, rand_frac=2.0 / (lines + 1), touches=touches,
        ))
    return trace


class Cell(sweep.Cell):
    """A sweep cell whose trace is the configuration's YCSB workload C."""

    def setup(self) -> None:
        t0 = time.perf_counter()
        self.trace = ycsb_c_trace(self.cfg, int(self.traffic["trace_intervals"]), self.seed)
        self.info["trace_generation_s"] = time.perf_counter() - t0
        self.info["trace_intervals"] = len(self.trace)
        touched = [ia.pages.size for ia in self.trace.intervals[1:]]
        self.info["read_interval_pages"] = [min(touched), max(touched)]
        # warm-up: the load interval and the first read intervals, which
        # hold every step shape the window uses
        n = 1 + int(self.traffic["warmup_intervals"])
        self._sweep(Trace(name=self.trace.name, rss_pages=self.trace.rss_pages,
                          intervals=self.trace.intervals[:n],
                          num_threads=self.trace.num_threads))
