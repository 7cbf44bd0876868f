"""YCSB core workload C: a read-only key-value store under zipfian keys.

Cooper et al., "Benchmarking Cloud Serving Systems with YCSB" (SoCC 2010),
``workloads/workloadc`` of github.com/brianfrankcooper/YCSB: 100% reads
(``readproportion=1.0``), ``requestdistribution=zipfian``, records of
``fieldcount=10`` fields of ``fieldlength=100`` bytes. YCSB describes it as
a user-profile cache. Here it is served by an in-memory hash store:

* **Layout.** The bucket array of the hash index comes first (8-byte
  buckets, as many as the least power of two at least the record
  count), then the records in 1 KiB slots, record ``i`` in slot ``i``
  (four to a 4 KiB page).
* **Load phase** (the trace's first interval): every record is inserted
  once, in key order. The record slots are written as one sequential
  scan (16 cache lines per record, one touch per page); each insert
  writes its key's bucket (one cache line and one touch). Every page is
  allocated first-touch in page order, the index first.
* **Run phase** (the other intervals): ``reads_per_interval`` reads. A
  read probes one cache line of its key's bucket (random, one touch) and
  reads the record's 16 cache lines (the first random, 15 sequential, one
  touch). Per interval the accesses are summed per page, so page ids are
  unique.

Keys follow YCSB's ``ScrambledZipfianGenerator``: Gray et al.'s
closed-form Zipfian draw over ``ZIPF_ITEMS`` items with constant 0.99 and
YCSB's precomputed ``ZETAN``, then ``fnvhash64`` of the rank taken modulo
the record count. A key's bucket is ``fnvhash64(key)`` modulo the bucket
count: ``fnvhash64`` of the key number is what YCSB's hashed insert order
puts in the key's name. The generator is vectorised in numpy; a seed
gives one uniform draw per read.

Assumed, not from YCSB: ``OPS_PER_REQUEST`` integer operations per
request (hash the key, probe the bucket, compare the key, copy the
record) and ``NUM_THREADS`` client threads.
"""

from __future__ import annotations

import numpy as np

from repro.core.trace import IntervalAccess, Trace

ZIPFIAN_CONSTANT = 0.99
ZIPF_ITEMS = 10**10 + 1  # ZipfianGenerator(0, ITEM_COUNT): max - min + 1
ZETAN = 26.46902820178302  # YCSB's zeta(10^10, 0.99)
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 0x100000001B3
RECORD_BYTES = 1024  # fieldcount 10 x fieldlength 100 in a 1 KiB slot
BUCKET_BYTES = 8
PAGE_BYTES = 4096
CACHELINE = 64
OPS_PER_REQUEST = 200.0
NUM_THREADS = 16


def fnvhash64(vals: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64``: FNV-1a-64 over the eight little-endian
    bytes of each value, then ``Math.abs`` of the signed result."""
    v = np.asarray(vals, dtype=np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= np.uint64(FNV_PRIME_64)
        v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian(u: np.ndarray, items: int = ZIPF_ITEMS, zetan: float = ZETAN) -> np.ndarray:
    """YCSB's ``ZipfianGenerator.nextLong`` for uniforms ``u`` in [0, 1):
    Gray et al.'s closed form, exact for ranks 0 and 1; ``zetan`` is
    zeta(items, 0.99)."""
    theta = ZIPFIAN_CONSTANT
    zeta2theta = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2theta / zetan)
    uz = u * zetan
    rank = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    rank[uz < 1.0 + 0.5**theta] = 1
    rank[uz < 1.0] = 0
    return rank


def ycsb_trace(
    n_intervals: int = 16,
    records: int = 100_000,
    reads_per_interval: int = 20_000,
    seed: int = 31,
) -> Trace:
    """The load interval, then ``n_intervals`` intervals of reads."""
    index_buckets = 1 << (records - 1).bit_length()
    lines = RECORD_BYTES // CACHELINE
    per_page = PAGE_BYTES // RECORD_BYTES
    index_pages = -(-index_buckets * BUCKET_BYTES // PAGE_BYTES)
    value_pages = -(-records // per_page)
    rss = index_pages + value_pages
    buckets_per_page = PAGE_BYTES // BUCKET_BYTES
    rng = np.random.default_rng(seed)
    trace = Trace(name="ycsb_c", rss_pages=rss, num_threads=NUM_THREADS)

    # each key's index page, hashed once at load
    key_page = fnvhash64(np.arange(records)) % index_buckets // buckets_per_page
    # load: one bucket write per insert, the slots as one sequential scan
    bucket_hits = np.bincount(key_page, minlength=index_pages)
    slot_lines = np.bincount(np.arange(records) // per_page, minlength=value_pages) * lines
    trace.append(IntervalAccess(
        pages=np.arange(rss, dtype=np.int64),
        counts=np.concatenate([bucket_hits, slot_lines]),
        ops=OPS_PER_REQUEST * records,
        rand_frac=1.0 / (lines + 1),
        touches=np.concatenate([bucket_hits, np.ones(value_pages, dtype=np.int64)]),
    ))
    for _ in range(n_intervals):
        keys = fnvhash64(zipfian(rng.random(reads_per_interval))) % records
        reads = np.concatenate([
            np.bincount(key_page[keys], minlength=index_pages),
            np.bincount(keys // per_page, minlength=value_pages),
        ])
        pages = np.flatnonzero(reads)
        touches = reads[pages]
        counts = touches * np.where(pages < index_pages, 1, lines)
        trace.append(IntervalAccess(
            pages=pages, counts=counts, ops=OPS_PER_REQUEST * reads_per_interval,
            rand_frac=2.0 / (lines + 1), touches=touches,
        ))
    return trace
