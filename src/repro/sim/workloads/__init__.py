"""The paper's evaluation workloads (Table 1), implemented for real.

Each workload runs its actual algorithm (numpy-vectorized) over synthetic
inputs, instrumented at page granularity: every data-structure access is
recorded into per-interval page-access histograms (a
:class:`repro.core.trace.Trace`). RSS values are scaled down from the
paper's 10–24 GB to tens of MB so a full evaluation sweep runs in seconds on
one CPU core; the scaling is uniform (page size, access counts, and
migration counts shrink together), which preserves the ratios the Tuna model
operates on.

| workload | paper RSS | here (default) | access pattern              |
|----------|-----------|----------------|-----------------------------|
| bfs      | 12.4 G    | ~50 MB         | frontier bursts, power law  |
| sssp     | 23.5 G    | ~80 MB         | relaxation rounds           |
| pagerank | 15.8 G    | ~60 MB         | full sweeps, power law      |
| xsbench  | 16.4 G    | ~60 MB         | random lookups, high AI     |
| btree    | 10.8 G    | ~45 MB         | Zipf lookups, hot root      |

``thrash`` is not from the paper's table: it is the adversarial rotating
working set (~2x the fast tier) that pins the migration-failure /
direct-reclaim regime the Tuna model's knee lives in — the engine
benchmark and the equivalence suite sweep it to exercise the bulk
policy step's thrash path.

``arrivals`` is the fleet traffic shape (:mod:`repro.sim.workloads.
arrivals`): open/closed-loop session arrivals under Poisson + diurnal +
flash-crowd rate modulation with long-tail session lifetimes — the
per-tenant workload of the :mod:`repro.fleet` multi-tenant layer, and a
bursty-churn stressor for every other engine path.

``ycsb_c`` is YCSB core workload C (:mod:`repro.sim.workloads.ycsb`):
zipfian reads of 1 KB records through a hash index, a skewed hot set of
a few percent of the RSS.
"""

from repro.sim.workloads.base import PageMapper
from repro.sim.workloads.graphs import bfs_trace, pagerank_trace, sssp_trace
from repro.sim.workloads.xsbench import xsbench_trace
from repro.sim.workloads.btree import btree_trace
from repro.sim.workloads.thrash import thrash_trace
from repro.sim.workloads.arrivals import arrivals_trace
from repro.sim.workloads.ycsb import ycsb_trace

WORKLOADS = {
    "bfs": bfs_trace,
    "sssp": sssp_trace,
    "pagerank": pagerank_trace,
    "xsbench": xsbench_trace,
    "btree": btree_trace,
    "thrash": thrash_trace,
    "arrivals": arrivals_trace,
    "ycsb_c": ycsb_trace,
}

__all__ = ["WORKLOADS", "PageMapper", "bfs_trace", "sssp_trace",
           "pagerank_trace", "xsbench_trace", "btree_trace", "thrash_trace",
           "arrivals_trace", "ycsb_trace"]
