"""Tiered serving demo: decode service with a two-tier paged KV cache and
the Tuna loop closed — the paper's technique as a first-class serving
feature (DESIGN.md §4).

Sessions arrive continuously (Zipf popularity with drift); idle sessions'
KV pages are demoted to host memory by the watermark reclaimer; resumes
promote them back through the batched-DMA migration kernel. Tuna tunes
the HBM page budget every interval from live telemetry.

Run:  PYTHONPATH=src python examples/serve_tiered.py
"""

from pathlib import Path

import numpy as np

from repro.core import TunaTuner, TunerConfig, WatermarkController
from repro.core.perfdb import PerfDB, PerfRecord
from repro.core.telemetry import ConfigVector
from repro.serving import ContinuousBatcher, TieredPagedKV, TieredServer
from repro.serving.kv_cache import KVPageConfig
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache(Path(__file__).resolve().parents[1])

TOTAL_PAGES, HBM_PAGES = 4096, 1024

kv = TieredPagedKV(
    KVPageConfig(n_groups=4, page_size=16, kv_heads=2, head_dim=32),
    total_pages=TOTAL_PAGES, hbm_capacity=HBM_PAGES,
)
batcher = ContinuousBatcher(n_sessions=400, page_size=16, max_batch=16,
                            resumes_per_round=3.0)

# a tiny hand-built perf DB for the demo (production: offline microbench
# sweep on the real tier hardware; see benchmarks/common.py)
grid = np.array([1.0, 0.85, 0.7, 0.55, 0.4, 0.25])
db = PerfDB()
for pacc in (200, 800, 2400):
    for pm in (2, 16, 64):
        loss = (pm / 32.0) * (1.0 / grid - 1.0) * 0.08
        db.add(PerfRecord(
            config=ConfigVector(pacc_f=pacc, pacc_s=pm, pm_de=pm, pm_pr=pm,
                                ai=1e6, rss_pages=TOTAL_PAGES, hot_thr=2,
                                num_threads=1),
            fm_fracs=grid, times=1.0 + loss,
        ))
db.build()

tuner = TunaTuner(
    db, WatermarkController(kv.pool, max_step_frac=0.1),
    TunerConfig(target_loss=0.05), peak_rss_pages=HBM_PAGES,
)
server = TieredServer(kv, batcher, tuner=tuner, tune_every=16)
server.run(rounds=800, drift_every=250)
s = server.summary()
print("== tiered serving summary ==")
for k, v in s.items():
    print(f"  {k:20s} {v}")
print(f"  HBM budget saving vs capacity: {s['fm_saving_vs_cap']*100:.1f}%")
