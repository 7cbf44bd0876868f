"""Discrete-interval tiered-memory performance simulator.

This container has no tiered hardware (no Optane, no TPU HBM/host split), so
execution time is produced by a calibrated cost model
(:mod:`repro.sim.costmodel`) driven by the *real* tiering runtime state: the
engine (:mod:`repro.sim.engine`) pushes genuine page-access traces (from the
workload implementations or the micro-benchmark generator) through the page
pool + policy, and charges time per interval for bandwidth, latency,
migration, and reclaim stalls.

Everything above the cost model — pools, policies, watermarks, telemetry,
the Tuna tuner — is production code that would run unchanged with a real
DMA/latency backend.
"""

from repro.sim.costmodel import HardwareProfile, OPTANE_LIKE, TPU_V5E_TIER
from repro.sim.engine import run_trace, SimResult
from repro.sim.api import (
    Experiment,
    PolicySpec,
    RunRecord,
    RunSet,
    Scenario,
    TunerSpec,
    run,
)

__all__ = [
    "HardwareProfile",
    "OPTANE_LIKE",
    "TPU_V5E_TIER",
    "run_trace",
    "SimResult",
    "Experiment",
    "PolicySpec",
    "RunRecord",
    "RunSet",
    "Scenario",
    "TunerSpec",
    "run",
]
