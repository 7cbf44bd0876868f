"""Driver of a sweep cell: one trace swept over a vector of fast-memory sizes.

The window drives the program's own entry point,
``repro.sim.api.run(Experiment(scenarios=[Scenario(trace, engine="jax")],
fm_fracs=...))``, from the trace's allocation interval with every tier
empty, and stops at the first interval boundary after ``--seconds``: the
trace it is given stops yielding intervals once the deadline has passed.
The count of simulated intervals is the program's own (intervals with a
recorded time); the sweep ends on the device-to-host pull of the final
tier state. If the trace runs out first, another sweep of it starts, warm.

``correct`` compares, at sizes drawn from the seed (one from each stratum
of the size vector, so that no contiguous half of the sizes goes
unchecked), every counter, every interval time and the final tier of
every page against the plain reference over the intervals the window
simulated: for the window's first sweep and, where the trace ran out and
the window swept it again, for its last one too.

The final tier of each size is not in the entry point's result: the
driver reads it from the private ``repro.sim.jax_engine._sweep_run_jax``,
whose second return value is the list of slice pools, and stops with a
message naming that symbol if it is gone or returns something else.
"""

from __future__ import annotations

import time

import numpy as np

from bench import generate
from bench.reference import tiering as ref
from repro.core.trace import Trace

ENGINE = "repro.sim.jax_engine"
SWEEP = "_sweep_run_jax"  # private: returns (times, pools, ...)
# host spans of the traced run: (module, attribute, span)
SPANS = (
    (ENGINE, "_resolve_step_victims", "resolve_victims"),
    (ENGINE, "GlobalDemoteRank", "demote_rank_host"),
    (ENGINE, "_tie_groups", "demote_rank_host"),
    (ENGINE, "_hot_sorted", "hot_sorted"),
    (ENGINE, "_fold_heat", "fold_heat"),
    (ENGINE, "interval_time", "cost_model"),
)


class DeadlineTrace(Trace):
    """A trace that stops yielding intervals once ``deadline`` has passed.

    Keeps ``len()`` and every field of the trace it wraps. Each iteration
    pass records the host time of each interval it hands out, so the
    harness can tell an engine that simulates as it iterates from one
    that drains the iterator first.
    """

    def __init__(self, base: Trace, deadline: float) -> None:
        super().__init__(name=base.name, rss_pages=base.rss_pages,
                         intervals=base.intervals, num_threads=base.num_threads,
                         slow_pages=base.slow_pages)
        self.deadline = deadline
        self.passes: list = []

    def __iter__(self):
        stamps: list = []
        self.passes.append(stamps)
        for ia in self.intervals:
            if time.perf_counter() >= self.deadline:
                return
            stamps.append(time.perf_counter())
            yield ia


class Cell:
    """One sweep cell: set-up, the measured window and the comparison."""

    def __init__(self, cfg: dict, traffic: dict, seed: int) -> None:
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.fracs = generate.fm_fracs(traffic)
        self.hw = dict(cfg["hw"])
        self.spans = SPANS
        self.info: dict = {}

    # ----------------------------------------------------------- program
    def _sweep(self, trace):
        """One run of the program's entry point; returns the run set and
        the slice pools the device sweep ended with."""
        from repro.sim import jax_engine
        from repro.sim.api import Experiment, PolicySpec, Scenario, run
        from repro.sim.costmodel import HardwareProfile

        captured = []
        inner = getattr(jax_engine, SWEEP, None)
        if inner is None:
            raise RuntimeError(f"the benchmark reads each size's final tier from "
                               f"{ENGINE}.{SWEEP}, which is gone")

        def capture(*args, **kwargs):
            out = inner(*args, **kwargs)
            pools = out[1] if isinstance(out, tuple) and len(out) > 1 else None
            if not isinstance(pools, list) or not all(hasattr(p, "tier") for p in pools):
                raise RuntimeError(f"{ENGINE}.{SWEEP} no longer returns the slice "
                                   "pools second; the benchmark reads the final tiers there")
            captured.append(pools)
            return out

        jax_engine._sweep_run_jax = capture
        try:
            rs = run(Experiment(
                name="bench_sweep",
                scenarios=[Scenario(trace=trace, engine=self.cfg["engine"],
                                    hw=HardwareProfile(**self.hw))],
                fm_fracs=self.fracs,
                policies=[PolicySpec(kind=self.cfg["policy"], hot_thr=self.cfg["hot_thr"])],
            ))
        finally:
            jax_engine._sweep_run_jax = inner
        (pools,) = captured
        return rs, pools

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        t0 = time.perf_counter()
        self.trace = trace_for(self.cfg, self.traffic, self.seed)
        self.info["trace_generation_s"] = time.perf_counter() - t0
        self.info["trace_intervals"] = len(self.trace)
        # warm-up: the allocation interval and the first update intervals,
        # which hold every step shape the window uses
        n = 1 + int(self.traffic["warmup_intervals"])
        warm = Trace(name=self.trace.name, rss_pages=self.trace.rss_pages,
                     intervals=self.trace.intervals[:n], num_threads=self.trace.num_threads)
        self._sweep(warm)

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        deadline = t0 + seconds
        self.sweeps = []
        while True:
            tr = DeadlineTrace(self.trace, deadline)
            rs, pools = self._sweep(tr)
            t_end = time.perf_counter()
            n = self._simulated(rs)
            sim_pass = tr.passes[-1] if tr.passes else []
            if n != len(sim_pass):
                raise RuntimeError(
                    f"the engine simulated {n} intervals but drew {len(sim_pass)} "
                    "from the trace: it does not simulate as it iterates, so "
                    "the window cannot be bounded")
            if n >= 3:
                gap = float(np.median(np.diff(sim_pass)))
                if t_end - sim_pass[-1] > 3 * gap + 2.0:
                    raise RuntimeError(
                        "the engine drew every interval before simulating "
                        "them: the window cannot be bounded")
            self.sweeps.append((rs, pools, n))
            if t_end >= deadline:
                break
        self.elapsed = t_end - t0
        intervals = sum(n for _, _, n in self.sweeps)
        self.info["sweeps"] = len(self.sweeps)
        self.info["intervals_simulated"] = intervals
        self.info["sizes"] = len(self.fracs)
        work = intervals * len(self.fracs)
        return {
            "attempted": work,
            "metrics": {"sweep_size_intervals_per_s": work / self.elapsed},
            "work": {"intervals": intervals, "n_sizes": len(self.fracs),
                     "rss_pages": int(self.trace.rss_pages)},
        }

    def _simulated(self, rs) -> int:
        """Intervals with a recorded time (the comparison checks that every
        size has the same)."""
        return max(int(np.count_nonzero(r.result.interval_times > 0)) for r in rs.runs)

    def release(self) -> None:
        """Keep only what the comparison reads (host arrays)."""
        kept = []
        for rs, pools, n in self.sweeps:
            kept.append((rs, [np.asarray(p.tier) for p in pools], n))
        self.sweeps = kept

    # -------------------------------------------------------------- check
    def compared_sizes(self) -> list:
        """One size per stratum of the vector, drawn from the seed; the
        traffic's ``compare_always`` sizes fill their own strata."""
        rng = generate.rng_for(self.seed, 2)
        strata = np.array_split(np.arange(len(self.fracs)), int(self.traffic["compare_sizes"]))
        always = [self.fracs.index(f) for f in self.traffic.get("compare_always", [])]
        out = []
        for st in strata:
            fixed = [i for i in always if i in st]
            out.extend(fixed if fixed else [int(rng.choice(st))])
        return sorted(out)

    def compared_sweeps(self) -> list:
        """The window's first sweep, and its last where there are more."""
        return sorted({0, len(self.sweeps) - 1})

    def program_outputs(self, idx: list, sweep: int = 0) -> tuple[dict, int]:
        """What one sweep of the window produced at the sizes ``idx``:
        counters, interval times and final tiers."""
        rs, tiers, n = self.sweeps[sweep]
        runs = [rs.record(fm_frac=self.fracs[i]).result for i in idx]
        return {
            "stats": [r.stats for r in runs],
            "times": [r.interval_times for r in runs],
            "tiers": [tiers[i] for i in idx],
        }, n

    def reference(self, idx: list, n: int, dtype=np.float64) -> dict:
        return ref.simulate(
            self.trace, [self.fracs[i] for i in idx], ref.Hardware(**self.hw),
            self.cfg["hot_thr"], self.cfg["heat_halflife_intervals"], dtype=dtype,
            n_intervals=n,
        )

    def check(self) -> dict:
        """Compared numbers, each ``(value, limit)``."""
        idx = self.compared_sizes()
        compared, self.failed = {}, 0
        want = {}
        for k in self.compared_sweeps():
            got, n = self.program_outputs(idx, k)
            if n not in want:
                want[n] = self.reference(idx, n)
            c, f = compare(got, want[n], n)
            compared = {name: (max(v, compared.get(name, (v, 0))[0]), lim)
                        for name, (v, lim) in c.items()}
            self.failed += f
        self.info["compared_sizes"] = [self.fracs[i] for i in idx]
        self.info["compared_intervals"] = [self.sweeps[k][2] for k in self.compared_sweeps()]
        return compared


def trace_for(cfg: dict, traffic: dict, seed: int):
    """The cell's trace, from the generator its configuration names."""
    make = getattr(generate, f"{cfg['generator']}_trace")
    return make(cfg, int(traffic["trace_intervals"]), seed)


def compare(got: dict, want: dict, n: int) -> tuple[dict, int]:
    """Every counter, interval time and final tier of each compared size,
    exactly; returns the compared numbers with their limits and the
    number of sizes that differ. Times past the ``n`` simulated intervals
    must be unrecorded (0)."""
    stats_bad = tier_bad = failed = 0
    gap = 0.0
    for j in range(len(want["times"])):
        s_bad = sum(got["stats"][j].get(k) != v for k, v in want["stats"][j].items())
        p_bad = int(np.count_nonzero(np.asarray(got["tiers"][j]) != want["tiers"][j]))
        t_got, t_want = np.asarray(got["times"][j], dtype=np.float64), want["times"][j]
        g = float(np.max(np.abs(t_got[:n] - t_want) / t_want)) if n else 0.0
        if t_got.size < n or np.any(t_got[n:] != 0):
            g = max(g, 1.0)
        stats_bad += s_bad
        tier_bad += p_bad
        gap = max(gap, g)
        failed += bool(s_bad or p_bad or g)
    return {
        "counter_mismatches": (stats_bad, 0),
        "tier_mismatch_pages": (tier_bad, 0),
        "interval_time_rel_gap": (gap, 0.0),
    }, failed
