"""Smoke run of Tuna's main path on one TPU chip, checked bit for bit.

Drives ``repro.sim.api.run`` on ``Scenario(engine="jax")`` — the steps of
``examples/quickstart.py`` — at 10 GiB of RSS (2,621,440 pages of 4 KiB)
over the perf database's 46-size fast-memory vector:

  a. a profiling run that harvests config vectors (fm 0.9);
  b. the offline perf-database build over a few harvested configs;
  c. an untuned sweep of the big trace over all 46 sizes;
  d. TPP vs TPP+Tuna at a 5% loss target, using the database from (b).

Then it repeats (a), (b), (d), and (c) at three sizes spanning the vector,
on the numpy sweep in the same process, and requires every result to be
identical bit for bit. Each phase prints its wall seconds, its XLA
compiles, the backends used, whether the compiled commit step holds the
Pallas kernel, and the device's peak memory. The last line is a JSON
object with ``"ok": true`` and the device, printed only when every check
passed. The script refuses to run anywhere but on a TPU.

Run from the repository root: ``python chip_smoke.py``.
"""

from __future__ import annotations

import json
import logging
import re
import sys
import time
from dataclasses import fields, is_dataclass
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
RSS_PAGES = 2_621_440  # 10 GiB of 4 KiB pages
N_DB_CONFIGS = 4
TRACE_SEED = 23


class _CompileLog(logging.Handler):
    """Collects ``jit(name)`` compile seconds from JAX's compile log."""

    PATTERN = re.compile(r"Finished XLA compilation of jit\((\w+)\) in ([\d.]+) sec")

    def __init__(self) -> None:
        super().__init__(logging.WARNING)
        self.events: list[tuple[str, float]] = []

    def emit(self, record: logging.LogRecord) -> None:
        m = self.PATTERN.search(record.getMessage())
        if m:
            self.events.append((m.group(1), float(m.group(2))))


def _first_diff(a, b):
    """``None`` when ``a`` and ``b`` are identical bit for bit, else where
    they first differ."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            return f"{a.dtype}{a.shape} vs {b.dtype}{b.shape}"
        bad = np.flatnonzero(a.reshape(-1) != b.reshape(-1))
        if bad.size:
            i = bad[0]
            return f"interval {i}: {a.reshape(-1)[i]!r} vs {b.reshape(-1)[i]!r}"
        return None
    if isinstance(a, (list, tuple)):
        if len(a) != len(b):
            return f"length {len(a)} vs {len(b)}"
        for i, (x, y) in enumerate(zip(a, b)):
            d = _first_diff(x, y)
            if d:
                return f"[{i}] {d}"
        return None
    if isinstance(a, dict):
        if a.keys() != b.keys():
            return f"keys {sorted(a)} vs {sorted(b)}"
        for k in a:
            d = _first_diff(a[k], b[k])
            if d:
                return f"{k}: {d}"
        return None
    if is_dataclass(a):
        if type(a) is not type(b):
            return f"{type(a).__name__} vs {type(b).__name__}"
        for f in fields(a):
            d = _first_diff(getattr(a, f.name), getattr(b, f.name))
            if d:
                return f"{f.name}: {d}"
        return None
    return None if a == b else f"{a!r} vs {b!r}"


def _compare_records(phase, jax_rs, np_rs, fracs=None):
    """Every numpy run against the jax run of the same cell."""
    for rn in np_rs.runs:
        if fracs is not None and rn.fm_frac not in fracs:
            continue
        rj = jax_rs.record(
            scenario=rn.scenario, policy=rn.policy, fm_frac=rn.fm_frac
        )
        for name in ("stats", "interval_times", "configs", "fm_sizes"):
            d = _first_diff(getattr(rj.result, name), getattr(rn.result, name))
            if d:
                return f"{phase}: size {rn.fm_frac} policy {rn.policy} {name} {d}"
        for name in ("decisions", "watermark_log"):
            d = _first_diff(getattr(rj, name), getattr(rn, name))
            if d:
                return f"{phase}: size {rn.fm_frac} policy {rn.policy} {name} {d}"
    return None


def _compare_db(jax_db, np_db):
    for i, (rj, rn) in enumerate(zip(jax_db.records, np_db.records)):
        for name in ("config", "fm_fracs", "times"):
            d = _first_diff(getattr(rj, name), getattr(rn, name))
            if d:
                return f"b_database: record {i} {name} {d}"
    if len(jax_db.records) != len(np_db.records):
        return "b_database: record count differs"
    return None


def main() -> int:
    import jax

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}
    print(f"device: {json.dumps(device)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; refusing to run elsewhere", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache(ROOT)}", flush=True)

    import jax.numpy as jnp

    from repro.core.tuner import build_database
    from repro.kernels.ops import pallas_mode
    from repro.sim import jax_engine
    from repro.sim.api import Experiment, PolicySpec, Scenario, TunerSpec, run
    from repro.sim.workloads import thrash_trace

    log = _CompileLog()
    logging.getLogger("jax").addHandler(log)
    jax.config.update("jax_log_compiles", True)
    fm_vec = tuple(float(f) for f in np.round(np.arange(1.0, 0.099, -0.02), 3))
    oracle_fracs = (fm_vec[0], fm_vec[len(fm_vec) // 2], fm_vec[-1])
    print(f"fm-size vector: {len(fm_vec)} sizes {fm_vec[0]}..{fm_vec[-1]}; "
          f"oracle sizes {oracle_fracs}", flush=True)

    def has_kernel(n_sizes: int, num_pages: int, p_pad: int) -> bool:
        """Whether the compiled commit step at these shapes holds the
        Pallas kernel (a Mosaic ``tpu_custom_call``)."""
        S = jax.ShapeDtypeStruct
        i32 = jnp.int32
        with jax.enable_x64(True):
            step = jax_engine._build_commit_step(pallas_mode())
            hlo = step.lower(
                S((n_sizes, num_pages), jnp.int8), S((num_pages,), i32),
                S((num_pages,), i32), S((num_pages,), i32),
                S((num_pages,), i32), S((n_sizes, p_pad), jnp.bool_),
                S((p_pad,), i32), S((8, n_sizes), jnp.int64),
            ).compile().as_text()
        return "tpu_custom_call" in hlo

    def phase(name, fn, shape=None):
        n0 = len(log.events)
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        ev = log.events[n0:]
        steps = [(n, s) for n, s in ev if n in ("schedule_step", "commit_step")]
        line = {
            "phase": name,
            "wall_s": wall,
            "xla_compiles": len(ev),
            "xla_compile_s": sum(s for _, s in ev),
            "step_compiles": len(steps),
            "step_compile_s": sum(s for _, s in steps),
        }
        rs = out[0] if isinstance(out, tuple) else out
        if hasattr(rs, "backends"):
            line["backends"] = list(rs.backends)
        if shape is not None:
            line["kernel_in_commit_step"] = has_kernel(*shape)
        line["peak_bytes_in_use"] = dev.memory_stats().get("peak_bytes_in_use")
        print(json.dumps(line), flush=True)
        return out

    t0 = time.perf_counter()
    trace = thrash_trace(rss_pages=RSS_PAGES, seed=TRACE_SEED)
    sizes = [ia.pages.size for ia in trace]
    print(f"trace: {trace.name} rss_pages={trace.rss_pages} intervals={len(trace)} "
          f"pages/interval {min(sizes)}..{max(sizes)} "
          f"generated in {time.perf_counter() - t0:.1f}s", flush=True)
    big_pad = jax_engine._bucket(max(sizes))

    def profile(engine):
        return run(Experiment(
            name="profile",
            scenarios=[Scenario(trace=trace, engine=engine)],
            fm_fracs=(0.9,),
            collect_configs=True,
        ))

    def sweep(engine, fracs):
        return run(Experiment(
            name="sweep", scenarios=[Scenario(trace=trace, engine=engine)],
            fm_fracs=fracs,
        ))

    def tuned(engine, db):
        return run(Experiment(
            name="tpp_vs_tuna",
            scenarios=[Scenario(trace=trace, engine=engine)],
            fm_fracs=(1.0,),
            policies=[
                PolicySpec(label="tpp"),
                PolicySpec(label="tpp+tuna",
                           tuner=TunerSpec(target_loss=0.05, tune_every=5,
                                           max_step_frac=0.05)),
            ],
        ), db=db)

    # ---- device phases
    prof = phase("a_profile", lambda: profile("jax"), (1, RSS_PAGES, big_pad))
    cvs = prof.record().result.configs
    pick = np.linspace(1, len(cvs) - 1, N_DB_CONFIGS).astype(int)
    configs = [cvs[i] for i in pick]
    print(f"cuts: database built over {len(configs)} of {len(cvs)} harvested "
          f"configs (intervals {pick.tolist()}), micro-benchmarks scaled to "
          "20,000 pages (build_database default); oracle (c) at "
          f"{len(oracle_fracs)} of {len(fm_vec)} sizes", flush=True)
    db = phase("b_database", lambda: build_database(configs, engine="jax"))
    full = phase("c_sweep46", lambda: sweep("jax", fm_vec),
                 (len(fm_vec), RSS_PAGES, big_pad))
    tun = phase("d_tpp_vs_tuna", lambda: tuned("jax", db), (1, RSS_PAGES, big_pad))
    base = tun.result(policy="tpp")
    trec = tun.record(policy="tpp+tuna")
    tres = trec.result
    print("simulated (cost model, not a device metric): "
          f"tpp {base.total_time:.6f}s, tpp+tuna {tres.total_time:.6f}s, "
          f"loss {(tres.total_time - base.total_time) / base.total_time:.4%} "
          f"vs 5% target, mean fast-memory saving "
          f"{1 - tres.fm_sizes.mean() / trace.rss_pages:.4%}, "
          f"{len(trec.decisions)} tuner decisions, "
          f"{len(trec.watermark_log)} watermark moves", flush=True)

    # ---- numpy oracle, same process
    checks = []
    prof_np = phase("a_profile_numpy", lambda: profile("numpy"))
    checks.append(_compare_records("a_profile", prof, prof_np))
    db_np = phase("b_database_numpy", lambda: build_database(configs, engine="numpy"))
    checks.append(_compare_db(db, db_np))
    tun_np = phase("d_tpp_vs_tuna_numpy", lambda: tuned("numpy", db_np))
    checks.append(_compare_records("d_tpp_vs_tuna", tun, tun_np))
    full_np = phase("c_sweep_numpy", lambda: sweep("numpy", oracle_fracs))
    checks.append(_compare_records("c_sweep46", full, full_np, set(oracle_fracs)))
    for c in checks:
        if c:
            print(f"MISMATCH {c}", flush=True)
            return 1
    print(f"oracle: {len(checks)} checks bit-exact against the numpy sweep "
          "(stats, interval_times, configs, fm_sizes, tuner decisions, "
          "watermark logs, perf-database records)", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
