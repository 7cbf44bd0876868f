"""Run one benchmark cell once on this machine's accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell is an entry of ``BENCHMARK.json``'s
``workloads``; its configuration (``bench/configs/<config>.json``) names
the driver (``bench/drivers/<driver>.py``) that sets it up, runs the
measured window and compares what the window produced with the plain
reference; its traffic mix is ``bench/traffic/<traffic>.json``; each
per-layer metric is read by ``bench/metrics/<metric>.py``. A run:

1. refuses to go on (exit code 2, no result) unless JAX finds a TPU with
   as many chips as the cell asks for, and finds the device in
   ``bench/peaks.json``;
2. sets up: inputs from ``--seed``, and every step shape the window uses
   compiled or loaded from the persistent cache in ``<checkout>/.jax_cache``
   (or ``JAX_COMPILATION_CACHE_DIR``); ``setup_s`` runs from the start of
   the process to here;
3. measures for ``--seconds`` (to the next interval or record boundary);
   with ``--trace 1`` under the profiler, with the host spans of
   ``bench/spans.py`` installed;
4. reads the device's peak memory, frees the program's state, and runs the
   comparison that decides ``correct``.

Earlier lines name the device, the compiles in the window and the work
done; the compared numbers with their limits are the last lines on
standard error; the last line on standard output is the result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


class NoDevice(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


class CompileLog(logging.Handler):
    """Counts new executables (compiled or loaded from the persistent
    cache) from JAX's compile log."""

    def __init__(self) -> None:
        super().__init__(logging.WARNING)
        self.executables = 0
        self.cache_hits = 0
        self.names: list = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Finished XLA compilation of"):
            self.executables += 1
            self.names.append(msg.split(" ")[4])
        elif msg.startswith("Persistent compilation cache hit"):
            self.cache_hits += 1


def require_device(chips: int) -> list:
    """The chips the cell runs on; raises :class:`NoDevice` elsewhere."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU found (JAX platform {devs[0].platform!r}); "
                       "the benchmark runs on the accelerator only")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def enable_cache() -> str:
    """The persistent compile cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program size."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache

    path = enable_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def reader(metric: str):
    """``bench/metrics/<metric>.py``, loaded by file (names hold dots)."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, section: str, cell: str) -> list:
    return [m for m in spec[section] if cell in m.get("workloads", [cell])]


def peak_bytes(devs) -> int:
    """Peak device memory of the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)


def failed_run(device: dict, peak: int, where: str) -> int:
    """The result of a run in which the program raised: not correct, no
    metrics."""
    device["memory_peak_bytes"] = peak
    print(f"run: the program raised in {where}", flush=True)
    print("compared program_errors 1 limit 0", file=sys.stderr, flush=True)
    print(json.dumps({
        "correct": False, "attempted": 0, "failed": 1, "metrics": {}, "device": device,
        "compared": {"program_errors": {"value": 1, "limit": 0}},
    }), flush=True)
    return 0


def parse(argv) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; cells: {sorted(cells)}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    from bench import generate

    cfg = generate.load("configs", cell["config"])
    traffic = generate.load("traffic", cell["traffic"])
    try:
        devs = require_device(int(cell["chips"]))
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    peaks = load_peaks(devs[0].device_kind)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"device: {json.dumps(device)}", flush=True)

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    print(f"compile cache: {enable_cache()}", flush=True)
    log = CompileLog()
    jax_logger = logging.getLogger("jax")
    jax_logger.addHandler(log)
    jax.config.update("jax_log_compiles", True)
    try:
        return measure(args, spec, cell, cfg, traffic, devs, device, peaks, log)
    finally:
        jax.config.update("jax_log_compiles", False)
        jax_logger.removeHandler(log)


def measure(args, spec, cell, cfg, traffic, devs, device, peaks, log) -> int:
    """Set-up, window, comparison and the result lines of one run."""
    import jax

    from bench import trace_reduce
    from bench.spans import Spans

    driver = importlib.import_module(f"bench.drivers.{cfg['driver']}")
    run = driver.Cell(cfg, traffic, args.seed)
    try:
        run.setup()
    except Exception:  # the program failed in set-up: not correct
        traceback.print_exc()
        return failed_run(device, peak_bytes(devs), "set-up")
    setup_s = time.perf_counter() - T0
    exe0, hits0 = log.executables, log.cache_hits

    spans = Spans(run.spans)
    tracedir = None
    if args.trace:
        tracedir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        spans.install()
        jax.profiler.start_trace(tracedir, create_perfetto_trace=True, profiler_options=opts)
    win = None
    try:
        if args.trace:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                win = run.window(args.seconds)
        else:
            win = run.window(args.seconds)
    except Exception:  # the program failed in the window: not correct
        traceback.print_exc()
    finally:
        if args.trace:
            jax.profiler.stop_trace()
            spans.remove()
    new_exe = log.executables - exe0
    window_names = log.names[exe0:]
    peak = peak_bytes(devs)

    if win is None:
        return failed_run(device, peak, "the window")

    reduced = None
    if args.trace:
        names = sorted({s for _, _, s in run.spans})
        reduced = trace_reduce.reduce(trace_reduce.load(tracedir), spans=names)
        shutil.rmtree(tracedir, ignore_errors=True)

    info = {
        "setup_s": setup_s,
        "setup_executables": exe0,
        "setup_cache_hits": hits0,
        "window_executables": new_exe,
        "window_executable_names": window_names,
        "window_s": run.elapsed,
        "peak_hbm_bytes": peak,
        **run.info,
    }
    if args.trace:
        info["span_calls"] = dict(spans.calls)
        info["span_seconds"] = dict(spans.seconds)
        info["resolve_victims_calls"] = spans.calls.get("resolve_victims", 0)
    print(f"run: {json.dumps(info, default=float)}", flush=True)

    run.release()
    gc.collect()
    t_check = time.perf_counter()
    compared = run.check()
    print(f"check: {json.dumps(run.info, default=float)} in "
          f"{time.perf_counter() - t_check:.3f}s", flush=True)
    correct = all(
        not (isinstance(v, float) and math.isnan(v)) and v <= lim
        for v, lim in compared.values()
    )

    metrics = {}
    if not args.trace:
        values = dict(win["metrics"], setup_s=setup_s)
        for m in cell_metrics(spec, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = SimpleNamespace(trace=reduced, spans=spans, window=win, peaks=peaks,
                              cfg=cfg, traffic=traffic)
        for m in cell_metrics(spec, "per_layer", cell["name"]):
            v = reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_us * 1e-6, window_s=reduced.window_us * 1e-6)
    device["memory_peak_bytes"] = peak

    result = {
        "correct": bool(correct),
        "attempted": int(win["attempted"]),
        "failed": int(run.failed),
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        result["breakdown"] = reduced.breakdown()
    result["compared"] = {k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()}
    for k, (v, lim) in compared.items():
        print(f"compared {k} {v!r} limit {lim!r}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
