"""End-to-end trainer: data → pjit step → checkpoint → fault tolerance.

This is the driver ``examples/train_lm.py`` uses; on CPU it runs reduced
configs on a 1×1 mesh with the exact code paths (shardings, watchdog,
retries, async checkpointing, resume) that the production meshes lower.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax

from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLMDataset
from repro.launch.train import make_train_fns, width_scaled_lr
from repro.models.config import ModelConfig
from repro.runtime.fault_tolerance import StepWatchdog, StragglerMonitor, retry_step


@dataclass
class TrainReport:
    steps: int
    final_loss: float
    losses: list
    resumed_from: int | None
    step_times: list


def train(
    cfg: ModelConfig,
    mesh,
    steps: int = 20,
    global_batch: int = 8,
    seq_len: int = 64,
    ckpt_dir=None,
    ckpt_every: int = 10,
    step_timeout_s: float = 600.0,
    remat: str = "none",
    seed: int = 0,
    inject_failure_at: int | None = None,
    lr: float | None = None,
    warmup: int | None = None,
    total_steps: int = 10_000,
) -> TrainReport:
    # The production schedule (3e-4 peak, 200-step warmup) never leaves
    # early warmup on the reduced `.scaled()` configs: a 25-step smoke run
    # tops out at lr ~4e-5, so losses only reflect per-batch noise. The
    # defaults transfer the peak lr across width and shorten warmup for
    # smoke widths. Both stay functions of the *global* step only (never
    # of this call's ``steps``), so an interrupted run resumed from a
    # checkpoint replays the exact same schedule (bit-exact resume).
    if lr is None:
        lr = width_scaled_lr(cfg.d_model)
    if warmup is None:
        warmup = 3 if cfg.d_model <= 256 else 200
    fns = make_train_fns(
        cfg, mesh, lr=lr, warmup=warmup, total_steps=total_steps, remat=remat
    )
    ds = SyntheticLMDataset(cfg.vocab_size, seq_len, global_batch, seed=seed)
    step_jit = jax.jit(
        fns["step"],
        out_shardings=(
            fns["param_shardings"],
            fns["opt_shardings"],
            fns["metric_shardings"],
        ),
    )

    mgr = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
    start_step = 0
    resumed_from = None
    params = opt_state = None
    if mgr is not None:
        restored, manifest = mgr.restore_latest(
            {"params": fns["param_shapes"], "opt": fns["opt_shapes"]},
            {"params": fns["param_shardings"], "opt": fns["opt_shardings"]},
        )
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            start_step = manifest["step"]
            resumed_from = start_step
    if params is None:
        params, opt_state = fns["init"](jax.random.key(seed))
        params = jax.device_put(params, fns["param_shardings"])
        opt_state = jax.device_put(opt_state, fns["opt_shardings"])

    monitor = StragglerMonitor()
    losses, step_times = [], []
    injected = {"done": False}

    for step in range(start_step, steps):
        batch = ds.batch_at(step)
        batch = {k: jax.device_put(v) for k, v in batch.items()}

        def one_step():
            if (
                inject_failure_at is not None
                and step == inject_failure_at
                and not injected["done"]
            ):
                injected["done"] = True
                raise RuntimeError("injected transient step failure")
            return step_jit(params, opt_state, batch)

        t0 = time.time()
        with StepWatchdog(step_timeout_s):
            params, opt_state, metrics = retry_step(one_step, retries=2)
        dt = time.time() - t0
        step_times.append(dt)
        monitor.observe({"host0": dt})
        losses.append(float(metrics["loss"]))
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state})
    if mgr is not None:
        mgr.wait()
    return TrainReport(
        steps=steps,
        final_loss=losses[-1] if losses else float("nan"),
        losses=losses,
        resumed_from=resumed_from,
        step_times=step_times,
    )
