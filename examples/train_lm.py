"""End-to-end training driver: train a (reduced) LM for a few hundred
steps on CPU with the full production code path — pjit shardings,
watchdog, transient-failure retry, async checkpointing, and resume.

Run:  PYTHONPATH=src python examples/train_lm.py [--arch qwen3-1.7b]
          [--steps 200] [--scale full|smoke]
"""

import argparse
import tempfile
from pathlib import Path

import jax

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.trainer import train
from repro.runtime.compile_cache import enable_compile_cache

enable_compile_cache(Path(__file__).resolve().parents[1])

ap = argparse.ArgumentParser()
ap.add_argument("--arch", default="qwen3-1.7b")
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--seq", type=int, default=128)
ap.add_argument("--d-model", type=int, default=256, help="reduced width")
ap.add_argument("--layers", type=int, default=4)
args = ap.parse_args()

cfg = get_config(args.arch).scaled(
    d_model=args.d_model,
    num_heads=max(4, args.d_model // 64),
    head_dim=64,
    d_ff=args.d_model * 4,
    num_layers=args.layers,
    vocab_size=4096,
)
mesh = make_host_mesh()
with tempfile.TemporaryDirectory() as ckpt:
    print(f"training {cfg.name} ({args.steps} steps) with checkpoints in {ckpt}")
    rep = train(
        cfg, mesh, steps=args.steps, global_batch=args.batch,
        seq_len=args.seq, ckpt_dir=ckpt, ckpt_every=50,
        inject_failure_at=min(7, args.steps - 1),  # exercise the retry path
    )
    print(f"loss: {rep.losses[0]:.3f} -> {rep.final_loss:.3f} "
          f"({rep.steps} steps, retry exercised, resumed_from={rep.resumed_from})")
    assert rep.final_loss < rep.losses[0], "loss must go down"
    print("ok.")
