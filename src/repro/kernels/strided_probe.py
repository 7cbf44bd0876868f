"""The Tuna micro-benchmark as a Pallas TPU kernel.

On real tiered hardware this is the workload that populates the
performance database: strided page reads from two pools (the fast-tier and
slow-tier arrays of Section 3.2) with a controlled number of arithmetic
ops per loaded element (the AI knob). The page-id vectors are scalar
prefetch operands; each grid step streams one page through VMEM and runs
``ai_iters`` fused multiply-adds per element, accumulating a checksum so
nothing is dead-code eliminated. A page is a ``(rows, 128)`` tile stack,
one slot of the pools' untiled leading axis (a 4 KiB f32 page is
``(8, 128)``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _probe_kernel(fast_idx_ref, slow_idx_ref, fast_ref, slow_ref, out_ref,
                  acc_scr, *, n_fast: int, ai_iters: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # first n_fast grid steps stream fast pages, the rest slow pages
    x = jnp.where(i < n_fast, fast_ref[...], slow_ref[...]).astype(jnp.float32)

    def body(_, acc):
        # tuna: ignore[TUNA004] deliberately FMA-shaped: the probe wants
        # peak-rate arithmetic per element, not a numeric contract
        return acc * 1.000001 + x

    acc = jax.lax.fori_loop(0, ai_iters, body, jnp.zeros_like(x))
    acc_scr[...] += jnp.sum(acc, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _emit():
        out_ref[...] = acc_scr[...]


@functools.partial(jax.jit, static_argnames=("ai_iters", "interpret"))
def strided_probe(fast_pool, slow_pool, fast_idx, slow_idx, ai_iters: int,
                  interpret: bool = False):
    """fast_pool/slow_pool (P, rows, 128) f32; fast_idx (nf,), slow_idx
    (ns,) int32 page ids. Returns the checksum (1, rows, 128)."""
    if fast_pool.ndim != 3:
        raise ValueError(
            f"pool shape {fast_pool.shape}: give pages as (rows, 128)"
        )
    nf, ns = fast_idx.shape[0], slow_idx.shape[0]
    blk = (1,) + fast_pool.shape[1:]
    kernel = functools.partial(_probe_kernel, n_fast=nf, ai_iters=ai_iters)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nf + ns,),
        in_specs=[
            pl.BlockSpec(
                blk,
                lambda i, fi, si: (fi[jnp.minimum(i, fi.shape[0] - 1)], 0, 0),
            ),
            pl.BlockSpec(
                blk,
                lambda i, fi, si: (
                    si[jnp.clip(i - fi.shape[0], 0, si.shape[0] - 1)],
                    0,
                    0,
                ),
            ),
        ],
        out_specs=pl.BlockSpec(blk, lambda i, fi, si: (0, 0, 0)),
        scratch_shapes=[pltpu.VMEM(blk, jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(blk, jnp.float32),
        interpret=interpret,
    )(fast_idx.astype(jnp.int32), slow_idx.astype(jnp.int32),
      fast_pool, slow_pool)
