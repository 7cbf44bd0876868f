"""Batched page migration (the tier-migration DMA) as a Pallas kernel.

Copies ``src_pool[src_idx[i]] → dst_pool[dst_idx[i]]`` for a batch of page
moves. Both pools stay where they live (``memory_space=ANY``); each grid
step issues one page-sized DMA straight from the source slot to the
destination slot, so no page passes through VMEM and the page size is not
bounded by it. The index vectors are scalar-prefetch operands, and the
destination pool is donated via input/output aliasing, so untouched pages
are never copied — the descriptor-ring DMA a real HBM⇄host migrator
issues, as one kernel launch per migration batch.

A page is indexed along the pool's leading axis, which the TPU does not
tile, so any page shape of rank 2 or more works (``(rows, 128)`` for a
flat page, ``(page_size, kv_heads, head_dim)`` for attention pages). A
rank-1 page would be a slice of a tiled axis, which the DMA engine cannot
address per page: such pools are refused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _migrate_kernel(dst_idx_ref, src_idx_ref, dst_ref, src_ref, out_ref, sem):
    # dst_ref is only present for the aliasing contract — never read
    i = pl.program_id(0)
    copy = pltpu.make_async_copy(
        src_ref.at[src_idx_ref[i]], out_ref.at[dst_idx_ref[i]], sem
    )
    copy.start()
    copy.wait()


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def migrate_pages(dst_pool, src_pool, dst_idx, src_idx, interpret: bool = False):
    """dst_pool (Pd, *page_shape); src_pool (Ps, *page_shape), with
    ``len(page_shape) >= 2``; dst_idx/src_idx (n,) int32. Returns the
    updated dst_pool."""
    if dst_pool.ndim < 3:
        raise ValueError(
            f"pool shape {dst_pool.shape}: pages must have rank >= 2 "
            "(view a flat page as (rows, 128))"
        )
    n = dst_idx.shape[0]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _migrate_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[any_spec, any_spec],
            out_specs=any_spec,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct(dst_pool.shape, dst_pool.dtype),
        input_output_aliases={2: 0},  # dst_pool (arg index after prefetch) → out
        interpret=interpret,
    )(dst_idx.astype(jnp.int32), src_idx.astype(jnp.int32), dst_pool, src_pool)
