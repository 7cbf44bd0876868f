"""Per-size demotion-ranking re-partition (segment scan) for the sweep.

The JAX sweep backend (:mod:`repro.sim.jax_engine`) ranks every page once
per interval by the shared demotion key — ``(effective heat, page id)``,
identical at every fast-memory size — and then each size must take the
first ``demand[s]`` pages of that ranking that sit in *its* fast tier. In
rank-order coordinates that is a segment scan per size row: a running
count of fast-tier entries compared against the size's reclaim demand.

A ``[n_sizes, rss]`` row block does not fit VMEM at real sizes (10.5 MB
of int32 per size row at 10 GiB of 4 KiB pages), so the kernel tiles the
rank axis: one sequential grid axis walks ``_TILE``-wide column tiles of
all size rows at once, carrying each row's running fast count in VMEM
scratch. Within a tile the inclusive prefix sum is one MXU product with an
upper-triangular ones matrix (0/1 inputs are exact in bf16, and f32
accumulation is exact far beyond ``_TILE``). The demands arrive as a
scalar-prefetch operand in SMEM.

The kernel is compiled on TPU and runs in Pallas interpret mode on the
CPU when ``REPRO_PALLAS=interpret``; :func:`_victim_partition_jnp` is the
plain reference (and the CPU path otherwise). Both are integer-exact, so
the choice never perturbs victim identities.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_TILE = 512  # rank positions per grid step (a multiple of the 128 lanes)
_SUBLANE = 8


def _victim_partition_kernel(d_ref, f_ref, tri_ref, o_ref, carry_ref, dvec_ref):
    """One column tile of every size row: select fast entries while the
    row's running fast count stays within its demand."""
    n_rows = f_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)
        rows = lax.broadcasted_iota(jnp.int32, dvec_ref.shape, 0)
        dvec = jnp.zeros(dvec_ref.shape, jnp.int32)
        for s in range(n_rows):
            dvec = jnp.where(rows == s, d_ref[s], dvec)
        dvec_ref[...] = dvec

    f = f_ref[...]  # [rows, _TILE] int32: fast-tier membership, rank order
    local = jnp.dot(
        f.astype(jnp.bfloat16), tri_ref[...], preferred_element_type=jnp.float32
    ).astype(jnp.int32)
    carry = carry_ref[...]
    cum = local + carry
    o_ref[...] = ((f > 0) & (cum <= dvec_ref[...])).astype(jnp.int32)
    carry_ref[...] = carry + jnp.sum(f, axis=1, keepdims=True, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _victim_partition_pallas(
    fast01: jax.Array, demand: jax.Array, interpret: bool = False
) -> jax.Array:
    n_sizes, r = fast01.shape
    n_pad = -(-n_sizes // _SUBLANE) * _SUBLANE
    r_pad = -(-r // _TILE) * _TILE
    f = jnp.pad(fast01.astype(jnp.int32), ((0, n_pad - n_sizes), (0, r_pad - r)))
    d = jnp.pad(demand.astype(jnp.int32), (0, n_pad - n_sizes))
    # tri[i, j] = 1 for i <= j: (f @ tri)[:, j] is the inclusive prefix sum
    tri = jnp.triu(jnp.ones((_TILE, _TILE), jnp.bfloat16))
    # Mosaic lowers 32-bit index maps only: trace the kernel with x64 off
    # even when the caller (the int64 sweep step) has it on
    with jax.enable_x64(False):
        out = _victim_partition_call(n_pad, r_pad, interpret)(d, f, tri)
    return out[:n_sizes, :r]


def _victim_partition_call(n_pad: int, r_pad: int, interpret: bool):
    return pl.pallas_call(
        _victim_partition_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r_pad // _TILE,),
            in_specs=[
                pl.BlockSpec((n_pad, _TILE), lambda j, d: (0, j)),
                pl.BlockSpec((_TILE, _TILE), lambda j, d: (0, 0)),
            ],
            out_specs=pl.BlockSpec((n_pad, _TILE), lambda j, d: (0, j)),
            scratch_shapes=[
                pltpu.VMEM((n_pad, 1), jnp.int32),  # running fast count
                pltpu.VMEM((n_pad, 1), jnp.int32),  # demand per row
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, r_pad), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )


def _victim_partition_jnp(fast01: jax.Array, demand: jax.Array) -> jax.Array:
    """Pure lax/jnp reference: bit-identical selection mask."""
    f = fast01.astype(jnp.int32)
    cum = jnp.cumsum(f, axis=1)
    sel = (f > 0) & (cum <= demand.astype(jnp.int32)[:, None])
    return sel.astype(jnp.int32)
