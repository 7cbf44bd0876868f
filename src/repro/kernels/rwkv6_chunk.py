"""RWKV6 (Finch) WKV as a chunked linear-attention Pallas kernel.

The sequential recurrence S_t = diag(w_t) S_{t-1} + k_t v_tᵀ is O(S) steps;
on TPU that starves the MXU. The chunked form does parallel matmuls within
a chunk of C tokens and carries the (hd × hd) state across chunks:

  intra:  o_t += Σ_{s<t} (r_t ⊙ cw_t)·(k_s ⊘ cw_s) v_s  + (r_t ⊙ u ⊙ k_t) v_t
  inter:  o_t += (r_t ⊙ cw_t) S_chunk
  state:  S' = diag(cw_C) S + Σ_s (k_s ⊙ cw_C ⊘ cw_s) v_sᵀ

where cw is the inclusive cumulative decay within the chunk (f32; chunk
sizes are kept ≤ 64 so the cw ratios stay in range — decays are
exp(-exp(·)) ∈ (0,1)).

Grid: (B, H, S/C) with the chunk axis innermost (sequential), state in
VMEM scratch. This is the hardware-adaptation example from DESIGN.md §8:
the paper-adjacent GPU implementations use warp-level scans; the TPU-native
form is matmul-heavy chunking.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_out_ref, state_scr,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0, 0].astype(jnp.float32)  # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, hd)

    # inclusive cumulative decay (C, hd): Mosaic has no cumprod, so the
    # log-decays are prefix-summed by a lower-triangular ones product
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = (s_idx <= t_idx).astype(jnp.float32)
    logw = jnp.log(w)
    cw = jnp.exp(
        jax.lax.dot_general(
            lower, logw, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    )
    # decay from the chunk start to *before* token t: cw_t / w_t
    cw_in = cw / jnp.maximum(w, 1e-30)
    rq = r * cw_in  # query side carries decay from chunk start (exclusive)
    kk = k / jnp.maximum(cw, 1e-30)  # key side divides out its decay

    # ---- intra-chunk: strictly-lower-triangular attention + bonus diag
    A = jax.lax.dot_general(
        rq, kk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (C, C): A[t, s] = Σ_k r_t cw_in_t kk_s
    A = jnp.where(s_idx < t_idx, A, 0.0)
    o = jax.lax.dot_general(
        A, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # (C,1)
    o = o + diag * v  # tuna: ignore[TUNA004] float-tolerance kernel, no bit-exact contract

    # ---- inter-chunk: contribution of the carried state
    S = state_scr[...]  # (hd, hd)
    o = o + jax.lax.dot_general(
        rq, S, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # ---- state update: the chunk's total decay cw_C, as a row (1, hd)
    # for the key side and as a column (hd, 1) for the carried state
    # (static slices and a ones product: Mosaic has no dynamic slice or
    # lane-to-sublane reshape here)
    cwC = cw[chunk - 1 : chunk, :]
    k_scaled = kk * cwC  # k_s ⊙ cw_C / cw_s
    cwC_col = jnp.exp(
        jax.lax.dot_general(
            logw, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    )
    # tuna: ignore[TUNA004] decayed-state update: float-tolerance kernel,
    # no bit-exact-vs-numpy contract; FMA welcome
    state_scr[...] = cwC_col * S + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    o_ref[0, 0] = o.astype(o_ref.dtype)

    @pl.when(ci == pl.num_programs(2) - 1)
    def _emit_state():
        s_out_ref[0, 0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_chunked(r, k, v, w, u, chunk: int = DEFAULT_CHUNK,
                 interpret: bool = False):
    """r,k,v,w (B,S,H,hd); u (H,hd) → (o (B,S,H,hd), state (B,H,hd,hd))."""
    B, S, H, hd = r.shape
    C = min(chunk, S)
    Sp = -(-S // C) * C

    def prep(x, pad_value=0.0):
        xt = jnp.moveaxis(x, 2, 1)  # (B,H,S,hd)
        if Sp != S:
            xt = jnp.pad(xt, ((0, 0), (0, 0), (0, Sp - S), (0, 0)),
                         constant_values=pad_value)
        return xt

    rt, kt, vt = prep(r), prep(k), prep(v)
    wt = prep(w, pad_value=1.0)  # padded decay of 1 keeps the state intact
    kernel = functools.partial(_wkv_kernel, chunk=C)
    o, state = pl.pallas_call(
        kernel,
        grid=(B, H, Sp // C),
        in_specs=[
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            # u as (H, 1, hd): the block's last two dims are the array's
            pl.BlockSpec((1, 1, hd), lambda b, h, c: (h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rt, kt, vt, wt, u.reshape(H, 1, hd))
    return jnp.moveaxis(o[:, :, :S], 1, 2), state
