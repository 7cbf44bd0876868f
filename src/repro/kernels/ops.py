"""Public kernel API with platform dispatch.

:func:`pallas_mode` is the one place the kernel mode is decided. On a TPU
every Pallas kernel is compiled; a kernel that fails to lower raises. On
the CPU the jnp references in :mod:`repro.kernels.ref` run, unless
``REPRO_PALLAS=interpret`` asks for the kernels in Pallas interpret mode
(how the test suite covers kernel code without a chip). All model code
calls through this module, so the backend never leaks into model code.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref


def pallas_mode() -> str:
    """``"compiled"`` on TPU; on other backends ``"interpret"`` when
    ``REPRO_PALLAS=interpret``, else ``"off"`` (jnp references).

    Read per call, so tests can set the variable without re-importing.
    """
    env = os.environ.get("REPRO_PALLAS", "")
    if env not in ("", "interpret"):
        raise ValueError(f"REPRO_PALLAS={env!r}: the only setting is 'interpret'")
    if jax.default_backend() == "tpu":
        if env:
            raise ValueError(
                "REPRO_PALLAS=interpret is for the CPU backend; kernels are "
                "always compiled on TPU"
            )
        return "compiled"
    return "interpret" if env else "off"


# --------------------------------------------------------------- attention
def attention(q, k, v, causal: bool = True):
    """Training/prefill attention; flash kernel on TPU, reference on CPU."""
    mode = pallas_mode()
    if mode == "off":
        return _ref.attention(q, k, v, causal=causal)
    from repro.kernels import flash_attention as fa

    return fa.flash_attention(q, k, v, causal=causal, interpret=mode == "interpret")


def decode_attention(q, k_cache, v_cache, valid_len):
    return _ref.decode_attention(q, k_cache, v_cache, valid_len)


def cp_decode_attention(q, k_cache, v_cache, valid_len, mesh,
                        k_scale=None, v_scale=None,
                        batch_axis="data", seq_axis="model"):
    """Context-parallel decode attention (flash-decoding LSE merge).

    The KV cache is sequence-sharded over ``seq_axis``; each shard attends
    over its local chunk producing (m, l, o) partials, merged with the
    log-sum-exp rescale + psum across the axis. GSPMD cannot partition the
    softmax over a sharded contraction (it all-gathers K/V — 172 GB/step
    on the 72B decode cell); this shard_map formulation moves only the
    (B, H, hd) partials: ~3 MB/step (§Perf iteration 3).

    q (B,1,H,hd); k/v (B,S,KV,hd) [+ optional int8 scales (B,S,KV,1) —
    dequantization happens *inside* the shard so quantized bytes never
    cross links].
    """
    import math as _math

    from jax.sharding import PartitionSpec as P

    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    seq_n = sizes.get(seq_axis, 1)
    S_loc = S // seq_n
    if B % sizes.get(batch_axis, 1) != 0:
        batch_axis = None  # B=1 cells: replicate the batch dim

    quant = k_scale is not None

    def local(qb, kb, vb, ks, vs, vlen):
        i = jax.lax.axis_index(seq_axis)
        if quant:
            kb = kb.astype(jnp.bfloat16) * ks.astype(jnp.bfloat16)
            vb = vb.astype(jnp.bfloat16) * vs.astype(jnp.bfloat16)
        kx = jnp.repeat(kb, rep, axis=2) if rep > 1 else kb
        vx = jnp.repeat(vb, rep, axis=2) if rep > 1 else vb
        s = jnp.einsum(
            "bshd,bthd->bhst", qb.astype(jnp.float32), kx.astype(jnp.float32)
        ) / _math.sqrt(hd)
        tpos = i * S_loc + jnp.arange(S_loc)[None, None, None, :]
        s = jnp.where(tpos < vlen, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)  # (b,h,1,1)
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
        l_loc = jnp.sum(p, axis=-1, keepdims=True)  # (b,h,1,1)
        o = jnp.einsum("bhst,bthd->bshd", p, vx.astype(jnp.float32))
        # ---- merge across the sequence shards (log-sum-exp rescale)
        m_g = jax.lax.pmax(m, seq_axis)
        m_g_safe = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_g_safe), 0.0)
        l_g = jax.lax.psum(l_loc * corr, seq_axis)  # (b,h,1,1)
        corr_o = jnp.moveaxis(corr, 1, 2)  # (b,1,h,1)
        o_g = jax.lax.psum(o * corr_o, seq_axis)  # (b,1,h,d)
        l_o = jnp.maximum(jnp.moveaxis(l_g, 1, 2), 1e-30)  # (b,1,h,1)
        return (o_g / l_o).astype(qb.dtype)

    # replication checking off: the LSE merge's psum outputs are
    # per-shard-identical by construction, which the checker cannot see
    qspec = P(batch_axis, None, None, None)
    kvspec = P(batch_axis, seq_axis, None, None)
    if quant:
        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(qspec, kvspec, kvspec, kvspec, kvspec, P()),
            out_specs=qspec,
            check_vma=False,
        )
        return fn(q, k_cache, v_cache, k_scale, v_scale, valid_len)
    fn = jax.shard_map(
        lambda qb, kb, vb, vlen: local(qb, kb, vb, None, None, vlen),
        mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, P()),
        out_specs=qspec,
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, valid_len)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths):
    mode = pallas_mode()
    if mode == "off":
        return _ref.paged_decode_attention(q, k_pages, v_pages, page_table, lengths)
    from repro.kernels import paged_attention as pa

    return pa.paged_decode_attention(
        q, k_pages, v_pages, page_table, lengths, interpret=mode == "interpret"
    )


def wkv6(r, k, v, w, u):
    mode = pallas_mode()
    if mode == "off":
        return _ref.wkv6(r, k, v, w, u)
    from repro.kernels import rwkv6_chunk as rk

    return rk.wkv6_chunked(r, k, v, w, u, interpret=mode == "interpret")


def migrate_pages(dst_pool, src_pool, dst_idx, src_idx):
    mode = pallas_mode()
    if mode == "off":
        return _ref.migrate_pages(dst_pool, src_pool, dst_idx, src_idx)
    from repro.kernels import page_migrate as pm

    return pm.migrate_pages(
        dst_pool, src_pool, dst_idx, src_idx, interpret=mode == "interpret"
    )


def strided_probe(fast_arr, slow_arr, fast_idx, slow_idx, ai_iters: int):
    mode = pallas_mode()
    if mode == "off":
        return _ref.strided_probe(fast_arr, slow_arr, fast_idx, slow_idx, ai_iters)
    from repro.kernels import strided_probe as sp

    return sp.strided_probe(
        fast_arr, slow_arr, fast_idx, slow_idx, ai_iters,
        interpret=mode == "interpret",
    )
