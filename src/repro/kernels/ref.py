"""Pure-jnp oracles for every kernel (the correctness references).

These are also the CPU execution path: ``ops.py`` dispatches to the Pallas
kernels on TPU (or in interpret mode under REPRO_PALLAS=interpret) and to
these references otherwise.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def attention(q, k, v, causal: bool = True, logits_soft_cap: float | None = None):
    """Multi-head attention with GQA broadcast.

    q (B,S,H,hd); k,v (B,T,KV,hd); returns (B,S,H,hd). Softmax in f32.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    rep = H // KV
    kx = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vx = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    scores = jnp.einsum(
        "bshd,bthd->bhst", q.astype(jnp.float32), kx.astype(jnp.float32)
    ) / math.sqrt(hd)
    if logits_soft_cap:
        scores = logits_soft_cap * jnp.tanh(scores / logits_soft_cap)
    if causal:
        qpos = jnp.arange(S)[:, None] + (T - S)  # right-aligned queries
        kpos = jnp.arange(T)[None, :]
        scores = jnp.where(kpos[None, None] <= qpos[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", w, vx.astype(jnp.float32))
    return o.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, valid_len):
    """One-token decode: q (B,1,H,hd) against cache (B,T,KV,hd); cache
    positions >= valid_len are masked. valid_len may be a traced scalar."""
    B, _, H, hd = q.shape
    T, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    kx = jnp.repeat(k_cache, rep, axis=2) if rep > 1 else k_cache
    vx = jnp.repeat(v_cache, rep, axis=2) if rep > 1 else v_cache
    scores = jnp.einsum(
        "bshd,bthd->bhst", q.astype(jnp.float32), kx.astype(jnp.float32)
    ) / math.sqrt(hd)
    kpos = jnp.arange(T)[None, None, None, :]
    scores = jnp.where(kpos < valid_len, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", w, vx.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths):
    """Decode attention over a paged KV cache.

    q (B,H,hd); k_pages/v_pages (P, page_size, KV, hd) — the global page
    pools; page_table (B, pages_per_seq) int32 page ids (-1 = unused);
    lengths (B,) valid token count per sequence. Returns (B,H,hd).
    """
    B, H, hd = q.shape
    P, page_size, KV, _ = k_pages.shape
    ppseq = page_table.shape[1]
    rep = H // KV
    # gather each sequence's pages: (B, ppseq, page_size, KV, hd)
    safe_tbl = jnp.maximum(page_table, 0)
    k = k_pages[safe_tbl]
    v = v_pages[safe_tbl]
    k = k.reshape(B, ppseq * page_size, KV, hd)
    v = v.reshape(B, ppseq * page_size, KV, hd)
    kx = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vx = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    scores = jnp.einsum(
        "bhd,bthd->bht", q.astype(jnp.float32), kx.astype(jnp.float32)
    ) / math.sqrt(hd)
    tpos = jnp.arange(ppseq * page_size)[None, None, :]
    valid = (tpos < lengths[:, None, None]) & (
        jnp.repeat(page_table >= 0, page_size, axis=1)[:, None, :]
    )
    scores = jnp.where(valid, scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    o = jnp.einsum("bht,bthd->bhd", w, vx.astype(jnp.float32))
    return o.astype(q.dtype)


def wkv6(r, k, v, w, u):
    """RWKV6 (Finch) WKV with data-dependent decay — sequential reference.

    r,k,v,w (B,S,H,hd); u (H,hd). State S_t = diag(w_t) S_{t-1} + k_t v_t^T;
    o_t = r_t (S_{t-1} + diag(u) k_t v_t^T). Returns (o (B,S,H,hd),
    final state (B,H,hd,hd)), computed in f32.
    """
    B, S, H, hd = r.shape
    rf, kf, vf, wf = (t.astype(jnp.float32) for t in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(state, t):
        rt, kt, vt, wt = rf[:, t], kf[:, t], vf[:, t], wf[:, t]
        at = jnp.einsum("bhk,bhv->bhkv", kt, vt)
        ot = jnp.einsum("bhk,bhkv->bhv", rt, state + uf[None, :, :, None] * at)
        new = wt[..., None] * state + at
        return new, ot

    init = jnp.zeros((B, H, hd, hd), jnp.float32)
    final, os = jax.lax.scan(step, init, jnp.arange(S))
    o = jnp.moveaxis(os, 0, 1)  # (B,S,H,hd)
    return o.astype(r.dtype), final


def migrate_pages(dst_pool, src_pool, dst_idx, src_idx):
    """Copy pages src_pool[src_idx] → dst_pool[dst_idx] (batched gather/
    scatter — the DMA migration reference)."""
    return dst_pool.at[dst_idx].set(src_pool[src_idx])


def strided_probe(fast_pool, slow_pool, fast_idx, slow_idx, ai_iters: int):
    """Tuna micro-benchmark reference: strided page loads from the two tier
    pools + ``ai_iters`` fused multiply-adds per loaded element; returns the
    ``(1, *page_shape)`` checksum accumulated over pages."""
    x = jnp.concatenate([fast_pool[fast_idx], slow_pool[slow_idx]], axis=0)
    x = x.astype(jnp.float32)

    def body(i, acc):
        return acc * 1.000001 + x

    acc = jax.lax.fori_loop(0, ai_iters, body, jnp.zeros_like(x))
    return acc.sum(axis=0, keepdims=True)
