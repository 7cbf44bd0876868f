"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), with
shape/dtype sweeps and property-based invariants."""

import numpy as np
import pytest
import jax.numpy as jnp

pytest.importorskip("hypothesis", reason="hypothesis not installed (test extra)")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.page_migrate import migrate_pages
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.rwkv6_chunk import wkv6_chunked
from repro.kernels.strided_probe import strided_probe

RNG = np.random.default_rng(0)


def _tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 else dict(
        rtol=2e-4, atol=2e-4
    )


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize(
        "B,S,T,H,KV,hd,causal",
        [
            (1, 128, 128, 4, 2, 64, True),
            (2, 96, 96, 4, 4, 64, True),
            (1, 64, 192, 8, 2, 128, False),
            (1, 33, 65, 2, 1, 64, True),  # ragged (padding path)
        ],
    )
    def test_matches_ref(self, B, S, T, H, KV, hd, causal, dtype):
        q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
        k = jnp.asarray(RNG.normal(size=(B, T, KV, hd)), dtype)
        v = jnp.asarray(RNG.normal(size=(B, T, KV, hd)), dtype)
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                            interpret=True)
        r = ref.attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(r, np.float32), **_tol(dtype)
        )

    def test_block_shape_invariance(self):
        q = jnp.asarray(RNG.normal(size=(1, 128, 2, 64)), jnp.float32)
        k = jnp.asarray(RNG.normal(size=(1, 128, 2, 64)), jnp.float32)
        outs = [
            flash_attention(q, k, k, block_q=bq, block_k=bk, interpret=True)
            for bq, bk in [(32, 32), (64, 32), (32, 64), (128, 128)]
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(
                np.asarray(outs[0]), np.asarray(o), rtol=1e-5, atol=1e-5
            )


class TestPagedAttention:
    @pytest.mark.parametrize(
        "B,H,KV,hd,P,psize,ppseq",
        [(2, 8, 4, 64, 16, 16, 4), (3, 4, 4, 128, 8, 32, 2), (1, 16, 2, 64, 32, 8, 8)],
    )
    def test_matches_ref(self, B, H, KV, hd, P, psize, ppseq):
        q = jnp.asarray(RNG.normal(size=(B, H, hd)), jnp.float32)
        kp = jnp.asarray(RNG.normal(size=(P, psize, KV, hd)), jnp.float32)
        vp = jnp.asarray(RNG.normal(size=(P, psize, KV, hd)), jnp.float32)
        tbl = np.full((B, ppseq), -1, np.int32)
        lens = np.zeros(B, np.int32)
        for b in range(B):
            n = int(RNG.integers(1, ppseq + 1))
            tbl[b, :n] = RNG.choice(P, size=n, replace=False)
            lens[b] = RNG.integers((n - 1) * psize + 1, n * psize + 1)
        o = paged_decode_attention(q, kp, vp, jnp.asarray(tbl),
                                   jnp.asarray(lens), interpret=True)
        r = ref.paged_decode_attention(q, kp, vp, jnp.asarray(tbl),
                                       jnp.asarray(lens))
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)

    def test_page_permutation_invariance(self):
        """Shuffling which physical pages hold the data (with the table
        updated accordingly) must not change the output — the property that
        makes Tuna's page migration transparent to attention."""
        B, H, KV, hd, P, psize, ppseq = 2, 4, 4, 64, 12, 16, 3
        q = jnp.asarray(RNG.normal(size=(B, H, hd)), jnp.float32)
        kp = np.asarray(RNG.normal(size=(P, psize, KV, hd)), np.float32)
        vp = np.asarray(RNG.normal(size=(P, psize, KV, hd)), np.float32)
        tbl = np.array([[0, 1, 2], [3, 4, -1]], np.int32)
        lens = np.array([40, 20], np.int32)
        o1 = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                    jnp.asarray(tbl), jnp.asarray(lens),
                                    interpret=True)
        perm = RNG.permutation(P)
        inv = np.argsort(perm)
        kp2, vp2 = kp[inv], vp[inv]
        tbl2 = np.where(tbl >= 0, perm[np.maximum(tbl, 0)], -1).astype(np.int32)
        o2 = paged_decode_attention(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                    jnp.asarray(tbl2), jnp.asarray(lens),
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=1e-5, atol=1e-5)


class TestWKV6:
    @pytest.mark.parametrize("B,S,H,hd,C",
                             [(2, 64, 2, 32, 16), (1, 100, 4, 64, 32),
                              (2, 32, 2, 16, 32)])
    def test_matches_ref(self, B, S, H, hd, C):
        r = jnp.asarray(RNG.normal(size=(B, S, H, hd)) * 0.5, jnp.float32)
        k = jnp.asarray(RNG.normal(size=(B, S, H, hd)) * 0.5, jnp.float32)
        v = jnp.asarray(RNG.normal(size=(B, S, H, hd)) * 0.5, jnp.float32)
        w = jnp.asarray(np.exp(-np.exp(RNG.normal(size=(B, S, H, hd)) * 0.5 - 1)),
                        jnp.float32)
        u = jnp.asarray(RNG.normal(size=(H, hd)) * 0.3, jnp.float32)
        o, s = wkv6_chunked(r, k, v, w, u, chunk=C, interpret=True)
        ro, rs = ref.wkv6(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ro),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                                   rtol=3e-4, atol=3e-4)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000), chunk=st.sampled_from([8, 16, 32]))
    def test_chunk_size_invariance(self, seed, chunk):
        """The chunked form is exact: chunk size must not change results.

        Decay magnitudes follow the RWKV6 parameterization
        (w = exp(-exp(decay_base + ddlerp)) with decay_base ≈ -4): the
        kernel's cw-ratio factorization requires the cumulative decay
        within a chunk to stay above ~1e-30, which realistic decays satisfy
        for chunks ≤ 64 by a huge margin (documented kernel envelope)."""
        g = np.random.default_rng(seed)
        B, S, H, hd = 1, 48, 2, 16
        r = jnp.asarray(g.normal(size=(B, S, H, hd)) * 0.5, jnp.float32)
        w = jnp.asarray(
            np.exp(-np.exp(-4.0 + 0.8 * g.normal(size=(B, S, H, hd)))),
            jnp.float32,
        )
        u = jnp.asarray(g.normal(size=(H, hd)) * 0.3, jnp.float32)
        o1, s1 = wkv6_chunked(r, r, r, w, u, chunk=chunk, interpret=True)
        o2, s2 = wkv6_chunked(r, r, r, w, u, chunk=48, interpret=True)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   rtol=2e-4, atol=2e-4)


class TestPageMigrate:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_matches_ref(self, seed):
        g = np.random.default_rng(seed)
        Pd, Ps = int(g.integers(4, 12)), int(g.integers(4, 12))
        shape = (int(g.integers(2, 6)), int(g.integers(8, 24)))
        n = int(g.integers(1, min(Pd, Ps)))
        dst = jnp.asarray(g.normal(size=(Pd,) + shape), jnp.float32)
        src = jnp.asarray(g.normal(size=(Ps,) + shape), jnp.float32)
        di = jnp.asarray(g.choice(Pd, n, replace=False), jnp.int32)
        si = jnp.asarray(g.choice(Ps, n, replace=False), jnp.int32)
        r = ref.migrate_pages(dst, src, di, si)
        o = migrate_pages(dst, src, di, si, interpret=True)
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


class TestStridedProbe:
    @pytest.mark.parametrize("ai_iters", [0, 1, 7, 32])
    def test_matches_ref(self, ai_iters):
        fp = jnp.asarray(RNG.normal(size=(10, 8, 128)), jnp.float32)
        sp = jnp.asarray(RNG.normal(size=(12, 8, 128)), jnp.float32)
        fi = jnp.asarray([0, 3, 5, 9], jnp.int32)
        si = jnp.asarray([1, 2, 11], jnp.int32)
        r = ref.strided_probe(fp, sp, fi, si, ai_iters)
        o = strided_probe(fp, sp, fi, si, ai_iters, interpret=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=1e-5, atol=1e-5)

    def test_ai_knob_changes_flops_not_reads(self):
        """Arithmetic intensity knob is pure compute: output is a
        deterministic function; more iterations = more FMAs applied."""
        fp = jnp.ones((4, 8, 128), jnp.float32)
        sp = jnp.ones((4, 8, 128), jnp.float32)
        fi = jnp.asarray([0, 1], jnp.int32)
        si = jnp.asarray([2], jnp.int32)
        o1 = strided_probe(fp, sp, fi, si, 1, interpret=True)
        o2 = strided_probe(fp, sp, fi, si, 8, interpret=True)
        assert not np.allclose(np.asarray(o1), np.asarray(o2))
