"""The device sweep's rank step (:func:`repro.sim.jax_engine._rank_step` and
:func:`~repro.sim.jax_engine._hot_ranks`) against the host ranking it
replaces off the CPU: ``GlobalDemoteRank``, ``_tie_groups`` and the
commit step's hot-set arrays as the host builds them, element for
element. The steps are plain jnp, so they run here on the CPU backend."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.sim import jax_engine  # noqa: E402
from repro.tiering.page_pool import GlobalDemoteRank  # noqa: E402

N_PAGES = 5_003  # not a power of two


def effective_heats(seed: int, n: int = N_PAGES) -> np.ndarray:
    """Effective heats as the sweep forms them (decayed heat plus the
    interval's touches), with large tie groups, exact zeros, neighbours
    one ulp apart, subnormals and huge values."""
    rng = np.random.default_rng(seed)
    decay = 0.5 ** 0.5
    eff = rng.integers(0, 5, size=n) * decay ** rng.integers(0, 40, size=n)
    eff += rng.integers(0, 3, size=n)  # the touches: many exact ties
    eff[rng.random(n) < 0.3] = 0.0  # untouched, fully decayed pages
    pick = rng.permutation(n)
    a, b, c = pick[:200], pick[200:400], pick[400:440]
    eff[b] = np.nextafter(eff[a], np.inf)  # one ulp above another page
    eff[c[:20]] = np.nextafter(0.0, 1.0) * rng.integers(1, 4, size=20)  # subnormal
    eff[c[20:]] = 1e300 * rng.random(20)
    assert np.all(eff >= 0) and not np.isnan(eff).any()
    return eff


def host_ranking(eff: np.ndarray, hot: np.ndarray, p_pad: int):
    """The host path of the sweep, as ``_sweep_run_jax`` builds it."""
    rk = GlobalDemoteRank(eff)
    grp = jax_engine._tie_groups(rk)
    hot_rank = rk.rank[hot]
    hot_grp = jax_engine._pad(grp[hot_rank], p_pad, jax_engine._NO_GROUP, np.int32)
    hot_slot = np.full(eff.size, p_pad, dtype=np.int32)
    hot_slot[hot_rank] = np.arange(hot.size, dtype=np.int32)
    return rk.order, rk.rank, grp, hot_slot, hot_grp


@pytest.mark.parametrize("hot_frac", [0.0, 0.3, 1.0])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rank_step_equals_host_ranking(seed, hot_frac):
    eff = effective_heats(seed)
    rng = np.random.default_rng(seed + 100)
    hot = rng.permutation(eff.size)[: int(hot_frac * eff.size)]
    p_pad = jax_engine._bucket(hot.size)
    want = host_ranking(eff, hot, p_pad)
    assert want[2][-1] < eff.size - 1000  # large tie groups

    with jax.enable_x64(True):  # as in the sweep
        order, rank_inv, grp = jax_engine._rank_step(*jax_engine._heat_keys(eff))
        hot_p = jnp.asarray(jax_engine._pad(hot, p_pad, 0, np.int32))
        hot_slot, hot_grp = jax_engine._hot_ranks(rank_inv, grp, hot_p, np.int32(hot.size))
    got = (order, rank_inv, grp, hot_slot, hot_grp)
    for name, g, w in zip(("order", "rank_inv", "grp", "hot_slot", "hot_grp"), got, want):
        g = np.asarray(g)
        assert g.dtype == np.int32, name
        assert np.array_equal(g, w), name


def test_heat_keys_order_like_the_heat():
    """The (high, low) halves of the bits sort exactly like the doubles."""
    eff = effective_heats(3)
    hi, lo = jax_engine._heat_keys(eff)
    assert hi.dtype == lo.dtype == np.uint32
    assert np.array_equal(np.lexsort((lo, hi)), np.argsort(eff, kind="stable"))
