"""Accelerator-native sweep backend: the interval inner loop on JAX.

The numpy sweep (:func:`repro.sim.sweep._sweep_run`) is the equivalence
oracle; this module executes the *same* per-interval sequence over the
stacked ``[n_sizes, rss]`` tier array on the device, as two jitted steps
per interval:

* the **schedule step** — first-touch allocation, batched tier
  classification, the per-size promotion-candidate filter and the
  vectorized TPP decision batch (:func:`repro.tiering.page_pool.
  _bulk_schedule_batch` as a :func:`jax.lax.while_loop`);
* the **commit step** — per-size victim selection over the shared
  demotion ranking (the :mod:`repro.kernels.demote_rank` Pallas
  segment-scan kernel), interference detection, and the promote/demote
  commit. It runs only in intervals where some size migrates.

The size-independent work stays on the host, in the numpy sweep's own
code: the ``float64`` heat recurrence (:class:`~repro.tiering.page_pool.
LazyHeat`), the interval's hottest-first candidate order and the
admission test. A TPU has no native ``float64``, so the device never
holds a float.

The stable ``(effective heat, page id)`` demotion ranking, computed once
per demoting interval for every size, runs on the device as the **rank
step** (:func:`_rank_step`): the host splits each page's effective heat
into the two uint32 halves of its IEEE-754 bit pattern, and one integer
sort by ``(high, low, page id)`` gives the int32
permutation, its inverse and the *tie groups* (equal effective heat ⇔
equal group, ordered like the heat). Effective heat is non-negative and
never NaN, so its bits order exactly like its value: the ranking is
:class:`~repro.tiering.page_pool.GlobalDemoteRank`'s, bit for bit. On the
CPU backend the "device" is the host, where numpy's stable argsort is
faster than XLA:CPU's sort, so there the host builds the same arrays
(``GlobalDemoteRank`` and :func:`_tie_groups`) and ships them
(:func:`_rank_on_device` decides).

Exactness contract (pinned by ``tests/test_engine_equivalence.py``):

* integer counters, victim identities, ``ConfigVector``s, interval times
  and tuner decisions are **bit-exact** against the numpy sweep and the
  frozen ``ReferencePagePool`` lanes in every regime, including thrash;
* the run is chunked-loop-free (``policy.chunked_steps`` stays zero);
* on the device everything is integer: int8 tiers, int32 positions and
  tie groups, and int64 (``jax.enable_x64``) for access sums and the
  schedule's counters, which the TPU emulates exactly.

Thrash-regime victim resolution stays host-side by design: the commit step
detects interference (reclaim demand reaching into same-step promotions)
per size and commits a provisional fast-path state; interfering sizes are
then corrected through the *same* host resolver the numpy sweep uses
(:func:`repro.tiering.page_pool._resolve_step_victims` over the schedule's
replayed availability horizons) and a tiny fix-up scatter. Counters are
schedule-determined and identical either way, so only tier identity is
patched. The resolver reads its key order from the shared ranking: both
streams are keyed on integer ranks, and a size's winners come in key order
as the interval's hot set in rank order filtered by that size's winners (a
size with few winners sorts their ranks instead), so no size sorts heat.
Where the device ranked, an interval with an interfering size pulls the
permutation and the hot set's ranks once for this; other intervals pull
nothing of the ranking.

Eligibility (enforced here, routed by :mod:`repro.sim.api`): the policy
must advertise ``jax_batchable`` (TPP and the trace-pure admission
backend; thrash-guard's stateful host hooks are excluded), the run must be
fault-free, and every interval's page ids must be unique — duplicate ids
raise loudly instead of silently degrading to the chunked path.

The victim partition follows :func:`repro.kernels.ops.pallas_mode`: the
compiled kernel on TPU, the kernel in interpret mode on the CPU under
``REPRO_PALLAS=interpret``, and the jnp reference otherwise.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.demote_rank import (
    _victim_partition_jnp,
    _victim_partition_pallas,
)
from repro.kernels.ops import pallas_mode
from repro.runtime import tracing
from repro.sim.costmodel import absorb_cache, effective_mlp, interval_time
from repro.sim.sweep import _fold_heat, _hot_sorted
from repro.tiering.page_pool import (
    GlobalDemoteRank,
    LazyHeat,
    Tier,
    TieredPagePool,
    _resolve_step_victims,
)
from repro.tiering.policy import PolicyOutcome

_FAST = int(Tier.FAST)
_SLOW = int(Tier.SLOW)
_NO_GROUP = np.iinfo(np.int32).max  # tie group of "no winner"
# an interfering size sorts its winners' ranks itself while fewer than
# 1/_OWN_ORDER_CUT of the hot set win, and else filters the shared order
_OWN_ORDER_CUT = 3


def _bucket(n: int, floor: int = 128) -> int:
    """Pad length to a power of two (bounds jit recompiles per trace)."""
    return max(floor, 1 << max(0, int(n - 1).bit_length()))


def _pad(arr: np.ndarray, size: int, fill, dtype) -> np.ndarray:
    out = np.full(size, fill, dtype=dtype)
    out[: arr.size] = arr
    return out


def _schedule_loop(free, fastc, minf, lowf, highf, kswapd, n_cand):
    """:func:`repro.tiering.page_pool._bulk_schedule_batch` on device.

    The same integer vector recurrence, with the Python ``while`` replaced
    by :func:`jax.lax.while_loop`; arithmetic is int64 throughout, so the
    six outputs are bit-identical to the numpy batch schedule.
    """
    zeros = jnp.zeros_like(free)

    def cond(st):
        return jnp.any(st[8] > 0)

    def body(st):
        free, fastc, done, pm_de, pm_fail, direct_total, events, d_demand, active = st
        active_b = active > 0
        headroom = free - minf
        reclaim = active_b & (headroom <= 0)
        # run_reclaim(allow_direct=True): direct to min, kswapd to high
        dm = reclaim & (free < minf)
        n = jnp.maximum(jnp.where(dm, jnp.minimum(minf - free, fastc), 0), 0)
        d_demand = d_demand + n
        fastc = fastc - n
        free = free + n
        pm_de = pm_de + n
        direct_total = direct_total + n
        events = events + dm.astype(free.dtype)  # one event even when n == 0
        km = reclaim & (free < lowf)
        n = jnp.maximum(
            jnp.where(
                km, jnp.minimum(jnp.minimum(highf - free, kswapd), fastc), 0
            ),
            0,
        )
        d_demand = d_demand + n
        fastc = fastc - n
        free = free + n
        pm_de = pm_de + n
        headroom = free - minf
        fail = reclaim & (headroom <= 0)
        pm_fail = jnp.where(fail, n_cand - done, pm_fail)
        active_b = active_b & ~fail
        chunk = jnp.where(active_b, jnp.minimum(headroom, n_cand - done), 0)
        done = done + chunk
        free = free - chunk
        fastc = fastc + chunk
        active_b = active_b & (done < n_cand)
        return (
            free, fastc, done, pm_de, pm_fail, direct_total, events,
            d_demand, active_b.astype(free.dtype),
        )

    st = (
        free, fastc, zeros, zeros, zeros, zeros, zeros, zeros,
        (zeros < n_cand).astype(free.dtype),
    )
    free, fastc, done, pm_de, pm_fail, direct_total, events, d_demand, _ = (
        lax.while_loop(cond, body, st)
    )
    # final run_reclaim() — kswapd only
    km = free < lowf
    n = jnp.maximum(
        jnp.where(
            km, jnp.minimum(jnp.minimum(highf - free, kswapd), fastc), 0
        ),
        0,
    )
    d_demand = d_demand + n
    pm_de = pm_de + n
    return done, pm_de, pm_fail, direct_total, events, d_demand


@functools.lru_cache(maxsize=None)
def _build_schedule_step(hot_thr: int, promote_batch):
    """The allocation + classification + schedule step for one policy
    mode (``promote_batch=None`` = unbounded). Shapes are jit's to key."""

    def schedule_step(
        tier, new_rank, n_fast, pages_p, counts_p, rep_p, valid, hot_p,
        hot_valid, hot_ok, free, fastc, minf, lowf, highf, kswapd,
    ):
        # --- first-touch allocation: per size a prefix of the new pages
        # (access order) goes fast, the rest slow — n_fast is the host's
        # watermark-budget prefix length, new_rank a page's place in it
        alloc = jnp.where(new_rank[None, :] < n_fast[:, None], _FAST, _SLOW)
        tier = jnp.where(
            (new_rank >= 0)[None, :], alloc.astype(tier.dtype), tier
        )
        # --- batched tier classification of the touched pages: exact
        # integer sums (the numpy sweep's integer-valued float GEMM)
        fast_m = (jnp.take(tier, pages_p, axis=1) == _FAST) & valid[None, :]
        fast_w = fast_m & (rep_p < hot_thr)[None, :]

        def masked_sum(mask, vals):
            return jnp.sum(jnp.where(mask, vals[None, :], 0), axis=1)

        sums = jnp.stack(
            [
                masked_sum(fast_m, counts_p),
                masked_sum(fast_m, rep_p),
                jnp.sum(fast_w, axis=1, dtype=jnp.int64),
                masked_sum(fast_w, rep_p),
            ],
            axis=1,
        )
        # --- promotion candidates: each size's slow-tier subset of the
        # host's hottest-first order, filtered by the admission test
        slow_cand = (jnp.take(tier, hot_p, axis=1) == _SLOW) & hot_valid[None, :]
        admitted = slow_cand & hot_ok[None, :]
        rejected = jnp.sum(slow_cand, axis=1, dtype=jnp.int64) - jnp.sum(
            admitted, axis=1, dtype=jnp.int64
        )
        if promote_batch is not None:
            arank = jnp.cumsum(admitted.astype(jnp.int32), axis=1)
            admitted = admitted & (arank <= promote_batch)
        n_cand = jnp.sum(admitted, axis=1, dtype=jnp.int64)
        # --- the promote/reclaim schedule for every size at once
        pm_pr, pm_de, pm_fail, direct_total, events, d_demand = (
            _schedule_loop(free, fastc, minf, lowf, highf, kswapd, n_cand)
        )
        # --- winners: the first pm_pr admitted candidates per size
        wrank = jnp.cumsum(admitted.astype(jnp.int32), axis=1)
        win_mask = admitted & (wrank <= pm_pr[:, None])
        counters = jnp.stack(
            [pm_pr, pm_de, pm_fail, direct_total, events, d_demand,
             rejected, n_cand]
        )
        return tier, sums, counters, win_mask

    return jax.jit(schedule_step)


@functools.lru_cache(maxsize=None)
def _build_commit_step(mode: str):
    """The victim-selection + commit step for one Pallas mode."""

    def commit_step(
        tier, order, rank_inv, grp, hot_slot, win_mask, hot_grp, counters
    ):
        pm_pr, d_demand = counters[0], counters[5]
        # --- victims: first d_demand fast pages per size in the shared
        # (effective heat, page id) ranking — the segment-scan kernel
        ranked = jnp.take(tier, order, axis=1, mode="clip")
        fast01 = (ranked == _FAST).astype(jnp.int32)
        if mode == "off":
            vic_sel = _victim_partition_jnp(fast01, d_demand)
        else:
            vic_sel = _victim_partition_pallas(
                fast01, d_demand, interpret=mode == "interpret"
            )
        vsel = vic_sel > 0
        vcount = jnp.sum(vsel, axis=1, dtype=jnp.int64)
        pos = jnp.arange(order.shape[0], dtype=jnp.int32)
        last_pos = jnp.max(jnp.where(vsel, pos[None, :], -1), axis=1)
        last_grp = jnp.where(
            last_pos >= 0, jnp.take(grp, jnp.maximum(last_pos, 0)), -1
        )
        win_grp_min = jnp.min(
            jnp.where(win_mask, hot_grp[None, :], _NO_GROUP), axis=1
        )
        # interference: demand reaching into same-step promotions — the
        # exact _try_bulk_step precondition (ties count as interference)
        interf = (d_demand > 0) & (
            (vcount < d_demand) | ((pm_pr > 0) & (win_grp_min <= last_grp))
        )
        # --- provisional commit in rank order (exact for non-interfering
        # sizes; the host patches interfering rows' tier identity after)
        win_ranked = jnp.take(
            win_mask, hot_slot, axis=1, mode="fill", fill_value=False
        )
        ranked = jnp.where(vsel, jnp.int8(_SLOW), ranked)
        ranked = jnp.where(win_ranked, jnp.int8(_FAST), ranked)
        tier = jnp.take(ranked, rank_inv, axis=1, mode="clip")
        return tier, interf, vsel

    return jax.jit(commit_step)


@jax.jit
def _row(x, s):
    """One size row of a stacked device array (``s`` traced: one compile)."""
    return lax.dynamic_index_in_dim(x, s, axis=0, keepdims=False)


@jax.jit
def _fix_row(tier, row, fix):
    """Patch one interfering size's tier identity after host resolution.

    ``fix`` is a dense per-page int8 vector: 1 for walked victims the
    resolver did *not* demote (back to fast), 2 for same-step promotions
    it did (back to slow), 0 elsewhere. Dense keeps one executable per
    tier shape (a scatter of variable-length id lists compiles once per
    length bucket)."""
    old = lax.dynamic_index_in_dim(tier, row, axis=0, keepdims=False)
    new = jnp.where(fix == 1, jnp.int8(_FAST), old)
    new = jnp.where(fix == 2, jnp.int8(_SLOW), new)
    return lax.dynamic_update_index_in_dim(tier, new, row, axis=0)


def _rank_on_device() -> bool:
    """Whether the demotion ranking runs as :func:`_rank_step` on the
    device: everywhere but the CPU backend, where the "device" is the host
    and XLA:CPU's sort of 2.5M keys takes 1.86 s to numpy's 0.36 s. Read
    per sweep call, like :func:`repro.kernels.ops.pallas_mode`."""
    return jax.default_backend() != "cpu"


def _heat_keys(eff: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """The high and low uint32 halves of each effective heat's bits: for
    non-negative, non-NaN doubles, ``(high, low)`` orders like the value."""
    bits = eff.view(np.uint64)
    return (bits >> np.uint64(32)).astype(np.uint32), bits.astype(np.uint32)


@jax.jit
def _rank_step(hi, lo):
    """The shared demotion ranking on the device, from :func:`_heat_keys`.

    One integer sort by ``(hi, lo, page id)`` gives ``GlobalDemoteRank``'s
    stable ``order``; ``rank_inv`` is its inverse and ``grp``
    :func:`_tie_groups`, all int32. One executable per page count.
    """
    pos = lax.iota(jnp.int32, hi.shape[0])
    hi_s, lo_s, order = lax.sort((hi, lo, pos), num_keys=3)
    rank_inv = jnp.zeros_like(pos).at[order].set(pos, unique_indices=True)
    new = (hi_s[1:] != hi_s[:-1]) | (lo_s[1:] != lo_s[:-1])
    grp = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(new, dtype=jnp.int32)])
    return order, rank_inv, grp


@jax.jit
def _hot_ranks(rank_inv, grp, hot_p, n_hot):
    """The commit step's view of the hot set in a device-ranked interval.

    ``hot_p`` is the hottest-first candidate order padded past ``n_hot``.
    Returns ``hot_slot`` (by rank position: the candidate's slot in
    ``hot_p``, else the padded length) and ``hot_grp`` (each slot's tie
    group, ``_NO_GROUP`` past ``n_hot``), as the host path builds them."""
    p_pad = hot_p.shape[0]
    slot = lax.iota(jnp.int32, p_pad)
    valid = slot < n_hot
    hot_rank = jnp.take(rank_inv, hot_p)
    n = rank_inv.shape[0]
    # padding slots scatter to distinct out-of-range positions, dropped
    at = jnp.where(valid, hot_rank, n + slot)
    hot_slot = jnp.full(n, p_pad, jnp.int32).at[at].set(
        slot, mode="drop", unique_indices=True
    )
    hot_grp = jnp.where(valid, jnp.take(grp, hot_rank), _NO_GROUP)
    return hot_slot, hot_grp


def _require_jax_runnable(trace, policy, faults) -> None:
    """The eligibility contract (mirrored by the api.py planner checks)."""
    if faults is not None or policy.fault_injector is not None:
        raise ValueError(
            "engine='jax' does not support fault injection; run fault "
            "scenarios on the numpy sweep"
        )
    if not getattr(policy, "jax_batchable", False):
        raise ValueError(
            f"policy kind '{policy.kind}' is not jax_batchable; the JAX "
            "sweep backend only replicates TPP-contract policies whose "
            "decision semantics are device-portable (see "
            "repro.tiering.policy capability flags)"
        )
    for i, ia in enumerate(trace):
        if ia.pages.size and np.unique(ia.pages).size != ia.pages.size:
            raise ValueError(
                f"engine='jax' requires unique page ids per interval; "
                f"interval {i} of trace '{trace.name}' repeats ids"
            )


def _tie_groups(g: GlobalDemoteRank) -> np.ndarray:
    """Number the tie classes of effective heat in rank order (int32), so
    that comparing groups on the device is comparing heats, ties
    included."""
    eff_sorted = g.eff[g.order]
    grp = np.zeros(g.order.size, dtype=np.int32)
    np.cumsum(eff_sorted[1:] != eff_sorted[:-1], out=grp[1:])
    return grp


@tracing.traced("sweep")
def _sweep_run_jax(
    trace,
    fm_fracs: np.ndarray,
    policy,
    hw,
    hw_capacity_pages: int | None,
    seed: int,
    collect_configs: bool,
    tuners: list | None = None,
    tune_everys: list | None = None,
    kswapd_batch: int | None = None,
    faults=None,
):
    """Drop-in device-backed replacement for ``sweep._sweep_run``.

    Same signature, same ``(times, pools, configs_out, fm_sizes, costs)``
    return, bit-exact results; see the module docstring for the contract.
    The layers are :mod:`repro.runtime.tracing` spans (``sweep.*``,
    ``interval.*``, ``fixup.*``) with counters of intervals, touched and
    hot pages, migrating sizes, commit steps, interfering sizes (and which
    key order each took), device dispatches and host<->device bytes; they
    time and count, and feed nothing back.
    """
    with tracing.span("sweep.eligibility"):
        _require_jax_runnable(trace, policy, faults)
    n_sizes = int(np.asarray(fm_fracs).size)
    num_pages = int(trace.rss_pages)
    cap = int(hw_capacity_pages or trace.rss_pages)
    hot_thr = policy.hot_thr
    admit_margin = getattr(policy, "admit_margin", None)
    schedule_step = _build_schedule_step(hot_thr, policy.promote_batch)
    commit_step = _build_commit_step(pallas_mode())
    rank_on_device = _rank_on_device()

    with jax.enable_x64(True):
        with tracing.span("sweep.setup"):
            # host-side slice pools: watermarks, stats, rss — the control
            # plane the profilers/tuners read — plus the shared heat and
            # touch counters. Tier rows live on device for the run and are
            # imported back at the end.
            tier_b = np.full((n_sizes, num_pages), int(Tier.UNALLOCATED), np.int8)
            halflife_decay = 0.5 ** (1.0 / 2.0)
            heat = LazyHeat(num_pages, halflife_decay)
            interval_acc = np.zeros(num_pages, dtype=np.int64)
            interval_touch = np.zeros(num_pages, dtype=np.int64)
            pools = []
            for s in range(n_sizes):
                pool = TieredPagePool._shared_slice(
                    tier_row=tier_b[s],
                    heat=heat,
                    interval_acc=interval_acc,
                    interval_touch=interval_touch,
                    hw_capacity=cap,
                    page_bytes=hw.page_bytes,
                    kswapd_batch=kswapd_batch,
                    seed=seed,
                )
                pool.set_fm_size(int(round(float(fm_fracs[s]) * cap)))
                if trace.slow_pages is not None:
                    pool.place(trace.slow_pages, Tier.SLOW)
                pools.append(pool)
            tuned = tuners is not None
            if tuned:
                for pool, tuner in zip(pools, tuners):
                    if tuner is not None:
                        tuner.bind_pool(pool, cap)

            tier_stack = TieredPagePool._export_tier_stack(pools)
            tracing.count("xfer.h2d_bytes", tier_stack.nbytes)
            dev_tier = jnp.asarray(tier_stack)
            allocated = tier_b[0] != int(Tier.UNALLOCATED)
            # device constants, made on first use: "no new page" allocation
            # ranks, and the identity ranking of intervals that only promote
            no_new = identity = None

            n_intervals = len(trace)
            times = np.zeros((n_sizes, n_intervals), dtype=np.float64)
            profilers = configs_out = None
            if collect_configs:
                from repro.core.telemetry import IntervalProfiler

                profilers = [
                    IntervalProfiler(hot_thr=hot_thr, num_threads=trace.num_threads)
                    for _ in range(n_sizes)
                ]
                configs_out = [[] for _ in range(n_sizes)]
            costs = [[] for _ in range(n_sizes)]
            fm_sizes = t_now = None
            if tuned:
                fm_sizes = np.zeros((n_sizes, n_intervals), dtype=np.int64)
                t_now = [0.0] * n_sizes

        for i, ia in enumerate(trace):
            with tracing.span("sweep.interval", interval=i):
                tracing.count("sweep.intervals")
                with tracing.span("interval.prep"):
                    pages = np.asarray(ia.pages, dtype=np.int64)
                    counts_mem = absorb_cache(ia.counts, hw.llc_pages)
                    mlp_eff = effective_mlp(counts_mem, hw.mlp, trace.num_threads)
                    touches = np.asarray(ia.touches, dtype=np.int64)
                    rep = np.minimum(touches, hot_thr)
                    # --- host allocation bookkeeping (pre-step, per size):
                    # the new-page set and rss delta are size-independent,
                    # the fast-prefix length is each size's watermark budget
                    new_mask = ~allocated[pages] if pages.size else np.zeros(0, bool)
                    n_new = int(np.count_nonzero(new_mask))
                    n_fast_arr = np.zeros(n_sizes, dtype=np.int32)
                    if n_new:
                        for s, pool in enumerate(pools):
                            budget = max(0, pool.fast_free - pool.watermarks.low_free)
                            nf = min(budget, n_new)
                            n_fast_arr[s] = nf
                            pool.stats.alloc_fast += int(nf)
                            pool.stats.alloc_slow += int(n_new - nf)
                            pool._rss_pages += n_new
                            pool._fast_used += int(nf)
                        allocated[pages[new_mask]] = True
                        new_rank = np.full(num_pages, -1, dtype=np.int32)
                        new_rank[pages[new_mask]] = np.arange(n_new, dtype=np.int32)
                    else:
                        if no_new is None:
                            no_new = jnp.full(num_pages, -1, dtype=jnp.int32)
                        new_rank = no_new
                    # --- size-independent host work: the interval's
                    # touches, hottest-first candidates and their admission
                    interval_touch[pages] += touches  # ids are unique per interval
                    hot = _hot_sorted(pages, touches, hot_thr)
                    tracing.count("interval.touched_pages", pages.size)
                    tracing.count("interval.hot_pages", hot.size)
                    if admit_margin is None:
                        hot_ok = np.ones(hot.size, dtype=bool)
                    else:
                        # AdmissionTPPPolicy._admit: trace-pure, size-independent
                        eff_hot = heat.lookahead(hot) + interval_touch[hot]
                        hot_ok = eff_hot >= float(admit_margin) * hot_thr
                    # --- schedule inputs: post-allocation free/fast state
                    free_a = np.empty(n_sizes, dtype=np.int64)
                    fastc_a = np.empty(n_sizes, dtype=np.int64)
                    minf_a = np.empty(n_sizes, dtype=np.int64)
                    lowf_a = np.empty(n_sizes, dtype=np.int64)
                    highf_a = np.empty(n_sizes, dtype=np.int64)
                    kswapd_a = np.empty(n_sizes, dtype=np.int64)
                    for s, pool in enumerate(pools):
                        wm = pool.watermarks
                        free_a[s] = pool.fast_free
                        fastc_a[s] = pool.fast_used
                        minf_a[s] = wm.min_free
                        lowf_a[s] = wm.low_free
                        highf_a[s] = wm.high_free
                        kswapd_a[s] = pool.kswapd_batch
                    p_pad = _bucket(pages.size)
                    # on the device once: the rank step reads it too
                    hot_p = jnp.asarray(_pad(hot, p_pad, 0, np.int32))
                    tracing.count("xfer.h2d_bytes", hot_p.nbytes)
                    step_in = (
                        new_rank,
                        n_fast_arr,
                        _pad(pages, p_pad, 0, np.int32),
                        _pad(counts_mem, p_pad, 0, np.int64),
                        _pad(rep, p_pad, 0, np.int64),
                        _pad(np.ones(pages.size, bool), p_pad, False, bool),
                        hot_p,
                        _pad(np.ones(hot.size, bool), p_pad, False, bool),
                        _pad(hot_ok, p_pad, False, bool),
                        free_a, fastc_a, minf_a, lowf_a, highf_a, kswapd_a,
                    )
                if tracing.active():  # new_rank may be the device constant
                    tracing.count("xfer.h2d_bytes",
                                  sum(a.nbytes for a in step_in if isinstance(a, np.ndarray)))
                tracing.count("device.dispatches")
                with tracing.span("interval.schedule"):
                    tier_alloc, sums_d, counters_d, win_mask_d = schedule_step(
                        dev_tier, *step_in
                    )
                with tracing.span("interval.pull"):
                    counters = np.asarray(counters_d)
                if tracing.active():
                    tracing.count("xfer.d2h_bytes", counters.nbytes)
                (pm_pr, pm_de, pm_fail, direct_total, events, d_demand,
                 rejected, n_cand) = counters
                tracing.count("sweep.migrating_sizes", np.count_nonzero(pm_pr + pm_de))
                dev_tier = tier_alloc
                if pm_pr.any() or d_demand.any():
                    with tracing.span("interval.rank"):
                        # --- the shared demotion ranking, only when some
                        # size demotes (promote-only intervals commit in
                        # page order)
                        if d_demand.any() and rank_on_device:
                            tracing.count("rank.device")
                            tracing.count("device.dispatches", 2)
                            hi, lo = _heat_keys(heat.lookahead_dense() + interval_touch)
                            tracing.count("xfer.h2d_bytes", hi.nbytes + lo.nbytes)
                            order, rank_inv, grp = _rank_step(hi, lo)
                            hot_slot, hot_grp = _hot_ranks(
                                rank_inv, grp, hot_p, np.int32(hot.size)
                            )
                        else:
                            if d_demand.any():
                                tracing.count("rank.host")
                                rk = GlobalDemoteRank(heat.lookahead_dense() + interval_touch)
                                grp_np = _tie_groups(rk)
                                order = jnp.asarray(rk.order.astype(np.int32))
                                rank_inv = jnp.asarray(rk.rank.astype(np.int32))
                                grp = jnp.asarray(grp_np)
                                if tracing.active():
                                    tracing.count("xfer.h2d_bytes",
                                                  order.nbytes + rank_inv.nbytes + grp.nbytes)
                                hot_rank = rk.rank[hot]
                                hot_grp = _pad(grp_np[hot_rank], p_pad, _NO_GROUP, np.int32)
                            else:
                                if identity is None:
                                    identity = jnp.arange(num_pages, dtype=jnp.int32)
                                order = rank_inv = grp = identity
                                hot_rank = hot
                                hot_grp = np.full(p_pad, _NO_GROUP, dtype=np.int32)
                            hot_slot = np.full(num_pages, p_pad, dtype=np.int32)
                            hot_slot[hot_rank] = np.arange(hot.size, dtype=np.int32)
                            if tracing.active():
                                tracing.count("xfer.h2d_bytes", hot_slot.nbytes + hot_grp.nbytes)
                    tracing.count("sweep.commit_intervals")
                    tracing.count("device.dispatches")
                    with tracing.span("interval.commit"):
                        dev_tier, interf_d, vsel_d = commit_step(
                            tier_alloc, order, rank_inv, grp, hot_slot, win_mask_d,
                            hot_grp, counters_d,
                        )
                    with tracing.span("interval.pull"):
                        interf = np.asarray(interf_d)
                    if tracing.active():
                        tracing.count("xfer.d2h_bytes", interf.nbytes)
                    # --- thrash regime: resolve interfering sizes' victim
                    # identities with the numpy sweep's own host resolver
                    # and patch the device tier (counters are
                    # schedule-determined and already exact)
                    with tracing.span("interval.fixup"):
                        interfering = np.flatnonzero(interf)
                        if interfering.size:
                            # what the resolver reads of the ranking: the
                            # permutation, and the hot set's ranks and its
                            # positions in (effective heat, page id) order
                            # (every interfering size's candidate order is
                            # a filter of it)
                            with tracing.span("interval.pull"):
                                rank_order = np.asarray(order)
                                hot_slot = np.asarray(hot_slot)
                            if rank_on_device and tracing.active():
                                tracing.count("xfer.d2h_bytes",
                                              rank_order.nbytes + hot_slot.nbytes)
                            hot_at = np.flatnonzero(hot_slot < p_pad)
                            hot_by_key = hot_slot[hot_at]
                            hot_rank = np.empty(hot.size, dtype=np.int64)
                            hot_rank[hot_by_key] = hot_at
                        for s in interfering:
                            tracing.count("sweep.interfering_sizes")
                            tracing.count("device.dispatches", 3)  # 2 x _row, _fix_row
                            with tracing.span("fixup.pull", size=s):
                                vsel = np.asarray(_row(vsel_d, s))
                                win = np.asarray(_row(win_mask_d, s))
                            if tracing.active():
                                tracing.count("xfer.d2h_bytes", vsel.nbytes + win.nbytes)
                            vrank = np.flatnonzero(vsel)  # walk order = rank order
                            victims = rank_order[vrank]
                            won = win[: hot.size]
                            widx = np.flatnonzero(won)  # promotion order
                            winners = hot[widx]
                            if victims.size + winners.size < d_demand[s]:
                                raise RuntimeError(
                                    "jax sweep: victim supply mismatch (corrupted "
                                    "tier state)"
                                )
                            with tracing.span("fixup.merge", size=s):
                                step_events = pools[s]._schedule_events(int(n_cand[s]))
                                # the merge keys on ranks; the winners' key
                                # order is read off the shared ranking, or
                                # sorted where they are few beside it
                                wrank = hot_rank[widx]
                                if widx.size * _OWN_ORDER_CUT < hot.size:
                                    tracing.count("fixup.own_order")
                                    worder = np.argsort(wrank)
                                else:
                                    tracing.count("fixup.shared_order")
                                    promo = np.empty(hot.size, dtype=np.int64)
                                    promo[widx] = np.arange(widx.size)
                                    worder = promo[hot_by_key[won[hot_by_key]]]
                                base_n, cand_taken = _resolve_step_victims(
                                    vrank, victims, wrank, winners, step_events, worder
                                )
                            with tracing.span("fixup.patch", size=s):
                                fix = np.zeros(num_pages, dtype=np.int8)
                                fix[victims[base_n:]] = 1
                                fix[winners[cand_taken]] = 2
                                dev_tier = _fix_row(dev_tier, s, fix)
                            if tracing.active():
                                tracing.count("xfer.h2d_bytes", fix.nbytes)
                with tracing.span("interval.account"):
                    # --- commit counters to the host pools (the
                    # _try_bulk_step bookkeeping, fed from the pulled
                    # schedule)
                    for s, pool in enumerate(pools):
                        pool._fast_used += int(pm_pr[s]) - int(d_demand[s])
                        st = pool.stats
                        st.pgdemote_direct += int(direct_total[s])
                        st.pgdemote_kswapd += int(pm_de[s]) - int(direct_total[s])
                        st.direct_reclaim_events += int(events[s])
                        st.pgpromote_success += int(pm_pr[s])
                    # --- per-size telemetry + cost (host, identical
                    # arithmetic)
                    with tracing.span("interval.pull"):
                        sums = np.asarray(sums_d)
                    if tracing.active():
                        tracing.count("xfer.d2h_bytes", sums.nbytes)
                    pacc_f_all = sums[:, 0]
                    pacc_s_all = int(counts_mem.sum()) - pacc_f_all
                    ptouch_f_all = sums[:, 1]
                    ptouch_s_all = int(rep.sum()) - ptouch_f_all
                    warm_pages_all = sums[:, 2]
                    warm_touch_all = sums[:, 3]
                    for s, pool in enumerate(pools):
                        outcome = PolicyOutcome(
                            pm_pr=int(pm_pr[s]),
                            pm_de=int(pm_de[s]),
                            pm_fail=int(pm_fail[s]),
                            direct_reclaim=int(direct_total[s]),
                            pm_admit_fail=int(rejected[s]),
                        )
                        if profilers is not None:
                            profilers[s].record_accesses(
                                int(ptouch_f_all[s]),
                                int(ptouch_s_all[s]),
                                ia.ops,
                                cachelines=int(pacc_f_all[s]) + int(pacc_s_all[s]),
                                warm_pages=int(warm_pages_all[s]),
                                warm_touches=int(warm_touch_all[s]),
                            )
                            profilers[s].record_policy(outcome)
                            configs_out[s].append(profilers[s].finish(pool))
                        cost = interval_time(
                            hw,
                            pacc_f=int(pacc_f_all[s]),
                            pacc_s=int(pacc_s_all[s]),
                            ops=ia.ops,
                            pm_pr=outcome.pm_pr,
                            pm_de=outcome.pm_de,
                            pm_fail=outcome.pm_fail,
                            direct_reclaimed=int(direct_total[s]),
                            mlp_eff=mlp_eff,
                            num_threads=trace.num_threads,
                            rand_frac=ia.rand_frac,
                        )
                        times[s, i] = cost.total
                        costs[s].append(cost)
                        if tuned:
                            fm_sizes[s, i] = pool.effective_fm_size
                            t_now[s] += cost.total
                with tracing.span("interval.fold"):
                    _fold_heat(heat, interval_touch, pages)
                # --- per-slice tuner steps (_simulate() order: after the fold)
                if tuned:
                    with tracing.span("interval.tune"):
                        for s, tuner in enumerate(tuners):
                            te = tune_everys[s]
                            if tuner is not None and te and (i + 1) % te == 0:
                                window = costs[s][-te:]
                                acc = sum(
                                    c.pacc_f + c.pacc_s for c in configs_out[s][-te:]
                                )
                                tpa = sum(c.total for c in window) / max(acc, 1)
                                tuner.step(
                                    configs_out[s][-1], t=t_now[s], measured_tpa=tpa
                                )
        # --- import the final device state back into the host pools so
        # they are indistinguishable from a numpy-sweep run's
        with tracing.span("sweep.import"):
            final_fast = [pool._fast_used for pool in pools]
            final_rss = [pool._rss_pages for pool in pools]
            final_tier = np.asarray(dev_tier)
            tracing.count("xfer.d2h_bytes", final_tier.nbytes)
            TieredPagePool._import_tier_stack(pools, final_tier)
            for s, pool in enumerate(pools):
                if pool._fast_used != final_fast[s] or pool._rss_pages != final_rss[s]:
                    raise RuntimeError(
                        "jax sweep: host/device tier accounting diverged "
                        f"(size {s}: fast_used {final_fast[s]} vs "
                        f"{pool._fast_used}, rss {final_rss[s]} vs "
                        f"{pool._rss_pages})"
                    )
    return times, pools, configs_out, fm_sizes, costs
