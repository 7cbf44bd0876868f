"""Two-tier paged KV cache: HBM pool + host pool, Tuna-managed.

Pages are the unit of everything (DESIGN.md §4): allocation, tier
migration, and context-parallel sharding. A logical page holds one
``page_size``-token slice of K and V for *all* layer groups (layer-fused
pages make the migration unit large enough for DMA efficiency — DESIGN.md
§8 change 1).

The management state is the same :class:`repro.tiering.TieredPagePool` +
:class:`~repro.tiering.policy.TPPPolicy` the simulator validates: hot
pages (actively decoded sessions) are HBM-resident; idle sessions cool
down and the watermark reclaimer demotes them to host memory; resumes
promote them back. Tuna's runtime tunes ``fm_pages`` (the HBM watermark)
from the interval telemetry, within the operator's loss target.

Physical copies go through :func:`repro.kernels.ops.migrate_pages` (the
batched-DMA Pallas kernel on TPU; gather/scatter reference on CPU).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops as kops
from repro.tiering.page_pool import Tier, TieredPagePool
from repro.tiering.policy import TPPPolicy


@dataclass
class KVPageConfig:
    n_groups: int
    page_size: int  # tokens per page
    kv_heads: int
    head_dim: int
    dtype: str = "bfloat16"

    @property
    def elems_per_page(self) -> int:
        return self.n_groups * 2 * self.page_size * self.kv_heads * self.head_dim

    @property
    def bytes_per_page(self) -> int:
        return self.elems_per_page * jnp.dtype(self.dtype).itemsize

    @property
    def page_shape(self) -> tuple[int, int]:
        """A page as ``(rows, 128)`` lanes: the layout the TPU tiles, so
        a page is one slot of the pool's untiled leading axis."""
        rows, rem = divmod(self.elems_per_page, 128)
        if rem:
            raise ValueError(
                f"{self.elems_per_page} elements per page is not a multiple "
                "of the 128 TPU lanes"
            )
        return (rows, 128)


class TieredPagedKV:
    """Physical two-tier page store with slot allocators + page table."""

    def __init__(
        self,
        cfg: KVPageConfig,
        total_pages: int,
        hbm_capacity: int,
        hot_thr: int = 2,
        seed: int = 0,
    ):
        self.cfg = cfg
        self.total_pages = total_pages
        # management state (tiers, heat, watermarks)
        self.pool = TieredPagePool(
            num_pages=total_pages,
            hw_capacity=hbm_capacity,
            page_bytes=cfg.bytes_per_page,
            seed=seed,
        )
        self.policy = TPPPolicy(hot_thr=hot_thr)
        page = cfg.page_shape
        # physical pools: HBM (device array) and host (numpy)
        self.hbm = jnp.zeros((hbm_capacity,) + page, jnp.dtype(cfg.dtype))
        self.host = np.zeros((total_pages,) + page, dtype=jnp.dtype(cfg.dtype))
        self.hbm_slot = np.full(total_pages, -1, np.int64)  # page -> hbm slot
        self._free_hbm = list(range(hbm_capacity - 1, -1, -1))
        self.migrated_in = 0
        self.migrated_out = 0

    # ---------------------------------------------------------------- state
    def tier_of(self, page: int) -> Tier:
        return Tier(self.pool.tier[page])

    def hbm_view(self, pages: np.ndarray) -> jnp.ndarray:
        """HBM slots for resident pages (must all be FAST)."""
        slots = self.hbm_slot[pages]
        if np.any(slots < 0):
            raise RuntimeError("page not HBM-resident; promote first")
        return jnp.asarray(slots)

    # ------------------------------------------------------------ migration
    def promote(self, pages: np.ndarray) -> int:
        """host → HBM (the DMA in). Returns pages actually promoted."""
        pages = np.asarray(
            [p for p in np.atleast_1d(pages) if self.pool.tier[p] != Tier.FAST],
            dtype=np.int64,
        )
        n = min(len(self._free_hbm), pages.size)
        pages = pages[:n]
        if n == 0:
            return 0
        dst = np.array([self._free_hbm.pop() for _ in range(n)], np.int64)
        self.hbm = kops.migrate_pages(
            self.hbm, jnp.asarray(self.host[pages]), jnp.asarray(dst),
            jnp.arange(n),
        )
        self.hbm_slot[pages] = dst
        self.pool.place(pages, Tier.FAST)
        self.migrated_in += n
        return n

    def demote(self, pages: np.ndarray) -> int:
        """HBM → host (the DMA out, kswapd's work)."""
        pages = np.asarray(
            [p for p in np.atleast_1d(pages) if self.pool.tier[p] == Tier.FAST],
            dtype=np.int64,
        )
        if pages.size == 0:
            return 0
        slots = self.hbm_slot[pages]
        self.host[pages] = np.asarray(self.hbm[jnp.asarray(slots)])
        for s in slots:
            self._free_hbm.append(int(s))
        self.hbm_slot[pages] = -1
        self.pool.place(pages, Tier.SLOW)
        self.migrated_out += pages.size
        return int(pages.size)

    def reclaim_to_watermark(self) -> int:
        """Demote coldest pages until the HBM free count satisfies the
        watermark (Tuna's actuation path after set_fm_size)."""
        demoted = 0
        wm = self.pool.watermarks
        while len(self._free_hbm) < wm.low_free:
            fast = self.pool.fast_pages()
            if fast.size == 0:
                break
            order = np.argsort(self.pool.heat_of(fast))
            batch = fast[order[: max(1, min(64, wm.high_free - len(self._free_hbm)))]]
            demoted += self.demote(batch)
        return demoted

    # ------------------------------------------------------------- writes
    def ensure_resident(self, pages: np.ndarray) -> tuple[int, int]:
        """Promote any non-resident pages (session resume). Returns
        (promoted, failures) — failures when HBM has no free slot even
        after reclaim (TPP's migration failure)."""
        pages = np.atleast_1d(pages).astype(np.int64)
        need = pages[self.pool.tier[pages] != Tier.FAST]
        # unallocated pages are first-touch allocated straight into HBM
        fails = 0
        if need.size:
            got = self.promote(need)
            if got < need.size:
                self.reclaim_to_watermark()
                got += self.promote(need[got:])
            fails = need.size - got
            self.pool.stats.pgpromote_fail += max(0, fails)
        return int(need.size - fails), int(fails)

    def write_tokens(self, pages: np.ndarray, data: jnp.ndarray) -> None:
        """Write new KV data into resident pages (decode appends)."""
        slots = self.hbm_view(pages)
        self.hbm = self.hbm.at[slots].set(
            data.reshape((len(slots),) + self.cfg.page_shape)
        )

    def touch(self, pages: np.ndarray, counts=None) -> None:
        pages = np.atleast_1d(pages).astype(np.int64)
        c = np.ones(pages.size, np.int64) if counts is None else counts
        self.pool.apply_accesses(pages, c, c)

    def end_interval(self):
        self.pool.end_interval()
