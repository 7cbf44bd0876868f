"""Every Pallas kernel compiles for a TPU v5e at real widths.

No chip is needed: the v5e compiler is installed and compiles for a chip
that is described and not attached. Interpret-mode tests cannot catch what
only Mosaic refuses (block shapes off the (8, 128) tiling, primitives with
no TPU lowering, VMEM overflow); these compiles do, at about a second
each. The topology is described inside a module fixture — never while the
module is imported — so every test worker collects the same tests and
only the worker that runs this file loads the TPU compiler.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.demote_rank import _victim_partition_pallas
from repro.kernels.flash_attention import flash_attention
from repro.kernels.page_migrate import migrate_pages
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.rwkv6_chunk import wkv6_chunked
from repro.kernels.strided_probe import strided_probe
from repro.serving.kv_cache import KVPageConfig

N_SIZES = 46  # the perf-database fm-size vector (core/tuner.py)
RSS_PAGES = 2_621_440  # 10 GiB of 4 KiB pages


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(chip, fn, *shapes):
    compiled = jax.jit(fn).lower(*(_shape(chip, *s) for s in shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_demote_rank_at_sweep_width(chip):
    compiled = _compile(
        chip,
        _victim_partition_pallas,
        ((N_SIZES, RSS_PAGES), jnp.int32),
        ((N_SIZES,), jnp.int32),
    )
    # the tiled kernel streams: nothing near a whole size row in scratch
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * N_SIZES * RSS_PAGES * 4


def test_page_migrate_kv_pages(chip):
    cfg = get_config("qwen3-1.7b")
    page = KVPageConfig(
        n_groups=cfg.num_layers, page_size=16, kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim,
    ).page_shape
    _compile(
        chip,
        migrate_pages,
        ((2048,) + page, jnp.bfloat16),
        ((64,) + page, jnp.bfloat16),
        ((32,), jnp.int32),
        ((32,), jnp.int32),
    )


def test_strided_probe_4k_pages(chip):
    page = (8, 128)  # one 4 KiB page of f32
    _compile(
        chip,
        lambda f, s, fi, si: strided_probe(f, s, fi, si, 8),
        ((16_384,) + page, jnp.float32),
        ((16_384,) + page, jnp.float32),
        ((512,), jnp.int32),
        ((512,), jnp.int32),
    )


def test_paged_decode_attention(chip):
    cfg = get_config("qwen3-1.7b")
    kv, hd, page_size, pages_per_seq, batch = (
        cfg.num_kv_heads, cfg.head_dim, 16, 256, 8,
    )
    _compile(
        chip,
        paged_decode_attention,
        ((batch, cfg.num_heads, hd), jnp.bfloat16),
        ((batch * pages_per_seq, page_size, kv, hd), jnp.bfloat16),
        ((batch * pages_per_seq, page_size, kv, hd), jnp.bfloat16),
        ((batch, pages_per_seq), jnp.int32),
        ((batch,), jnp.int32),
    )


def test_flash_attention(chip):
    cfg = get_config("qwen3-1.7b")
    seq = 4096
    _compile(
        chip,
        flash_attention,
        ((1, seq, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
        ((1, seq, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16),
        ((1, seq, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16),
    )


def test_wkv6(chip):
    cfg = get_config("rwkv6-3b")
    heads, hd, seq = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim, 4096
    _compile(
        chip,
        wkv6_chunked,
        *[((1, seq, heads, hd), jnp.bfloat16)] * 4,
        ((heads, hd), jnp.float32),
    )
