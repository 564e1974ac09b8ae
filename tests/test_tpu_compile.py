"""Compile the main-path kernels for one described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with jaxlib, lowers
each kernel through Mosaic at a real width and refuses what the chip
would refuse (unaligned slices, VMEM / SMEM overruns, primitives Mosaic
cannot lower).  Every compiled text must hold a ``tpu_custom_call``, so
a kernel that silently fell back to the Pallas interpreter fails here.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.compact import CompactLayout
from repro.core.domain import make_fractal_domain
from repro.kernels import ops
from repro.kernels.flash_attention import paged_flash_attention

N = 1 << 14          # the paper's largest embedded write/sum side here
BLOCK = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("grid_mode", ["closed_form", "bounding"])
def test_sierpinski_write_compiles_for_v5e(one_chip, grid_mode):
    txt = _compile_text(
        lambda m: ops.sierpinski_write(
            m, 1.0, block=BLOCK, grid_mode=grid_mode, coarsen=1,
            num_stages=1, backend="tpu"),
        _spec(one_chip, (N, N)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("num_stages", [1, 2])
def test_sierpinski_sum_compiles_for_v5e(one_chip, num_stages):
    txt = _compile_text(
        lambda m: ops.sierpinski_sum(
            m, block=BLOCK, grid_mode="closed_form", coarsen=1,
            num_stages=num_stages, backend="tpu"),
        _spec(one_chip, (N, N)))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("storage", ["embedded", "compact"])
def test_fused_ca_run_compiles_for_v5e(one_chip, storage):
    n = 1 << 13
    shape = (n, n)
    if storage == "compact":
        shape = CompactLayout(make_fractal_domain(
            "sierpinski-gasket", n // BLOCK)).array_shape(BLOCK)
    txt = _compile_text(
        lambda a, b: ops.ca_run(
            a, b, 16, fuse=8, block=BLOCK, grid_mode="closed_form",
            storage=storage, n=n, coarsen=1, num_stages=1, backend="tpu"),
        _spec(one_chip, shape), _spec(one_chip, shape))
    assert "tpu_custom_call" in txt


def test_causal_flash_attention_compiles_for_v5e(one_chip):
    qkv = _spec(one_chip, (1, 8, 2048, 128), jnp.bfloat16)
    txt = _compile_text(
        lambda q, k, v: ops.flash_attention(
            q, k, v, kind="causal", block_q=128, block_k=128,
            grid_mode="closed_form", backend="tpu"),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in txt


def test_paged_decode_compiles_for_v5e_at_phi3_widths(one_chip):
    # phi3-mini-3.8b: 32 query heads, 32 kv heads, head_dim 96; 4
    # slots of 66 pages of 16 tokens in a 265-page fused-KV pool
    slots, heads, hd, pages, page = 4, 32, 96, 66, 16
    txt = _compile_text(
        lambda q, kv, pt, pos: paged_flash_attention(
            q, kv, pt, pos, grid_mode="closed_form", backend="tpu"),
        _spec(one_chip, (slots, heads, 1, hd), jnp.bfloat16),
        _spec(one_chip, (slots * pages + 1, 2 * heads, page, hd),
              jnp.bfloat16),
        _spec(one_chip, (slots, pages), jnp.int32),
        _spec(one_chip, (slots,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("hd", [96, 128])
def test_paged_decode_compiles_for_v5e_at_the_phi3_cell(one_chip, hd):
    # phi3.chat-decode-c8: 8 slots of 32 pages of 16 tokens in a
    # 265-page pool, 32 query and kv heads.  Each grid step takes a
    # slot's (32, hd) queries and a page's whole fused tile, 64 rows of
    # 16 x hd bf16: 256 KB at the chip's 128 lanes (head size 96 is laid
    # out at 128, and 128 is the padded size itself)
    slots, heads, pages, page, pool = 8, 32, 32, 16, 265
    txt = _compile_text(
        lambda q, kv, pt, pos: paged_flash_attention(
            q, kv, pt, pos, grid_mode="closed_form", backend="tpu"),
        _spec(one_chip, (slots, heads, 1, hd), jnp.bfloat16),
        _spec(one_chip, (pool, 2 * heads, page, hd), jnp.bfloat16),
        _spec(one_chip, (slots, pages), jnp.int32),
        _spec(one_chip, (slots,), jnp.int32))
    assert "tpu_custom_call" in txt
    assert "_paged_impl_decode" in txt


def test_paged_latent_decode_compiles_for_v5e_at_deepseek_v2_lite_widths(
        one_chip):
    # DeepSeek-V2-Lite: 16 heads, latent rows of 512 + 64 stored in 640
    # lanes; 16 slots of 384 pages of 16 tokens in a 6145-page pool.
    # Each grid step copies a chunk of 32 pages into a double buffer
    from repro.core.paged import latent_lanes
    from repro.kernels.latent_decode import (latent_chunk_pages,
                                             paged_latent_decode)
    slots, heads, width, pages, page = 16, 16, 576, 384, 16
    assert latent_lanes(width) == 640
    assert latent_chunk_pages(page, latent_lanes(width), pages, 2) == 32
    txt = _compile_text(
        lambda q, pool, pt, pos: paged_latent_decode(
            q, pool, pt, pos, scale=0.1, v_dim=512, backend="tpu"),
        _spec(one_chip, (slots, heads, width), jnp.bfloat16),
        _spec(one_chip, (slots * pages + 1, page, latent_lanes(width)),
              jnp.bfloat16),
        _spec(one_chip, (slots, pages), jnp.int32),
        _spec(one_chip, (slots,), jnp.int32))
    assert "tpu_custom_call" in txt
    assert "paged_latent_decode" in txt
