"""Pallas kernel validation (interpret mode) against the ref.py oracles.

Per instructions: sweep shapes/dtypes and assert_allclose vs the
pure-jnp oracle for every kernel.

``REPRO_GRID_MODE`` (comma-separated lowering names) overrides the
default grid-mode sweep -- CI uses it to re-run the whole parity suite
under a single lowering (e.g. ``REPRO_GRID_MODE=mma``).
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fractal as F
from repro.core.domain import make_fractal_domain
from repro.kernels import ops, ref

GRID_MODES = (os.environ["REPRO_GRID_MODE"].split(",")
              if os.environ.get("REPRO_GRID_MODE")
              else ["compact", "bounding"])

RNG = np.random.default_rng(0)


def _fractal_state(n, dtype, binary=False):
    mask = F.membership_grid(n)
    if binary:
        s = RNG.integers(0, 2, size=(n, n))
    else:
        s = RNG.normal(size=(n, n))
    return jnp.asarray(np.where(mask, s, 0), dtype)


# ---------------------------------------------------------------------------
# sierpinski_write / sierpinski_sum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,block", [(8, 2), (16, 4), (64, 16), (64, 64),
                                     (256, 32), (128, 8)])
@pytest.mark.parametrize("grid_mode", GRID_MODES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_sierpinski_write(n, block, grid_mode, dtype):
    m = _fractal_state(n, dtype)
    got = ops.sierpinski_write(m, 7.0, block=block, grid_mode=grid_mode)
    want = ref.sierpinski_write_ref(m, 7.0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,block", [(16, 4), (64, 16), (256, 64)])
def test_sierpinski_sum(n, block):
    m = _fractal_state(n, jnp.float32)
    got = ops.sierpinski_sum(m, block=block)
    want = ref.sierpinski_sum_ref(m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_write_touches_exactly_the_fractal():
    n = 64
    m = jnp.zeros((n, n), jnp.float32)
    out = np.asarray(ops.sierpinski_write(m, 1.0, block=8))
    assert out.sum() == F.gasket_volume(n)  # Lemma 1: 3**r cells written


# ---------------------------------------------------------------------------
# ca_step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,block", [(16, 4), (32, 8), (64, 16), (64, 32)])
@pytest.mark.parametrize("rule", ["parity", "diffusion"])
@pytest.mark.parametrize("grid_mode", GRID_MODES)
def test_ca_step(n, block, rule, grid_mode):
    s = _fractal_state(n, jnp.float32, binary=(rule == "parity"))
    got = ops.ca_step(s, jnp.zeros_like(s), rule=rule, block=block,
                      grid_mode=grid_mode)
    want = ref.ca_step_ref(s, rule)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_ca_multi_step_double_buffer():
    n, block = 32, 8
    s = _fractal_state(n, jnp.float32, binary=True)
    a, b = s, jnp.zeros_like(s)
    want = s
    for _ in range(5):
        new = ops.ca_step(a, b, rule="parity", block=block)
        b, a = a, new
        want = ref.ca_step_ref(want, "parity")
    np.testing.assert_allclose(np.asarray(a), np.asarray(want))


def test_ca_preserves_zero_outside_fractal():
    n = 64
    s = _fractal_state(n, jnp.float32)
    out = np.asarray(ops.ca_step(s, jnp.zeros_like(s), rule="diffusion",
                                 block=16))
    assert (out[~F.membership_grid(n)] == 0).all()


def test_ca_diffusion_conserves_mass():
    # graph-Laplacian diffusion conserves the total heat on the gasket
    n = 64
    s = _fractal_state(n, jnp.float32)
    out = ops.ca_step(s, jnp.zeros_like(s), rule="diffusion", block=16)
    np.testing.assert_allclose(float(jnp.sum(out)), float(jnp.sum(s)),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

def _qkv(b, h, hkv, sq, sk, d, dtype):
    q = jnp.asarray(RNG.normal(size=(b, h, sq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, hkv, sk, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("b,h,hkv,s,d,bq", [
    (1, 1, 1, 128, 32, 64),
    (2, 4, 2, 256, 32, 64),
    (1, 8, 1, 256, 64, 128),   # MQA
    (2, 2, 2, 128, 128, 64),
])
@pytest.mark.parametrize("grid_mode", GRID_MODES)
def test_flash_causal(b, h, hkv, s, d, bq, grid_mode):
    q, k, v = _qkv(b, h, hkv, s, s, d, jnp.float32)
    got = ops.flash_attention(q, k, v, kind="causal", block_q=bq,
                              block_k=bq, grid_mode=grid_mode)
    want = ref.attention_ref(q, k, v, "causal")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [64, 128, 256])
@pytest.mark.parametrize("grid_mode", GRID_MODES)
def test_flash_local(window, grid_mode):
    q, k, v = _qkv(1, 2, 2, 512, 512, 32, jnp.float32)
    got = ops.flash_attention(q, k, v, kind="local", window=window,
                              block_q=64, block_k=64, grid_mode=grid_mode)
    want = ref.attention_ref(q, k, v, "local", window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_full_rectangular():
    q, k, v = _qkv(1, 2, 1, 128, 384, 64, jnp.float32)
    got = ops.flash_attention(q, k, v, kind="full", block_q=64,
                              block_k=128, grid_mode="bounding")
    want = ref.attention_ref(q, k, v, "full")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,rtol", [(jnp.bfloat16, 2e-2)])
def test_flash_dtypes(dtype, rtol):
    q, k, v = _qkv(1, 2, 1, 256, 256, 32, dtype)
    got = ops.flash_attention(q, k, v, kind="causal", block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, "causal")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=rtol)


def test_flash_compact_equals_bounding():
    # the two grid modes are bit-identical per block schedule
    q, k, v = _qkv(1, 4, 2, 256, 256, 32, jnp.float32)
    a = ops.flash_attention(q, k, v, kind="causal", block_q=64, block_k=64,
                            grid_mode="compact")
    b = ops.flash_attention(q, k, v, kind="causal", block_q=64, block_k=64,
                            grid_mode="bounding")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# chip semantics: the TPU-faithful interpreter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_stages", [1, 2])
def test_bounding_grid_fills_non_member_blocks(chip_semantics, num_stages):
    """Under ``bounding`` every block of the box is a grid step and its
    output block is written back; the non-member steps must fill it
    (input for write, stale buffer for CA) or the chip writes stale
    VMEM over them."""
    n, block = 32, 8
    m = jnp.asarray(RNG.integers(0, 4, (n, n)).astype(np.float32))
    kw = dict(block=block, grid_mode="bounding", coarsen=1,
              num_stages=num_stages)
    out = ops.sierpinski_write(m, 7.0, **kw)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.sierpinski_write_ref(m, 7.0)))
    s = _fractal_state(n, jnp.float32, binary=True)
    want = s
    for _ in range(3):
        want = ref.ca_step_ref(want, "parity")
    got = ops.ca_run(s, jnp.zeros_like(s), 3, fuse=2, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bounding_over_compact_storage_is_refused_on_the_chip():
    """Refused on every target where no member step sits next to the
    skipped ones in grid order: a domain that cannot name its row
    members, and sharded launches (lowering here needs no chip)."""
    import jax
    from repro.core.compact import CompactLayout
    from repro.core.domain import BoundingBoxDomain
    from repro.core.plan import GridPlan
    from repro.analysis.verifier import HostMesh
    from repro.core.shard import ShardedPlan
    dom = BoundingBoxDomain(4, 4, member=lambda x, y: (x + y) % 2 == 0)
    block = 8
    a = jax.ShapeDtypeStruct(CompactLayout(dom).array_shape(block),
                             jnp.float32)
    for backend in ("tpu", "tpu-interpret"):
        kw = dict(block=block, grid_mode="bounding", storage="compact",
                  domain=dom, coarsen=1, num_stages=1, backend=backend)
        with pytest.raises(ValueError, match="row members"):
            jax.jit(lambda m: ops.sierpinski_write(m, 1.0, **kw)).lower(a)
        with pytest.raises(ValueError, match="row members"):
            jax.jit(lambda x, y: ops.ca_run(x, y, 2, fuse=1, **kw)).lower(
                a, a)
        with pytest.raises(ValueError, match="row members"):
            GridPlan(dom, "bounding", storage="compact",
                     backend=backend).check_output_writeback()
        gasket = make_fractal_domain("sierpinski-gasket", 4)
        with pytest.raises(ValueError, match="cannot be sharded"):
            ShardedPlan(gasket, "bounding", storage="compact",
                        backend=backend, mesh=HostMesh(2),
                        axis="data").check_output_writeback()


@pytest.mark.parametrize("fractal,n,block,coarsen", [
    ("sierpinski-gasket", 32, 8, 1), ("sierpinski-gasket", 32, 4, 2),
    ("vicsek-cross", 27, 3, 1)])
@pytest.mark.parametrize("num_stages", [1, 2])
def test_bounding_over_compact_storage_revisits_member_blocks(
        chip_semantics, fractal, n, block, coarsen, num_stages):
    """Under ``bounding`` over compact storage each non-member step
    revisits the slot of the member step next to it in grid order, so
    the chip's write-back of every visited block keeps the members'
    results (vicsek's first row starts with non-members)."""
    from repro.core.compact import CompactLayout
    dom = make_fractal_domain(fractal, n)
    y, x = np.mgrid[0:n, 0:n]
    mask = np.asarray(dom.cell_member(x, y, n))
    m = jnp.asarray(np.where(mask, RNG.integers(0, 4, (n, n)), 0),
                    jnp.float32)
    lay = CompactLayout(make_fractal_domain(fractal, n // block))
    mp = lay.pack(m, block)
    kw = dict(block=block, fractal=fractal, storage="compact", n=n,
              coarsen=coarsen, num_stages=num_stages)
    got = ops.sierpinski_write(mp, 7.0, grid_mode="bounding", **kw)
    np.testing.assert_array_equal(
        np.asarray(lay.unpack(got, block))[mask], 7.0)
    s = jnp.asarray(np.where(mask, RNG.integers(0, 2, (n, n)), 0),
                    jnp.float32)
    sp = lay.pack(s, block)
    want = ops.ca_run(sp, jnp.zeros_like(sp), 3, fuse=2,
                      grid_mode="closed_form", **kw)
    got = ops.ca_run(sp, jnp.zeros_like(sp), 3, fuse=2,
                     grid_mode="bounding", **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
