"""GridPlan equivalence tests: for every registered domain the three
lowerings must agree with each other and with the host oracle
enumeration, at several scale levels / subdivision factors.

Layers covered:
  * host: coords_host == brute-force membership enumeration,
  * traced: closed-form block_coords under jit == host table (the table
    IS the prefetch_lut payload, so this is closed_form == prefetch_lut
    at the decode level),
  * kernel: the Pallas write / CA / flash kernels produce bit-identical
    outputs under all three lowerings.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fractal as F
from repro.core.domain import (GeneralizedFractalDomain, SierpinskiDomain,
                               make_fractal_domain)
from repro.core.plan import (LOWERINGS, GridPlan, normalize_lowering,
                             registered_domains, xla_schedule)
from repro.kernels import ops, ref

RNG = np.random.default_rng(3)


def _all_domains():
    """Every registered family at several r / m."""
    out = []
    for size in ("small", "medium"):
        for name, dom in registered_domains(size).items():
            out.append(pytest.param(dom, id=f"{name}-{size}"))
    return out


def _oracle_set(dom):
    nbx, nby = dom.bounding_box
    return {(x, y) for y in range(nby) for x in range(nbx)
            if dom.always_member or bool(dom.contains(x, y))}


# ---------------------------------------------------------------------------
# decode-level equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dom", _all_domains())
def test_coords_host_matches_oracle(dom):
    c = dom.coords_host()
    assert c.shape == (dom.num_blocks, 2)
    got = {tuple(r) for r in c}
    assert len(got) == dom.num_blocks  # enumeration is injective
    assert got == _oracle_set(dom)


@pytest.mark.parametrize("dom", _all_domains())
def test_closed_form_decode_equals_lut_table(dom):
    # the traced closed-form decode must reproduce the host table that
    # the prefetch_lut lowering ships to the scalar core
    i = jnp.arange(dom.num_blocks, dtype=jnp.int32)
    bx, by = jax.jit(dom.block_coords)(i)
    got = np.stack([np.asarray(bx), np.asarray(by)], -1)
    np.testing.assert_array_equal(got, dom.coords_host())


@pytest.mark.parametrize("dom", _all_domains())
def test_grid_shapes_per_lowering(dom):
    nbx, nby = dom.bounding_box
    for lowering, want in (("closed_form", (dom.num_blocks,)),
                           ("prefetch_lut", (dom.num_blocks,)),
                           ("bounding", (nby, nbx)),
                           ("mma", (dom.num_blocks,)),
                           ("compact", (dom.num_blocks,))):
        plan = GridPlan(dom, lowering, batch_dims=(3,))
        assert plan.grid == (3,) + want
        # prefetch_lut binds its host table, mma its chain-built one
        assert plan.num_scalar_prefetch == int(plan._table_backed)
        assert plan._table_backed == (lowering in ("prefetch_lut",
                                                   "mma"))


@pytest.mark.parametrize("dom", _all_domains())
def test_row_extents_match_enumeration(dom):
    ext = GridPlan(dom).row_extents()
    members = _oracle_set(dom)
    nbx, nby = dom.bounding_box
    for by in range(nby):
        xs = [x for (x, y) in members if y == by]
        if xs:
            assert ext[by, 0] == min(xs) and ext[by, 1] == max(xs)
        else:
            assert ext[by, 1] < ext[by, 0]


def test_coords_host_is_memoized():
    d = SierpinskiDomain(16)
    assert d.coords_host() is d.coords_host()


def test_membership_grid_is_memoized():
    spec = F.FractalSpec("test-gasket", k=3, m=2,
                         offsets=((0, 0), (0, 1), (1, 1)))
    assert spec.membership_grid(8) is spec.membership_grid(8)


@pytest.mark.parametrize("spec", [F.SIERPINSKI, F.CARPET, F.VICSEK])
@pytest.mark.parametrize("r", [1, 2, 3])
def test_generalized_is_member_matches_dense_grid(spec, r):
    n = spec.m ** r
    y, x = np.mgrid[0:n, 0:n]
    got = np.asarray(spec.is_member(jnp.asarray(x), jnp.asarray(y), n))
    np.testing.assert_array_equal(got, spec.membership_grid(n))


def test_generalized_contains_is_traceable():
    # the digit-test contains must trace (no dense-grid constant capture)
    d = GeneralizedFractalDomain(F.VICSEK, 9)
    got = jax.jit(d.contains)(jnp.arange(9)[None, :], jnp.arange(9)[:, None])
    np.testing.assert_array_equal(np.asarray(got),
                                  F.VICSEK.membership_grid(9))


def test_lowering_names():
    assert normalize_lowering("compact") == "closed_form"
    with pytest.raises(ValueError):
        normalize_lowering("nope")
    assert xla_schedule("bounding") == "dense"
    assert xla_schedule("prefetch_lut") == "triangular"
    assert xla_schedule("compact") == "triangular"
    assert xla_schedule("mma") == "triangular"


# ---------------------------------------------------------------------------
# kernel-level equivalence (bit-identical across lowerings)
# ---------------------------------------------------------------------------

_FRACTAL_CASES = [("sierpinski-gasket", 16, 4), ("sierpinski-gasket", 64, 8),
                  ("sierpinski-carpet", 9, 3), ("sierpinski-carpet", 27, 3),
                  ("vicsek-cross", 9, 3), ("vicsek-cross", 27, 9)]


def _fractal_state(fractal, n):
    dom = make_fractal_domain(fractal, n)
    y, x = np.mgrid[0:n, 0:n]
    mask = np.asarray(dom.cell_member(x, y, n))
    return jnp.asarray(np.where(mask, RNG.normal(size=(n, n)), 0),
                       jnp.float32), mask


@pytest.mark.parametrize("fractal,n,block", _FRACTAL_CASES)
def test_write_lowerings_bit_identical(fractal, n, block):
    m, mask = _fractal_state(fractal, n)
    outs = [np.asarray(ops.sierpinski_write(
        m, 7.0, block=block, grid_mode=gm, fractal=fractal))
        for gm in LOWERINGS]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)
    want = np.where(mask, np.float32(7.0), np.asarray(m))
    np.testing.assert_array_equal(outs[0], want)


@pytest.mark.parametrize("fractal,n,block", _FRACTAL_CASES)
def test_sum_lowerings_agree(fractal, n, block):
    m, mask = _fractal_state(fractal, n)
    sums = [float(ops.sierpinski_sum(m, block=block, grid_mode=gm,
                                     fractal=fractal))
            for gm in LOWERINGS]
    assert sums[0] == sums[1]  # identical schedule -> bit-identical
    assert sums[0] == sums[3]  # mma walks the same compact schedule
    np.testing.assert_allclose(sums[2], sums[0], rtol=1e-6)
    np.testing.assert_allclose(
        sums[0], float(np.asarray(m)[mask].sum()), rtol=1e-5)


@pytest.mark.parametrize("fractal,n,block",
                         [("sierpinski-gasket", 32, 8),
                          ("sierpinski-carpet", 27, 3),
                          ("vicsek-cross", 27, 3)])
@pytest.mark.parametrize("rule", ["parity", "diffusion"])
def test_ca_lowerings_bit_identical(fractal, n, block, rule):
    m, mask = _fractal_state(fractal, n)
    if rule == "parity":
        m = jnp.asarray(np.where(mask, RNG.integers(0, 2, (n, n)), 0),
                        jnp.float32)
    outs = [np.asarray(ops.ca_step(m, jnp.zeros_like(m), rule=rule,
                                   block=block, grid_mode=gm,
                                   fractal=fractal))
            for gm in LOWERINGS]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)
    assert (outs[0][~mask] == 0).all()


@pytest.mark.parametrize("kind,kw", [("causal", {}),
                                     ("local", {"window": 128}),
                                     ("full", {})])
def test_flash_lowerings_bit_identical(kind, kw):
    q = jnp.asarray(RNG.normal(size=(1, 4, 256, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 256, 32)), jnp.float32)
    outs = [np.asarray(ops.flash_attention(q, k, v, kind=kind, block_q=64,
                                           block_k=64, grid_mode=gm, **kw))
            for gm in LOWERINGS]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)
    want = np.asarray(ref.attention_ref(q, k, v, kind, **kw))
    np.testing.assert_allclose(outs[0], want, rtol=2e-5, atol=2e-5)


def test_flash_full_compact_enumeration():
    # "full" now runs under the compact lowerings too (row-major
    # bounding-box domain), including rectangular grids
    q = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 1, 384, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 1, 384, 32)), jnp.float32)
    outs = [np.asarray(ops.flash_attention(q, k, v, kind="full", block_q=64,
                                           block_k=128, grid_mode=gm))
            for gm in LOWERINGS]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


# ---------------------------------------------------------------------------
# XLA schedule plumbing
# ---------------------------------------------------------------------------

def test_xla_flash_accepts_lowering_names():
    from repro.models.attention import flash_attention_xla
    q = jnp.asarray(RNG.normal(size=(1, 2, 256, 32)), jnp.float32)
    dense = flash_attention_xla(q, q, q, kind="causal", chunk=64,
                                schedule="bounding")
    tri = flash_attention_xla(q, q, q, kind="causal", chunk=64,
                              schedule="prefetch_lut")
    np.testing.assert_allclose(np.asarray(dense), np.asarray(tri),
                               rtol=2e-5, atol=2e-5)


def test_config_grid_lowering_resolution():
    from repro.models.config import ModelConfig
    cfg = ModelConfig()
    assert cfg.attn_schedule_resolved == "dense"
    assert cfg.grid_mode == "closed_form"
    cfg2 = cfg.replace(grid_lowering="prefetch_lut")
    assert cfg2.attn_schedule_resolved == "triangular"
    assert cfg2.grid_mode == "prefetch_lut"
    cfg3 = cfg.replace(grid_lowering="bounding")
    assert cfg3.attn_schedule_resolved == "dense"
