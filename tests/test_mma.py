"""The ``mma`` lowering's digit-basis matmul decode chains
(:mod:`repro.core.mma`): mixed-precision exactness against the integer
closed forms, the asserted f32-accumulation bound, and kernel-level
parity with the closed form."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fractal as F
from repro.core import mma

from hypothesis_compat import given, settings, st

SPECS = (F.SIERPINSKI, F.CARPET, F.VICSEK)
#: deepest level per spec whose volume k^r and extent m^r both stay
#: under DIGIT_BOUND -- the exactness envelope the chains assert
MAX_R = {s.name: max(r for r in range(1, 40)
                     if s.k ** r < mma.DIGIT_BOUND
                     and s.m ** r < mma.DIGIT_BOUND)
         for s in SPECS}


# ---------------------------------------------------------------------------
# mixed-precision exactness property: chain == integer closed form for
# every level up to the asserted bound (large magnitudes included)
# ---------------------------------------------------------------------------

@given(st.integers(0, 2), st.data())
@settings(max_examples=60, deadline=None)
def test_property_decode_exact_up_to_bound(which, data):
    spec = SPECS[which]
    r = data.draw(st.integers(1, MAX_R[spec.name]))
    # bias toward the top of the index range, where f32 rounding would
    # first show
    i = data.draw(st.integers(max(0, spec.k ** r - 64),
                              spec.k ** r - 1))
    bx, by = mma.decode_linear(spec, r, jnp.int32(i))
    ex, ey = spec.lambda_map_linear(int(i), r)
    assert (int(bx), int(by)) == (int(ex), int(ey))
    sx, sy = mma.slots_of_linear(spec, r, jnp.int32(i))
    wx, wy = F.deinterleave_linear(int(i), spec.k, r)
    assert (int(sx), int(sy)) == (int(wx), int(wy))


@given(st.integers(0, 2), st.data())
@settings(max_examples=60, deadline=None)
def test_property_inverse_and_linear_exact(which, data):
    spec = SPECS[which]
    r = data.draw(st.integers(1, min(MAX_R[spec.name], 12)))
    i = data.draw(st.integers(0, spec.k ** r - 1))
    x, y = spec.lambda_map_linear(int(i), r)
    li = mma.linear_of(spec, r, jnp.int32(int(x)), jnp.int32(int(y)))
    assert int(li) == int(i)
    sx, sy = mma.inverse_slots(spec, r, jnp.int32(int(x)),
                               jnp.int32(int(y)))
    ex, ey = spec.lambda_inverse(int(x), int(y), r)
    assert (int(sx), int(sy)) == (int(ex), int(ey))


@pytest.mark.parametrize("spec", SPECS)
def test_decode_exact_at_bound_edge_batch(spec):
    """Dense check of the last 4k indices at the deepest in-bound
    level: the largest magnitudes the chain ever accumulates."""
    r = MAX_R[spec.name]
    k_r = spec.k ** r
    i = np.arange(max(0, k_r - 4096), k_r, dtype=np.int64)
    bx, by = mma.decode_linear(spec, r, jnp.asarray(i, jnp.int32))
    ex, ey = spec.lambda_map_linear(i, r)
    np.testing.assert_array_equal(np.asarray(bx), np.asarray(ex))
    np.testing.assert_array_equal(np.asarray(by), np.asarray(ey))


def test_bound_is_asserted():
    for spec in SPECS:
        with pytest.raises(ValueError, match="2\\^24"):
            mma.coords_basis(spec, MAX_R[spec.name] + 1)
    with pytest.raises(ValueError, match="2\\^24"):
        mma.decode_linear(F.SIERPINSKI, MAX_R["sierpinski-gasket"] + 1,
                          jnp.int32(0))


# ---------------------------------------------------------------------------
# kernel-level parity: the kernels consume the chain-built table through
# the same scalar-prefetch path as the host LUT, bit for bit
# ---------------------------------------------------------------------------

def _gasket_operand(n, block, storage, fill=0.0):
    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    m = jnp.asarray(np.where(F.membership_grid(n), fill, 0.0), jnp.float32)
    if storage == "compact":
        lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                                n // block))
        return lay.pack(m, block), dict(storage="compact", n=n)
    return m, {}


@pytest.mark.parametrize("backend", ["tpu-interpret"])
@pytest.mark.parametrize("storage", ["embedded", "compact"])
def test_write_mma_matches_closed_form_both_targets(backend, storage):
    from repro.kernels import ops
    n, block = 64, 8
    m, kw = _gasket_operand(n, block, storage)
    outs = [ops.sierpinski_write(m, 7.0, block=block, grid_mode=gm,
                                 backend=backend, **kw)
            for gm in ("closed_form", "mma")]
    np.testing.assert_array_equal(np.asarray(outs[0]),
                                  np.asarray(outs[1]))


@pytest.mark.parametrize("kernel", ["sum", "ca"])
def test_sum_and_ca_mma_match_closed_form(kernel):
    from repro.kernels import ops
    n, block = 64, 8
    rng = np.random.default_rng(11)
    m, kw = _gasket_operand(n, block, "compact", fill=1.0)
    m = m * jnp.asarray(rng.integers(0, 2, m.shape), jnp.float32)

    def run(gm):
        if kernel == "sum":
            return ops.sierpinski_sum(m, block=block, grid_mode=gm,
                                      backend="tpu-interpret", **kw)
        return ops.ca_run(m, jnp.zeros_like(m), 6, fuse=3, rule="parity",
                          block=block, grid_mode=gm, donate=False,
                          backend="tpu-interpret", **kw)

    want, got = np.asarray(run("closed_form")), np.asarray(run("mma"))
    assert want.any()          # a non-trivial state
    np.testing.assert_array_equal(got, want)
