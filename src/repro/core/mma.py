"""MMA-accelerated lambda decode: the block-space map as matrix products.

The paper's lambda(w) map (and its inverse, the Squeeze-style compact
slot resolution) is a per-scale-level weighted sum over base-k digits.
Following *Accelerating Compact Fractals with Tensor Core GPUs* (arXiv
2110.12952) and *Squeeze* (arXiv 2201.00613), every such sum is a small
matrix contraction: encode the digit stream of an index as a one-hot
matrix ``O`` of shape (levels, k) and contract it with a precomputed
*digit-basis* matrix ``B`` of shape (levels, k, width) --
``lambda = O . B`` rides the MXU instead of the scalar
ALUs the ``closed_form`` lowering burns.

Mixed-precision contract
------------------------
One-hot digit vectors are bf16 (0/1 are exact in any float format);
basis matrices are f32 with integer entries; every ``dot_general``
accumulates in f32 (``preferred_element_type``).  A dot of 0/1 values
against integer weights is a sum of exact addends, and f32 addition of
integers is exact while every partial sum stays below 2**24 --
:data:`DIGIT_BOUND`.  The basis builders therefore *reject* any level
count whose coordinates, volume, or slot indices could reach 2**24, and
within that bound the chains are bit-identical to the integer
``closed_form`` decode (asserted by ``tests/test_mma.py`` and the plan
verifier's table re-derivation).

Everything here is pure jnp, so the same chains run on host for table
construction (``GridPlan.mma_table``) and inside jit.  The kernels bind
the chain *output* as a scalar-prefetch table (Mosaic index maps cannot
run ``dot_general``), so the decoded coordinates ride the existing
BlockCoords plumbing.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from . import fractal as F
from . import memo

#: Largest integer magnitude whose f32 sums stay exact.  Every basis
#: builder raises ``ValueError`` when a coordinate, slot, or linear
#: index could reach this bound.
DIGIT_BOUND = 1 << 24


def fractal_of(domain) -> Optional[Tuple[F.FractalSpec, int]]:
    """``(spec, r_b)`` of a fractal block domain, else ``None``.

    Mirrors ``CompactLayout._fractal_spec``: the classic gasket domain
    predates :class:`~repro.core.fractal.FractalSpec` and carries no
    ``.spec`` attribute."""
    from .domain import GeneralizedFractalDomain, SierpinskiDomain
    if isinstance(domain, SierpinskiDomain):
        return F.SIERPINSKI, domain.r_b
    if isinstance(domain, GeneralizedFractalDomain):
        return domain.spec, domain.r_b
    return None


def _check_bound(spec: F.FractalSpec, r: int) -> None:
    if spec.k ** r >= DIGIT_BOUND or spec.m ** r >= DIGIT_BOUND:
        raise ValueError(
            f"mma digit-basis for {spec.name} at r={r}: volume k^r="
            f"{spec.k ** r} / extent m^r={spec.m ** r} reaches 2^24; "
            f"f32 accumulation would stop being exact "
            f"(DIGIT_BOUND={DIGIT_BOUND})")


# ---------------------------------------------------------------------------
# Host-built digit-basis matrices (memoized on the spec via core.memo)
# ---------------------------------------------------------------------------

def coords_basis(spec: F.FractalSpec, r: int) -> np.ndarray:
    """(r, k, 2) f32 basis: digit c at level mu contributes the copy
    offset ``offsets[c] * m**(mu-1)`` to the embedded (bx, by) -- the
    weights of :meth:`FractalSpec.lambda_map_linear` as a matrix."""
    def build():
        _check_bound(spec, r)
        b = np.zeros((r, spec.k, 2), np.float32)
        for mu in range(1, r + 1):
            p = spec.m ** (mu - 1)
            for c, (ox, oy) in enumerate(spec.offsets):
                b[mu - 1, c, 0] = ox * p
                b[mu - 1, c, 1] = oy * p
        b.setflags(write=False)
        return b
    return memo.cached("mma-coords-basis", spec, (r,), build)


def slots_basis(spec: F.FractalSpec, r: int) -> np.ndarray:
    """(r, k, 2) f32 basis: digit c at level mu contributes to the
    orthotope (w_x, w_y) -- odd levels are base-k digits of w_y, even of
    w_x (the Lemma 2 alternating unrolling).  Contracting the digit
    one-hots of a *linear* index with this basis is
    ``deinterleave_linear``; contracting per-level *copy rows* (see
    :func:`copy_rows`) is ``lambda_inverse``."""
    def build():
        _check_bound(spec, r)
        b = np.zeros((r, spec.k, 2), np.float32)
        for mu in range(1, r + 1):
            for c in range(spec.k):
                if mu % 2 == 1:
                    b[mu - 1, c, 1] = c * spec.k ** ((mu - 1) // 2)
                else:
                    b[mu - 1, c, 0] = c * spec.k ** (mu // 2 - 1)
        b.setflags(write=False)
        return b
    return memo.cached("mma-slots-basis", spec, (r,), build)


def linear_basis(spec: F.FractalSpec, r: int) -> np.ndarray:
    """(r, k, 1) f32 basis: copy c at level mu contributes
    ``c * k**(mu-1)`` to the linear lambda-order index."""
    def build():
        _check_bound(spec, r)
        b = np.zeros((r, spec.k, 1), np.float32)
        for mu in range(1, r + 1):
            for c in range(spec.k):
                b[mu - 1, c, 0] = c * spec.k ** (mu - 1)
        b.setflags(write=False)
        return b
    return memo.cached("mma-linear-basis", spec, (r,), build)


def pair_basis(spec: F.FractalSpec) -> np.ndarray:
    """(m*m, k) f32 match matrix: base-m digit pair (dx, dy) -> one-hot
    copy row.  Pairs matching no copy offset give an all-zero row, which
    under every weighted contraction contributes nothing -- exactly the
    copy-0 fall-through of the integer ``lambda_inverse`` (copy 0 has
    contribution ``0 * weight``)."""
    def build():
        b = np.zeros((spec.m * spec.m, spec.k), np.float32)
        for c, (ox, oy) in enumerate(spec.offsets):
            b[oy * spec.m + ox, c] = 1.0
        b.setflags(write=False)
        return b
    return memo.cached("mma-pair-basis", spec, (), build)


# ---------------------------------------------------------------------------
# Chain evaluators (jnp; host numpy inputs and jitted arrays take the
# same path)
# ---------------------------------------------------------------------------

def _lift(a: np.ndarray) -> jnp.ndarray:
    """Lift a host basis array into the trace as *ops* (a stack of
    scalar constants), which Pallas kernel bodies accept where they
    reject captured array constants.  Scalar constants fold into the
    program; the stack/reshape
    re-materializes the (tiny) basis per trace, a fixed prologue cost
    next to the dot itself.  Outside a kernel (host table builds under
    ``ensure_compile_time_eval``, plain jit) this is just an eager
    constant."""
    if a.size == 0:
        return jnp.zeros(a.shape, a.dtype)
    flat = [jnp.asarray(v, a.dtype) for v in a.ravel().tolist()]
    return jnp.stack(flat).reshape(a.shape)


def _basis(b) -> jnp.ndarray:
    return _lift(b) if isinstance(b, np.ndarray) else jnp.asarray(b)


def _powers(base: int, levels: int) -> jnp.ndarray:
    return _lift(
        np.power(base, np.arange(levels, dtype=np.int64)).astype(np.int32))


def digit_onehot(v, base: int, levels: int) -> jnp.ndarray:
    """(..., levels, base) bf16 one-hot of the base-``base`` digits of
    an integer array (0/1 are exact in bf16)."""
    v = jnp.asarray(v)
    d = (v[..., None] // _powers(base, levels)) % base
    oh = d[..., None] == jnp.arange(base, dtype=jnp.int32)
    return oh.astype(jnp.bfloat16)


def _contract(onehot: jnp.ndarray, basis) -> jnp.ndarray:
    """Contract (..., L, B) digit one-hots with an (L, B, W) basis into
    (..., W) f32 -- the MMA: one (1, L*B) x (L*B, W) matmul per decode,
    batched over the leading dims."""
    nb = onehot.ndim - 2
    return lax.dot_general(
        onehot, _basis(basis),
        dimension_numbers=(((nb, nb + 1), (0, 1)), ((), ())),
        preferred_element_type=jnp.float32)


def decode_linear(spec: F.FractalSpec, r: int, i):
    """lambda over a linear grid index: MMA replica of
    :meth:`FractalSpec.lambda_map_linear` -> (bx, by) i32."""
    out = _contract(digit_onehot(i, spec.k, r), coords_basis(spec, r))
    return out[..., 0].astype(jnp.int32), out[..., 1].astype(jnp.int32)


def slots_of_linear(spec: F.FractalSpec, r: int, i, swap: bool = False):
    """Packed slot (sx, sy) of linear step i -- MMA replica of
    ``deinterleave_linear`` (the compact enumeration is lambda-linear,
    so the own slot never needs the inverse chain).  ``swap`` mirrors
    the odd-level ``SuperTiling.tile_index`` transpose."""
    out = _contract(digit_onehot(i, spec.k, r), slots_basis(spec, r))
    sx = out[..., 0].astype(jnp.int32)
    sy = out[..., 1].astype(jnp.int32)
    return (sy, sx) if swap else (sx, sy)


def copy_rows(spec: F.FractalSpec, r: int, x, y) -> jnp.ndarray:
    """(..., r, k) f32 per-level copy-index rows of embedded coords:
    base-m digit-pair one-hots contracted with the pair-match basis.
    Each row is one-hot (a matched pair) or all-zero (non-member level,
    the copy-0 fall-through)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    pows = _powers(spec.m, r)
    dx = (x[..., None] // pows) % spec.m
    dy = (y[..., None] // pows) % spec.m
    pr = dy * spec.m + dx
    oh = (pr[..., None] == jnp.arange(spec.m * spec.m, dtype=jnp.int32))
    oh = oh.astype(jnp.bfloat16)
    return lax.dot_general(
        oh, _basis(pair_basis(spec)),
        dimension_numbers=(((oh.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def member_of_rows(r: int, rows: jnp.ndarray):
    """Membership from copy rows: every level matched <=> the f32 sum of
    the (at most r) ones equals r -- value-equal to the domain's
    digit-pair / bit membership test."""
    return jnp.sum(rows, axis=(-2, -1)) == np.float32(r)


def inverse_slots(spec: F.FractalSpec, r: int, x, y, swap: bool = False):
    """MMA replica of :meth:`FractalSpec.lambda_inverse`: embedded
    coords -> packed orthotope slot (sx, sy).  Non-member inputs decode
    to some in-range slot (zero rows contribute nothing), exactly like
    the integer fall-through."""
    rows = copy_rows(spec, r, x, y)
    out = _contract(rows.astype(jnp.bfloat16), slots_basis(spec, r))
    sx = out[..., 0].astype(jnp.int32)
    sy = out[..., 1].astype(jnp.int32)
    return (sy, sx) if swap else (sx, sy)


def linear_of(spec: F.FractalSpec, r: int, x, y):
    """MMA replica of :meth:`FractalSpec.linear_index`."""
    rows = copy_rows(spec, r, x, y)
    out = _contract(rows.astype(jnp.bfloat16), linear_basis(spec, r))
    return out[..., 0].astype(jnp.int32)


def neighbor_slots(spec: F.FractalSpec, r: int, domain, bx, by,
                   dx: int, dy: int, swap: bool = False):
    """MMA replica of ``CompactLayout.neighbor_slot`` /
    ``SuperTiling.neighbor_tile``: the (dx, dy) neighbour of embedded
    (bx, by), clamped into the bounding box, membership-tested via the
    copy-row sum, resolved to its packed slot, and zeroed when invalid
    -- bit-for-bit the integer table entry."""
    nbx, nby = domain.bounding_box
    x = jnp.asarray(bx) + dx
    y = jnp.asarray(by) + dy
    xc = jnp.clip(x, 0, nbx - 1)
    yc = jnp.clip(y, 0, nby - 1)
    rows = copy_rows(spec, r, xc, yc)
    out = _contract(rows.astype(jnp.bfloat16), slots_basis(spec, r))
    sx = out[..., 0].astype(jnp.int32)
    sy = out[..., 1].astype(jnp.int32)
    if swap:
        sx, sy = sy, sx
    ok = (x >= 0) & (x < nbx) & (y >= 0) & (y < nby) \
        & member_of_rows(r, rows)
    zero = jnp.int32(0)
    return jnp.where(ok, sx, zero), jnp.where(ok, sy, zero), ok


# ---------------------------------------------------------------------------
# Non-fractal (attention / generic) domains: row-comparison chains
# ---------------------------------------------------------------------------

def row_basis(domain):
    """Host row tables of a row-major contiguous block domain:
    ``(starts, diff, ones)`` where ``starts`` is the (R+1,) i32 first
    linear index of each block row (``starts[R] = num_blocks``),
    ``diff[rho] = min_bx[rho] - starts[rho]`` (f32), and ``ones`` is the
    (R,) f32 summing vector.  Raises ``ValueError`` when the domain's
    canonical enumeration is not row-major with ascending-contiguous
    rows (every registered attention domain is)."""
    def build():
        coords = np.asarray(domain.coords_host(), np.int64)
        n = len(coords)
        nbx, nby = domain.bounding_box
        if n >= DIGIT_BOUND or nbx >= DIGIT_BOUND:
            raise ValueError(
                f"mma row basis: {n} blocks / width {nbx} reaches "
                f"2^24; f32 accumulation would stop being exact")
        bx, by = coords[:, 0], coords[:, 1]
        if np.any(np.diff(by) < 0):
            raise ValueError(
                "mma row basis: domain enumeration is not row-major")
        starts = np.searchsorted(by, np.arange(nby + 1)).astype(np.int64)
        lo = np.zeros(nby, np.int64)
        for rho in range(nby):
            s, e = int(starts[rho]), int(starts[rho + 1])
            if e == s:
                continue
            lo[rho] = bx[s]
            if not np.array_equal(bx[s:e],
                                  np.arange(lo[rho], lo[rho] + e - s)):
                raise ValueError(
                    f"mma row basis: block row {rho} is not a "
                    f"contiguous ascending span")
        out = (starts.astype(np.int32),
               (lo - starts[:-1]).astype(np.float32),
               np.ones(nby, np.float32))
        for a in out:
            a.setflags(write=False)
        return out
    return memo.cached("mma-row-basis", domain, (), build)


def decode_rows(domain, t):
    """Linear step -> (bx, by) for a row-major contiguous domain, as
    two dot products: the row index is the count of row starts at or
    below t (a comparison matrix contracted with ones, minus one), and
    the column is t plus the one-hot row's ``min_bx - start`` offset.
    Value-equal to ``domain.block_coords`` for t in [0, num_blocks)."""
    starts, diff, ones = row_basis(domain)
    si = _lift(starts)
    t = jnp.asarray(t)
    ge_lo = (t[..., None] >= si[:-1]).astype(jnp.bfloat16)
    ge_hi = (t[..., None] >= si[1:]).astype(jnp.bfloat16)

    def dot(a, b):
        return lax.dot_general(
            a, _basis(b),
            dimension_numbers=(((a.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    by = dot(ge_lo, ones) - np.float32(1.0)
    bx = t.astype(jnp.float32) + dot(ge_lo - ge_hi, diff)
    return bx.astype(jnp.int32), by.astype(jnp.int32)
