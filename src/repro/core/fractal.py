"""Faithful implementation of the paper's block-space Sierpinski map.

Notation follows Navarro, Bustos, Vega, Hitschfeld (2017),
"Block-space GPU Mapping for Embedded Sierpinski Gasket Fractals":

* the discrete gasket of scale level ``r`` lives embedded in an
  ``n x n`` grid with ``n = 2**r``, origin at the top-left, ``y``
  increasing downwards.  Membership test (paper SS III.D.3):
  ``x & (n - 1 - y) == 0``.
* the gasket packs into a 2-orthotope of ``3**ceil(r/2) x 3**floor(r/2)``
  blocks (Lemma 2) via an alternating base-3 digit unrolling: odd scale
  levels consume base-3 digits of ``w_y``, even levels of ``w_x``.
* ``lambda(w)`` (Eq. 4-10) accumulates, per scale level ``mu``, a region
  offset ``tau^mu = Delta_mu * 2**(mu-1)`` with region index
  ``beta_mu(w) in {0, 1, 2}`` (0 = top, 1 = bottom-left, 2 = bottom-right).

Everything here is pure index math on jnp int32 arrays so the same code
runs (a) on host for table construction, (b) inside jit, and (c) inside
Pallas ``BlockSpec.index_map`` scalar code (via the *_py variants which
unroll at trace time).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

HAUSDORFF = math.log2(3.0)  # H = log2(3) ~ 1.5849625 (Lemma 1)


# ---------------------------------------------------------------------------
# Scalar / host-side helpers
# ---------------------------------------------------------------------------

def scale_level(n: int) -> int:
    """r = log2(n); n must be a power of two (paper: r = log_{1/s}(n), s=1/2)."""
    r = int(round(math.log2(n)))
    if 2 ** r != n:
        raise ValueError(f"n={n} is not a power of two")
    return r


def gasket_volume(n: int) -> int:
    """V(F_n^{3,1/2}) = 3**r = n**H   (Lemma 1)."""
    return 3 ** scale_level(n)


def orthotope_shape(r: int) -> Tuple[int, int]:
    """Packing orthotope (width_x, height_y) of the level-r gasket (Lemma 2).

    Odd scale levels mu=1,3,5,... consume base-3 digits of w_y, so w_y has
    ceil(r/2) digits; even levels consume digits of w_x -> floor(r/2) digits.
    The orthotope is therefore 3**floor(r/2) wide and 3**ceil(r/2) tall,
    matching the paper's (quasi-)regular 3**ceil(r/2) x 3**floor(r/2) up to
    the (width, height) naming convention.
    """
    return 3 ** (r // 2), 3 ** ((r + 1) // 2)


def is_member(x, y, n: int):
    """Embedded-space membership bit test: x & (n - 1 - y) == 0.

    Apex at (0,0); left edge x == 0 always member; bottom row y == n-1 full.
    Works on python ints and jnp arrays alike.
    """
    return (x & (n - 1 - y)) == 0


# ---------------------------------------------------------------------------
# The paper's map, Eq. (4) - (10)
# ---------------------------------------------------------------------------

def beta_mu(wx, wy, mu: int):
    """Region index beta_mu(w) in {0,1,2} at scale level mu  (Eq. 4)."""
    sel = wx * ((mu + 1) % 2) + wy * (mu % 2)      # odd mu -> w_y, even -> w_x
    return (sel // 3 ** ((mu + 1) // 2 - 1)) % 3


def delta_mu(beta):
    """Offset weights (Delta_x, Delta_y) in {0,1}^2 for a region index (Eq. 5)."""
    dx = beta // 2
    dy = beta - dx
    return dx, dy


def lambda_map(wx, wy, r: int):
    """lambda(w): orthotope block coords -> embedded fractal block coords.

    Faithful Eq. (8)-(10): sum over scale levels mu = 1..r of
    tau^mu = Delta_mu * 2**(mu-1).  The mu loop is unrolled at trace time
    (r is static), so inside jit/Pallas-index_map this is straight-line
    scalar int math -- the TPU analogue of the paper's per-block map.

    Accepts ints or jnp int arrays (vectorized over w).
    """
    lx = wx * 0
    ly = wy * 0
    for mu in range(1, r + 1):
        b = beta_mu(wx, wy, mu)
        dx, dy = delta_mu(b)
        lx = lx + dx * 2 ** (mu - 1)
        ly = ly + dy * 2 ** (mu - 1)
    return lx, ly


def lambda_map_linear(i, r: int):
    """lambda over a *linear* grid index i in [0, 3**r).

    Pallas grids are iterated linearly; rather than first splitting i into
    (w_x, w_y) and re-extracting alternating base-3 digits, note that the
    digit stream of i in base 3 IS the sequence (beta_1, beta_2, ..., beta_r)
    under the paper's alternating unrolling (odd digits come from w_y, even
    from w_x; concatenating them is exactly i = interleave(w_y, w_x) in
    base 3).  This is the same bijection with one fewer divmod chain.
    """
    lx = i * 0
    ly = i * 0
    for mu in range(1, r + 1):
        b = (i // 3 ** (mu - 1)) % 3
        dx, dy = delta_mu(b)
        lx = lx + dx * 2 ** (mu - 1)
        ly = ly + dy * 2 ** (mu - 1)
    return lx, ly


def lambda_inverse(x, y, r: int):
    """Inverse map: embedded fractal block coords -> orthotope coords.

    For each scale level mu the region is recovered from bit mu-1 of (x, y):
    (0,0) -> beta 0, (0,1) -> beta 1, (1,1) -> beta 2.  ((1,0) never occurs
    for members.)  The betas are then re-packed into the alternating base-3
    digits of (w_x, w_y).
    """
    wx = x * 0
    wy = y * 0
    px = x * 0 + 1  # 3**(even-digit position)
    py = y * 0 + 1
    for mu in range(1, r + 1):
        bx = (x >> (mu - 1)) & 1
        by = (y >> (mu - 1)) & 1
        b = bx + by  # (0,0)->0 (0,1)->1 (1,1)->2
        if mu % 2 == 1:
            wy = wy + b * py
            py = py * 3
        else:
            wx = wx + b * px
            px = px * 3
    return wx, wy


# ---------------------------------------------------------------------------
# Generalized F^{k,s} fractals (paper SS V, future-work question 1)
# ---------------------------------------------------------------------------

class FractalSpec:
    """A self-similar fractal built from k copies at scale s with integer
    per-copy offsets, generalizing the gasket's (k=3, s=1/2).

    offsets: tuple of (dx, dy) unit offsets in {0..m-1}^2 where m = 1/s is
    the integer subdivision factor.  Level-mu copy c sits at
    offsets[c] * m**(mu-1).
    """

    def __init__(self, name: str, k: int, m: int, offsets):
        if len(offsets) != k:
            raise ValueError("need one offset per copy")
        self.name, self.k, self.m = name, k, m
        self.offsets = tuple(tuple(o) for o in offsets)
        self._grid_cache = {}  # n -> dense membership grid (oracle)

    @property
    def hausdorff(self) -> float:
        return math.log(self.k) / math.log(self.m)

    @property
    def cache_key(self):
        """Value identity for :mod:`repro.core.memo`: the mma digit-basis
        builders memoize per spec geometry, not per instance."""
        return ("fractal-spec", self.name, self.k, self.m, self.offsets)

    def scale_level(self, n: int) -> int:
        r = int(round(math.log(n, self.m)))
        if self.m ** r != n:
            raise ValueError(f"n={n} is not a power of m={self.m}")
        return r

    def volume(self, n: int) -> int:
        return self.k ** self.scale_level(n)

    def lambda_map_linear(self, i, r: int):
        """Generalized digit-unrolled map: base-k digits of i choose copies.

        The copy-offset lookup is a select chain over the k static
        offsets (not a gather from a table), so the same code runs on
        host ints/numpy AND inside Pallas ``BlockSpec.index_map`` scalar
        code, which must not capture array constants."""
        where = np.where if isinstance(i, (int, np.integer, np.ndarray)) \
            else jnp.where
        lx = i * 0
        ly = i * 0
        for mu in range(1, r + 1):
            c = (i // self.k ** (mu - 1)) % self.k
            dx, dy = c * 0, c * 0
            for j, (ox, oy) in enumerate(self.offsets):
                dx = where(c == j, ox, dx)
                dy = where(c == j, oy, dy)
            lx = lx + dx * self.m ** (mu - 1)
            ly = ly + dy * self.m ** (mu - 1)
        return lx, ly

    def lambda_map(self, wx, wy, r: int):
        """Generalized lambda over *orthotope* coords (w_x, w_y) ->
        embedded fractal coords, the F^{k,s} analogue of module-level
        :func:`lambda_map`: odd scale levels mu = 1, 3, ... consume
        base-k digits of w_y, even levels of w_x (the Lemma 2
        alternating unrolling).  Straight-line int math usable on host
        ints/numpy and inside Pallas index maps; this is the decode the
        sharded orthotope-row-slab enumeration runs (row-major over
        packed slots instead of over the linear lambda order)."""
        host = all(isinstance(v, (int, np.integer, np.ndarray))
                   for v in (wx, wy))
        where = np.where if host else jnp.where
        lx = wx * 0
        ly = wy * 0
        for mu in range(1, r + 1):
            if mu % 2 == 1:
                c = (wy // self.k ** ((mu - 1) // 2)) % self.k
            else:
                c = (wx // self.k ** (mu // 2 - 1)) % self.k
            dx, dy = c * 0, c * 0
            for j, (ox, oy) in enumerate(self.offsets):
                dx = where(c == j, ox, dx)
                dy = where(c == j, oy, dy)
            lx = lx + dx * self.m ** (mu - 1)
            ly = ly + dy * self.m ** (mu - 1)
        return lx, ly

    def lambda_inverse(self, x, y, r: int):
        """Inverse map: embedded fractal coords -> orthotope coords.

        Per scale level mu the copy index c is recovered by matching the
        base-m digit pair of (x, y) against the copy offsets (a select
        chain, so the same code runs on host ints/numpy and traced); the
        copy indices are then re-packed into the alternating base-k
        digits of (w_x, w_y), generalizing the gasket's bit-pair trick.
        Non-member inputs decode to *some* in-range orthotope coordinate
        (unmatched digit pairs fall through to copy 0), which is exactly
        what a clamped compact-storage index map needs.
        """
        host = all(isinstance(v, (int, np.integer, np.ndarray))
                   for v in (x, y))
        where = np.where if host else jnp.where
        wx = x * 0
        wy = y * 0
        px = x * 0 + 1   # k**(even-digit position)
        py = y * 0 + 1
        for mu in range(1, r + 1):
            p = self.m ** (mu - 1)
            dx = (x // p) % self.m
            dy = (y // p) % self.m
            c = x * 0
            for j, (ox, oy) in enumerate(self.offsets):
                c = where((dx == ox) & (dy == oy), j, c)
            if mu % 2 == 1:
                wy = wy + c * py
                py = py * self.k
            else:
                wx = wx + c * px
                px = px * self.k
        return wx, wy

    def linear_index(self, x, y, r: int):
        """Embedded fractal coords -> linear index in lambda order (the
        inverse of :meth:`lambda_map_linear`); copy indices become the
        base-k digits of i."""
        host = all(isinstance(v, (int, np.integer, np.ndarray))
                   for v in (x, y))
        where = np.where if host else jnp.where
        i = x * 0
        for mu in range(1, r + 1):
            p = self.m ** (mu - 1)
            dx = (x // p) % self.m
            dy = (y // p) % self.m
            c = x * 0
            for j, (ox, oy) in enumerate(self.offsets):
                c = where((dx == ox) & (dy == oy), j, c)
            i = i + c * self.k ** (mu - 1)
        return i

    def nearest_row_member(self, x, y, r: int):
        """The member of row ``y`` at or before ``x`` in row-major order,
        or the row's first member when none precedes ``x`` (traceable).

        Digit by digit from the top: the candidate that keeps the
        longest member prefix of ``x``, lowers the next digit to the
        largest allowed one below it and fills the digits under it with
        their largest allowed values.  Every row must hold a member."""
        rows = {dy: sorted(ox for ox, oy in self.offsets if oy == dy)
                for dy in range(self.m)}
        if not all(rows.values()):
            raise ValueError(f"{self.name}: some rows hold no member")
        host = all(isinstance(v, (int, np.integer, np.ndarray))
                   for v in (x, y))
        where = np.where if host else jnp.where

        def pick(dy, fn):   # per-row-digit static value as a select chain
            v = dy * 0 + fn(rows[0])
            for d in range(1, self.m):
                v = where(dy == d, fn(rows[d]), v)
            return v

        dxs = [(x // self.m ** mu) % self.m for mu in range(r)]
        dys = [(y // self.m ** mu) % self.m for mu in range(r)]
        low_max = [pick(dys[mu], max) * self.m ** mu for mu in range(r)]
        out = sum((pick(dys[mu], min) * self.m ** mu for mu in range(r)),
                  x * 0)
        prefix_ok, prefix = True, x * 0
        for lvl in reversed(range(r)):
            below = x * 0 - 1
            for d in range(self.m - 1):     # ascending: the largest wins
                allowed = None
                for oy in (oy for ox, oy in self.offsets if ox == d):
                    hit = dys[lvl] == oy
                    allowed = hit if allowed is None else allowed | hit
                if allowed is not None:
                    below = where(allowed & (d < dxs[lvl]), d, below)
            cand = prefix + below * self.m ** lvl + sum(low_max[:lvl],
                                                         x * 0)
            out = where(prefix_ok & (below >= 0), cand, out)
            ok = None
            for ox, oy in self.offsets:
                hit = (dxs[lvl] == ox) & (dys[lvl] == oy)
                ok = hit if ok is None else ok | hit
            prefix_ok = prefix_ok & ok
            prefix = prefix + dxs[lvl] * self.m ** lvl
        return where(prefix_ok, x, out), y

    def orthotope_shape(self, r: int) -> Tuple[int, int]:
        """Packing orthotope (width_x, height_y): k**floor(r/2) wide by
        k**ceil(r/2) tall (Lemma 2 generalized to F^{k,s})."""
        return self.k ** (r // 2), self.k ** ((r + 1) // 2)

    def is_member(self, x, y, n: int):
        """Traceable membership test: (x, y) is in the level-r fractal iff
        every base-m digit pair of (x, y) is one of the copy offsets.

        Generalizes the gasket's O(1) bit test to any F^{k,s}: O(r * k)
        straight-line int ops, usable on python ints, jnp arrays, and
        inside Pallas kernels / index maps (no dense grid needed)."""
        r = self.scale_level(n)
        ok = None
        for mu in range(r):
            p = self.m ** mu
            dx = (x // p) % self.m
            dy = (y // p) % self.m
            lvl = None
            for (ox, oy) in self.offsets:
                hit = (dx == ox) & (dy == oy)
                lvl = hit if lvl is None else (lvl | hit)
            ok = lvl if ok is None else (ok & lvl)
        if ok is None:  # r == 0: the single cell is the whole fractal
            ok = (x == 0) & (y == 0)
        return ok

    def membership_grid(self, n: int) -> np.ndarray:
        """Dense boolean n x n occupancy via recursive construction (oracle).
        Memoized per instance: re-entered per traced index_map call."""
        if n in self._grid_cache:
            return self._grid_cache[n]
        r = self.scale_level(n)
        g = np.ones((1, 1), dtype=bool)
        for mu in range(1, r + 1):
            size = self.m ** (mu - 1)
            big = np.zeros((size * self.m, size * self.m), dtype=bool)
            for (dx, dy) in self.offsets:
                big[dy * size:(dy + 1) * size, dx * size:(dx + 1) * size] |= g
            g = big
        g.setflags(write=False)
        self._grid_cache[n] = g
        return g


SIERPINSKI = FractalSpec("sierpinski-gasket", k=3, m=2,
                         offsets=((0, 0), (0, 1), (1, 1)))
# Sierpinski carpet: 8 copies at 1/3 scale (center removed), H = log3(8).
CARPET = FractalSpec("sierpinski-carpet", k=8, m=3,
                     offsets=((0, 0), (1, 0), (2, 0),
                              (0, 1), (2, 1),
                              (0, 2), (1, 2), (2, 2)))
# Vicsek cross: 5 copies at 1/3 scale, H = log3(5).
VICSEK = FractalSpec("vicsek-cross", k=5, m=3,
                     offsets=((1, 0), (0, 1), (1, 1), (2, 1), (1, 2)))

FRACTALS = {f.name: f for f in (SIERPINSKI, CARPET, VICSEK)}


def deinterleave_linear(i, k: int, r: int):
    """Linear lambda-order index -> orthotope coords (w_x, w_y).

    The base-k digit stream of i is the alternating digit unrolling of
    (w_y, w_x) (odd scale levels mu = 1, 3, ... are digits of w_y, even
    of w_x), so de-interleaving i's digits recovers the Lemma 2 packing
    coordinate without going through embedded space."""
    wx = i * 0
    wy = i * 0
    px = i * 0 + 1
    py = i * 0 + 1
    for mu in range(1, r + 1):
        d = (i // k ** (mu - 1)) % k
        if mu % 2 == 1:
            wy = wy + d * py
            py = py * k
        else:
            wx = wx + d * px
            px = px * k
    return wx, wy


# ---------------------------------------------------------------------------
# Vectorized/device utilities
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("r",))
def all_block_coords(r: int) -> jnp.ndarray:
    """(3**r, 2) int32 array of embedded coords for every gasket block,
    enumerated in linear lambda order (the canonical compact layout order).
    """
    i = jnp.arange(3 ** r, dtype=jnp.int32)
    lx, ly = lambda_map_linear(i, r)
    return jnp.stack([lx, ly], axis=-1)


def membership_grid(n: int) -> np.ndarray:
    """Dense boolean occupancy of the embedded gasket via the bit test
    (broadcast rows x columns: no n x n index grids)."""
    y = np.arange(n)[:, None]
    x = np.arange(n)[None, :]
    return (x & (n - 1 - y)) == 0


def pack_to_orthotope(grid: jnp.ndarray, r: int) -> jnp.ndarray:
    """Gather an embedded n x n array into the compact (3**ceil, 3**floor)
    orthotope layout (Lemma 2).  grid[y, x] -> packed[w_y, w_x]."""
    ox, oy = orthotope_shape(r)
    wy, wx = jnp.mgrid[0:oy, 0:ox]
    lx, ly = lambda_map(wx, wy, r)
    return grid[ly, lx]


def unpack_from_orthotope(packed: jnp.ndarray, r: int, n: int,
                          fill=0) -> jnp.ndarray:
    """Scatter the compact orthotope layout back into the embedded n x n."""
    ox, oy = orthotope_shape(r)
    wy, wx = jnp.mgrid[0:oy, 0:ox]
    lx, ly = lambda_map(wx, wy, r)
    out = jnp.full((n, n) + packed.shape[2:], fill, dtype=packed.dtype)
    return out.at[ly, lx].set(packed)
