"""The Sierpinski gasket, its compact storage and the two kernels' plain
semantics, written from the paper (Navarro et al., arXiv:1706.04552):

- membership of cell (x, y) of the n x n embedding, apex at (0, 0):
  ``x & (n - 1 - y) == 0``;
- the compact (Lemma 2) storage: packed block (wx, wy) of the
  3**floor(r/2) x 3**ceil(r/2) orthotope holds the embedded block
  lambda(w) of Eq. (8)-(10), whose region index at scale level mu is the
  base-3 digit of w_y (odd mu) or w_x (even mu);
- the write: every gasket cell takes the value, every other cell keeps
  its input;
- heat diffusion: s' = s + alpha * sum over gasket neighbours (nbr - s),
  with the neighbour sum taken north, south, west, east in that order;
  cells off the gasket stay 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def member(x, y, n: int):
    """Gasket membership of embedded cell (x, y); False off the n x n box."""
    inside = (x >= 0) & (x < n) & (y >= 0) & (y < n)
    return inside & ((x & (n - 1 - y)) == 0)


def orthotope(r: int):
    """(rows, cols) of the packed block grid at level r = log2(n/block)."""
    return 3 ** ((r + 1) // 2), 3 ** (r // 2)


def slot_blocks(r: int):
    """(bx, by) int64 grids over the packed block grid: the embedded block
    that packed block (wx, wy) holds, lambda(w) of Eq. (8)-(10)."""
    rows, cols = orthotope(r)
    wy, wx = np.mgrid[0:rows, 0:cols].astype(np.int64)
    bx = np.zeros_like(wx)
    by = np.zeros_like(wy)
    for mu in range(1, r + 1):
        digit = wy if mu % 2 else wx
        beta = (digit // 3 ** ((mu + 1) // 2 - 1)) % 3
        dx = beta // 2
        bx += dx << (mu - 1)
        by += (beta - dx) << (mu - 1)
    return bx, by


def block_slots(r: int):
    """(nb, nb) grids (wy, wx) of the packed block that holds embedded block
    (bx, by) at [by, bx]; -1 where the block is not on the gasket."""
    nb = 1 << r
    bx, by = slot_blocks(r)
    rows, cols = orthotope(r)
    wy, wx = np.mgrid[0:rows, 0:cols]
    sy = np.full((nb, nb), -1, np.int64)
    sx = np.full((nb, nb), -1, np.int64)
    sy[by, bx] = wy
    sx[by, bx] = wx
    return sy, sx


# ---------------------------------------------------------------------------
# the embedded n x n state (tests compare the program's embedded runs)
# ---------------------------------------------------------------------------

def membership_grid(n: int) -> np.ndarray:
    y = np.arange(n)[:, None]
    x = np.arange(n)[None, :]
    return member(x, y, n)


def write_ref(m, value):
    """The write on the whole embedded state."""
    mask = jnp.asarray(membership_grid(m.shape[0]))
    return jnp.where(mask, jnp.asarray(value, m.dtype), m)


def _shift(a, dy: int, dx: int):
    """Value of the (-dy, -dx) neighbour at each cell: ``out[i, j] =
    a[i - dy, j - dx]``, 0 where that lies outside the array."""
    h, w = a.shape
    p = jnp.pad(a, ((1, 1), (1, 1)))
    return p[1 - dy:1 - dy + h, 1 - dx:1 - dx + w]


#: north, south, west, east: the order the neighbour sum is taken in
_NSWE = ((1, 0), (-1, 0), (0, 1), (0, -1))


def _diffusion_step(s, mem, deg, alpha):
    nsum = _shift(s, *_NSWE[0]) + _shift(s, *_NSWE[1]) \
        + _shift(s, *_NSWE[2]) + _shift(s, *_NSWE[3])
    new = s + alpha * (nsum - deg * s)
    return jnp.where(mem, new, 0).astype(s.dtype)


def ca_step_ref(state, alpha: float = 0.25):
    """One heat-diffusion step on the whole embedded state."""
    n = state.shape[0]
    mem = jnp.asarray(membership_grid(n))
    m = mem.astype(state.dtype)
    deg = _shift(m, *_NSWE[0]) + _shift(m, *_NSWE[1]) \
        + _shift(m, *_NSWE[2]) + _shift(m, *_NSWE[3])
    return _diffusion_step(state, mem, deg, jnp.asarray(alpha, state.dtype))


# ---------------------------------------------------------------------------
# windows: the references as the benchmark runs them at the cell's size
# ---------------------------------------------------------------------------

def _window_coords(shape, x0, y0):
    h, w = shape
    y = y0 + jax.lax.broadcasted_iota(jnp.int32, (h, w), 0)
    x = x0 + jax.lax.broadcasted_iota(jnp.int32, (h, w), 1)
    return x, y


def diffusion_window(win, x0, y0, *, n: int, steps: int, alpha: float):
    """``steps`` diffusion steps on a window of the embedded state whose
    top-left cell is (x0, y0).  Membership and the neighbour degree come
    from the global coordinates; values beyond the window count as 0, so
    after ``steps`` steps every cell more than ``steps`` cells inside the
    window's edge is exact.  Computed in ``win.dtype``."""
    x, y = _window_coords(win.shape, x0, y0)
    mem = member(x, y, n)
    deg = sum(member(x - dx, y - dy, n).astype(win.dtype)
              for dy, dx in _NSWE)
    al = jnp.asarray(alpha, win.dtype)
    s = jnp.where(mem, win, 0).astype(win.dtype)
    return jax.lax.fori_loop(
        0, steps, lambda i, v: _diffusion_step(v, mem, deg, al), s)


def write_window(win, x0, y0, *, n: int, value):
    """The write on a window whose top-left cell is (x0, y0)."""
    x, y = _window_coords(win.shape, x0, y0)
    return jnp.where(member(x, y, n), jnp.asarray(value, win.dtype), win)
