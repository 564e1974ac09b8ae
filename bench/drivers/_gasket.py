"""What the gasket cells share: the compact state made from the seed on
the device, the measured window of back-to-back calls, and the
comparison of whole packed states with the reference.  All of it is the
benchmark's own (bench.reference.gasket), not the program's layout
code."""
from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import gasket as ref
from bench.reference import prng_key

#: calls in flight in the window: the host waits on the call this many
#: back, so a stall of the shared host does not drain the chip while the
#: window still closes soon after its time is up
QUEUE_DEPTH = 6
#: packed blocks the diffusion reference runs at once
REF_BATCH = 1024


class Gasket:
    """A compact gasket state of side ``n`` in ``block`` x ``block``
    blocks, with the tables that place each packed block in the
    embedding and find its neighbours."""

    def __init__(self, config: dict, seed: int):
        self.n, self.block = int(config["n"]), int(config["block"])
        self.r = (self.n // self.block).bit_length() - 1
        if self.block << self.r != self.n:
            raise ValueError(f"n={self.n} is not block={self.block} times "
                             f"a power of two")
        self.dtype = jnp.dtype(config["dtype"])
        rows, cols = ref.orthotope(self.r)
        self.shape = (rows * self.block, cols * self.block)
        self.stored_bytes = self.shape[0] * self.shape[1] \
            * self.dtype.itemsize
        self.members = 3 ** (self.n.bit_length() - 1)
        bx, by = ref.slot_blocks(self.r)
        self._bx, self._by = (jnp.asarray(a, jnp.int32) for a in (bx, by))
        self._key = prng_key(seed, 0)
        # every packed block (row-major) with its 3 x 3 embedded
        # neighbourhood in packed coordinates (-1: none), in batches of
        # REF_BATCH (the last padded with block 0, harmless for a max)
        nb = 1 << self.r
        sy, sx = ref.block_slots(self.r)
        wy, wx = np.divmod(np.arange(rows * cols), cols)
        ex, ey = bx[wy, wx], by[wy, wx]
        offs = np.arange(-1, 2)
        hx = ex[:, None, None] + offs[None, None, :]
        hy = ey[:, None, None] + offs[None, :, None]
        inside = (hx >= 0) & (hx < nb) & (hy >= 0) & (hy < nb)
        hxc, hyc = np.clip(hx, 0, nb - 1), np.clip(hy, 0, nb - 1)
        ok = inside & (sy[hyc, hxc] >= 0)
        table = dict(wy=wy, wx=wx, ex=ex, ey=ey,
                     ny=np.where(ok, sy[hyc, hxc], 0),
                     nx=np.where(ok, sx[hyc, hxc], 0))
        table = {k: a.astype(np.int32) for k, a in table.items()}
        table["ok"] = ok
        k = min(REF_BATCH, len(wy))
        pad = -len(wy) % k
        self._blocks = {
            name: jnp.asarray(np.concatenate(
                [a, np.repeat(a[:1], pad, axis=0)]).reshape(
                    (-1, k) + a.shape[1:]))
            for name, a in table.items()}

    def initial_state(self, *, members_only: bool = True):
        """Uniform [0, 1) values in the packed layout, made in one jitted
        call on the device: on the gasket's cells only (0 elsewhere), or
        on every stored cell."""
        return _initial_state(self._key, self._bx, self._by, n=self.n,
                              block=self.block, dtype=self.dtype,
                              members_only=members_only)

    def write_error(self, before, after, value, *, control: bool):
        """Largest |after - reference write of ``value`` on ``before``|
        over every stored cell; under ``control`` the reference computed
        in bfloat16 stands in for ``after``.  Its input is rounded in a
        call of its own: inside one program the chip's compiler may drop
        a round trip through bfloat16."""
        low = before.astype(jnp.bfloat16) if control else None
        return float(_write_error(before, after, low, self._bx, self._by,
                                  jnp.asarray(value, before.dtype),
                                  n=self.n, block=self.block))

    def diffusion_error(self, before, after, *, steps: int, alpha: float,
                        control: bool):
        """Largest |after - ``steps`` reference diffusion steps from
        ``before``| over every stored cell, each packed block computed on
        its window of ``steps`` cells beyond the block (exact there);
        under ``control`` the reference in bfloat16 stands in for
        ``after``."""
        return float(_diffusion_error(before, after, self._blocks, n=self.n,
                                      block=self.block, steps=steps,
                                      alpha=alpha, control=control))


@functools.partial(jax.jit, static_argnames=("n", "block", "dtype",
                                             "members_only"))
def _initial_state(key, bx, by, *, n, block, dtype, members_only):
    rows, cols = bx.shape
    u = jax.random.uniform(key, (rows, block, cols, block), dtype)
    if members_only:
        u = jnp.where(_packed_member(bx, by, n=n, block=block), u,
                      0).astype(dtype)
    return u.reshape(rows * block, cols * block)


def _packed_member(bx, by, *, n, block):
    """(rows, block, cols, block) membership of every stored cell."""
    i = jnp.arange(block, dtype=jnp.int32)
    x = bx[:, None, :, None] * block + i[None, None, None, :]
    y = by[:, None, :, None] * block + i[None, :, None, None]
    return ref.member(x, y, n)


@functools.partial(jax.jit, static_argnames=("n", "block"))
def _write_error(before, after, low, bx, by, value, *, n, block):
    rows, cols = bx.shape
    shape4 = (rows, block, cols, block)
    mem = _packed_member(bx, by, n=n, block=block)
    want = jnp.where(mem, value, before.reshape(shape4))
    if low is not None:
        got = jnp.where(mem, value.astype(low.dtype),
                        low.reshape(shape4)).astype(want.dtype)
    else:
        got = after.reshape(shape4)
    return jnp.max(jnp.abs(got - want))          # a NaN propagates


def _tile(packed, y, x, block):
    return jax.lax.dynamic_slice(packed, (y * block, x * block),
                                 (block, block))


@functools.partial(jax.jit, static_argnames=("n", "block", "steps", "alpha",
                                             "control"))
def _diffusion_error(before, after, blocks, *, n, block, steps, alpha,
                     control):
    b, s = block, steps

    def batch(t):
        # each block's 3 x 3 neighbourhood read from the packed state,
        # cut to the window of s cells beyond the block
        tiles = jax.vmap(jax.vmap(jax.vmap(
            lambda y, x: _tile(before, y, x, b))))(t["ny"], t["nx"])
        tiles = jnp.where(t["ok"][..., None, None], tiles, 0)
        k = tiles.shape[0]
        win = tiles.transpose(0, 1, 3, 2, 4).reshape(k, 3 * b, 3 * b)
        win = win[:, b - s:2 * b + s, b - s:2 * b + s]
        x0, y0 = t["ex"] * b - s, t["ey"] * b - s

        def reference(w):
            return jax.vmap(lambda w, x0, y0: ref.diffusion_window(
                w, x0, y0, n=n, steps=s, alpha=alpha))(
                    w, x0, y0)[:, s:s + b, s:s + b].astype(before.dtype)

        want = reference(win)
        got = reference(win.astype(jnp.bfloat16)) if control else \
            jax.vmap(lambda y, x: _tile(after, y, x, b))(t["wy"], t["wx"])
        return jnp.max(jnp.abs(got - want))      # a NaN propagates

    return jnp.max(jax.lax.map(batch, blocks))


def _mark(state):
    """A tiny result of ``state`` for the host to wait on: the state
    itself may be donated to the next call before the wait."""
    return state[:1, :1]


def timed_calls(h, call, state, *, compared: int):
    """The measured window for a driver whose unit of work is one call of
    the program's entry on the whole state: ``call(state, j) -> state``,
    dispatched back to back with ``QUEUE_DEPTH`` calls in flight.  Once
    the time is up, the queue drains and ``compared`` more calls close
    the window, the input of each copied first (the program may donate
    it).  The drain keeps the copies from adding to the memory the
    queue holds.  Returns (state, calls, seconds, pairs): ``pairs`` holds
    (call index, input, output) of those last calls, whole, for the
    comparison."""
    jax.block_until_ready((jnp.copy(state), _mark(state)))  # set-up: compiles
    marks = collections.deque()
    secs = h.window_seconds()
    calls, kept, left = 0, [], None
    with h.window():
        t0 = time.perf_counter()
        while left != 0:
            if left is None and time.perf_counter() - t0 >= secs:
                left = compared
                jax.block_until_ready(list(marks))
                marks.clear()
            if left is not None:
                kept.append((calls, jnp.copy(state)))
                left -= 1
            state = call(state, calls)
            calls += 1
            marks.append(_mark(state))
            if len(marks) > QUEUE_DEPTH:
                jax.block_until_ready(marks.popleft())
        jax.block_until_ready(state)
        seconds = time.perf_counter() - t0
    outs = [before for _, before in kept[1:]] + [state]
    pairs = [(j, before, after) for (j, before), after in zip(kept, outs)]
    return state, calls, seconds, pairs
