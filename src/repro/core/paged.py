"""Paged block-space KV cache: the lambda-map trick applied to serving.

The paper's central move -- addressing a compact O(n^H) store through a
cheap index translation instead of materializing the bounding box -- is
structurally the same indirection a paged KV cache needs: a per-slot
table from *logical* key blocks to *physical* pages, read per grid step.
This module supplies the three pieces:

``PagedPlan``
    A :class:`~repro.core.plan.GridPlan` whose scalar-prefetch operands
    are led by the page table.  A page-table row per query slot is the
    same shape as the 28-col neighbour LUT the engine already prefetches
    (one i32 row per scheduled block), so the table rides the existing
    mechanism unchanged: it is prefetch operand 0, readable from
    BlockSpec index maps -- exactly how the decode LUT already travels
    (:mod:`repro.core.backend`).  The base plan's own LUT (when the
    lowering is table-backed) stays the *last* prefetch ref, so
    ``GridPlan._decode`` works untouched.

``PagedKVPool``
    The host-side allocator: a free list over physical pages with page 0
    reserved as the *null page* -- inactive slots route their writes
    there and no reader ever dereferences it, so fully-batched scatters
    need no host-side compaction.  Fragmentation statistics
    (``stats()``) feed the serving benchmarks.

Device-side layout helpers
    The pool array is ``(num_pages, 2*Hkv, page_size, d)`` with the K
    and V heads *interleaved* on the head axis (``[K0,V0,K1,V1,...]``):
    one physical page holds every head's K and V of its tokens, so the
    decode kernel copies it in one ``(1, 2*Hkv, page_size, d)`` block
    per (slot, page).  :func:`fuse_kv` / :func:`split_kv` convert
    between this layout and the separate ``(B, Hkv, S, d)`` caches;
    :func:`gather_kv` is the XLA gather that reconstructs a contiguous
    cache from the pool (the oracle the bit-identity tests and the
    degradation ladder's paged-xla rung share); :func:`append_token` /
    :func:`write_prefill_pages` are the scatter writes the serving
    decode/prefill steps use.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from .plan import GridPlan

#: physical page 0 is never allocated: it is the write target of
#: inactive slots (masked scatters) and the pad entry of page tables.
NULL_PAGE = 0


class PagedPlan(GridPlan):
    """A GridPlan whose prefetch operands are led by the page table.

    ``page_table`` is the ``(num_slots, max_pages)`` i32 device array
    (or tracer: the plan is built inside the kernel's jit trace, where
    the table is an argument).  ``num_scalar_prefetch`` grows by one and
    ``bound_prefetch`` prepends the table, so the emitter routes it
    exactly like the decode LUT, through scalar prefetch.  Index maps
    reach it as ``refs[0]`` (see :meth:`GridPlan._index_spec`); the
    base LUT, when the lowering is table-backed, remains ``refs[-1]``
    so the inherited decode is untouched.  ``seq_pos``, the
    ``(num_slots,)`` i32 decode positions, when given rides as
    ``refs[1]``, so index maps can bound a slot's pages by its
    length."""

    def __init__(self, *args, page_table=None, seq_pos=None, **kwargs):
        super().__init__(*args, **kwargs)
        if page_table is None:
            raise ValueError("PagedPlan requires page_table=")
        self.page_table = page_table
        self.seq_pos = seq_pos

    @property
    def _lead(self) -> tuple:
        if self.seq_pos is None:
            return (self.page_table,)
        return (self.page_table, self.seq_pos)

    @property
    def num_scalar_prefetch(self) -> int:
        return super().num_scalar_prefetch + len(self._lead)

    def bound_prefetch(self):
        # not super(): the base implementation keys off the (now larger)
        # num_scalar_prefetch and would bind a table for non-table
        # lowerings too.  The base LUT binds iff the base decode is
        # table-backed, and always *after* the leading operands.
        base = ()
        if self._table_backed:
            base = (self.mma_table() if self.lowering == "mma"
                    else self.lut(),)
        return self._lead + base


# ---------------------------------------------------------------------------
# host-side allocator
# ---------------------------------------------------------------------------

class PagedKVPool:
    """Free-list page allocator for one serving process.

    Pure host bookkeeping: the device pool array itself is threaded
    through the jitted decode step by the caller.  Page 0 is reserved
    (:data:`NULL_PAGE`).  Allocation hands out the lowest-numbered free
    pages first, which keeps reuse tight after churn; ``stats`` reports
    the fragmentation the benchmarks track."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._free = sorted(range(1, self.num_pages), reverse=True)
        self._used: set[int] = set()

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._used)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> Optional[list[int]]:
        """``n`` physical pages, or ``None`` when the pool cannot serve
        the request (the scheduler's admission signal -- never a raise:
        running out of pages is a load condition, not a bug)."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._used.update(pages)
        return pages

    def free(self, pages: Sequence[int]) -> None:
        for p in pages:
            if p == NULL_PAGE:
                continue
            if p not in self._used:
                raise ValueError(f"double free of page {p}")
            self._used.discard(p)
            self._free.append(p)
        self._free.sort(reverse=True)

    def stats(self, seq_lens: Sequence[int] = ()) -> dict:
        """Occupancy + fragmentation.  ``seq_lens`` are the live
        sequence lengths; *internal fragmentation* is the fraction of
        allocated token slots no live token occupies (the tail waste of
        partially-filled last pages), which a contiguous max-len
        preallocation drives toward 1 on mixed-length traffic."""
        cap = self.num_pages - 1
        used = len(self._used)
        tokens = int(sum(seq_lens))
        alloc_tokens = used * self.page_size
        return {
            "num_pages": cap,
            "used_pages": used,
            "free_pages": len(self._free),
            "utilization": used / cap if cap else 0.0,
            "live_tokens": tokens,
            "alloc_tokens": alloc_tokens,
            "fragmentation": (1.0 - tokens / alloc_tokens)
            if alloc_tokens else 0.0,
        }


def pages_for(seq_len: int, page_size: int) -> int:
    """Physical pages needed to hold ``seq_len`` tokens."""
    return -(-int(seq_len) // int(page_size)) if seq_len > 0 else 0


# ---------------------------------------------------------------------------
# device-side layout helpers (head-interleaved fused KV)
# ---------------------------------------------------------------------------

def fuse_kv(k, v):
    """(…, Hkv, S, d) x2 -> (…, 2*Hkv, S, d) with heads interleaved
    ``[K0, V0, K1, V1, ...]`` so one head-block read feeds both
    operands."""
    stacked = jnp.stack([k, v], axis=-3)        # (…, Hkv, 2, S, d)
    shape = stacked.shape
    return stacked.reshape(shape[:-4] + (shape[-4] * 2,) + shape[-2:])


def split_kv(kv):
    """Inverse of :func:`fuse_kv`."""
    shape = kv.shape
    hkv = shape[-3] // 2
    pairs = kv.reshape(shape[:-3] + (hkv, 2) + shape[-2:])
    return pairs[..., 0, :, :], pairs[..., 1, :, :]


def init_pool(num_pages: int, kv_heads: int, page_size: int, d: int,
              dtype=jnp.float32):
    """Zeroed device pool ``(num_pages, 2*Hkv, page_size, d)``."""
    return jnp.zeros((num_pages, 2 * kv_heads, page_size, d), dtype)


def gather_kv(pool, page_table):
    """Reconstruct contiguous caches from the pool (pure XLA gather).

    pool: (P, 2*Hkv, ps, d); page_table: (B, m) -> k, v each
    (B, Hkv, m*ps, d).  Rows mapped to the null page come back as
    whatever page 0 holds -- positions beyond each slot's ``seq_pos``
    are masked by every consumer, so the garbage never reaches an
    output.  This is the oracle of the paged bit-identity tests and the
    degradation ladder's ``paged-xla`` rung."""
    b, m = page_table.shape
    _, h2, ps, d = pool.shape
    tiles = pool[page_table]                     # (B, m, 2Hkv, ps, d)
    kv = tiles.transpose(0, 2, 1, 3, 4).reshape(b, h2, m * ps, d)
    return split_kv(kv)


def append_token(pool, page_table, pos, k_new, v_new, active=None):
    """Scatter one new K/V token per slot into its current page.

    pool: (P, 2*Hkv, ps, d); page_table: (B, m); pos: (B,) the token's
    position; k_new/v_new: (B, Hkv, 1, d).  ``active`` (B,) bool masks
    finished / empty slots by routing their write to the null page
    (page 0 is never read, so the duplicate scatter targets are
    harmless).  Returns the updated pool."""
    b = pos.shape[0]
    ps = pool.shape[2]
    pages = page_table[jnp.arange(b), pos // ps]
    if active is not None:
        pages = jnp.where(active, pages, NULL_PAGE)
    kv = fuse_kv(k_new, v_new)[:, :, 0, :]       # (B, 2Hkv, d)
    return pool.at[pages, :, pos % ps, :].set(
        kv.astype(pool.dtype), mode="drop")


def write_prefill_pages(pool, pages, k, v):
    """Write one request's contiguous prefill KV into its pages.

    pages: (n,) i32 physical page ids (pad entries = null page);
    k/v: (Hkv, S, d) with S <= n*ps -- the tail of the last page is
    left as zero padding (masked by ``seq_pos`` at read time).
    Returns the updated pool."""
    n = pages.shape[0]
    hkv, s, d = k.shape
    ps = pool.shape[2]
    kv = fuse_kv(k, v)                           # (2Hkv, S, d)
    pad = n * ps - s
    if pad:
        kv = jnp.pad(kv, ((0, 0), (0, pad), (0, 0)))
    tiles = kv.reshape(2 * hkv, n, ps, d).transpose(1, 0, 2, 3)
    return pool.at[pages].set(tiles.astype(pool.dtype), mode="drop")


# ---------------------------------------------------------------------------
# device-side latent layout (MLA): one row per token
# ---------------------------------------------------------------------------
#
# A latent-attention layer caches one row per token: the compressed kv
# latent and the shared roped key side by side, ``kv_lora_rank +
# qk_rope_dim`` wide.  Its pool is ``(num_pages, page_size, lanes)``,
# one ``(page_size, lanes)`` tile per page, addressed by the same page
# table as the fused-KV pools.  ``lanes`` is the row width rounded up to
# the chip's 128 lanes, zero past the row: a page is then a whole number
# of the chip's tiles, which the latent kernel's page copies need
# (Mosaic slices HBM only at tile bounds), and the pool keeps that
# layout across the decode step instead of being copied into it around
# every kernel call.

#: the chip's vector lanes: latent pool rows are padded to a multiple
LANES = 128


def latent_lanes(width: int) -> int:
    """Lanes a latent pool stores a ``width``-wide row in."""
    return -(-int(width) // LANES) * LANES


def init_latent_pool(num_pages: int, page_size: int, width: int,
                     dtype=jnp.float32):
    """Zeroed latent pool ``(num_pages, page_size, latent_lanes(width))``
    for rows ``width`` wide."""
    return jnp.zeros((num_pages, page_size, latent_lanes(width)), dtype)


def gather_latent(pool, page_table):
    """Contiguous latent rows from the pool (pure XLA gather): pool (P,
    ps, lanes), page_table (B, m) -> (B, m*ps, lanes).  Rows past each
    slot's position are whatever their page holds and are masked by
    every consumer.  The oracle of the latent kernel's tests and the
    degradation ladder's ``xla`` rung for latent pools."""
    b, m = page_table.shape
    _, ps, w = pool.shape
    return pool[page_table].reshape(b, m * ps, w)


def append_latent(pool, page_table, pos, rows, active=None):
    """Scatter one new latent row per slot into its current page.

    rows: (B, w), ``w`` at most the pool's lanes, written to the first
    ``w``; pos: (B,) the token's position; inactive slots write to the
    null page.  Returns the updated pool."""
    b, w = rows.shape
    ps = pool.shape[1]
    pages = page_table[jnp.arange(b), pos // ps]
    if active is not None:
        pages = jnp.where(active, pages, NULL_PAGE)
    return pool.at[pages, pos % ps, :w].set(rows.astype(pool.dtype),
                                            mode="drop")


def write_latent_pages(pool, pages, rows):
    """Write one request's contiguous prefill latents (S, w) into its
    pages (n,), ``S <= n*ps``; the last page's tail and the lanes past
    ``w`` are zero padding.  Returns the updated pool."""
    n = pages.shape[0]
    s, w = rows.shape
    _, ps, lanes = pool.shape
    if n * ps - s or lanes - w:
        rows = jnp.pad(rows, ((0, n * ps - s), (0, lanes - w)))
    return pool.at[pages].set(
        rows.reshape(n, ps, lanes).astype(pool.dtype), mode="drop")


# ---------------------------------------------------------------------------
# host-side page-table assembly (what the scheduler maintains)
# ---------------------------------------------------------------------------

def build_page_table(num_slots: int, max_pages: int,
                     slot_pages: dict[int, Sequence[int]]) -> np.ndarray:
    """(num_slots, max_pages) i32 table from the scheduler's per-slot
    page lists; unmapped entries are the null page."""
    table = np.full((num_slots, max_pages), NULL_PAGE, np.int32)
    for slot, pages in slot_pages.items():
        pages = list(pages)
        if len(pages) > max_pages:
            raise ValueError(
                f"slot {slot} holds {len(pages)} pages, table has room "
                f"for {max_pages}")
        table[slot, :len(pages)] = pages
    return table
