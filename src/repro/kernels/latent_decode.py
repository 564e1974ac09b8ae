"""Paged single-token decode of absorbed latent attention (MLA).

A latent-attention layer caches one ``(kv_lora_rank + qk_rope_dim)``
row per token (:func:`repro.core.paged.init_latent_pool`), shared by
every query head.  With ``W_uk`` absorbed into the query, each head's
score against a cached token is one dot product of its
``(kv_lora_rank + qk_rope_dim)`` query row with that token's latent row,
and its output is the softmax-weighted sum of the rows' first
``kv_lora_rank`` lanes (``W_uv`` is applied after the kernel).

The grid is ``slots x chunks`` on a :class:`~repro.core.paged.PagedPlan`
(:func:`latent_decode_grid`): one step per chunk of ``pages`` logical
pages of a slot, ``pages`` derived from the shapes
(:func:`latent_chunk_pages`).  The page table and the positions ride
scalar prefetch; the latent pool stays in HBM (``pl.ANY``) and each
live page of a chunk is copied by its own async copy, straight from the
page table, into one half of a double buffer while the other half is
computed.  One grid step takes the slot's whole ``(heads, width)``
query tile against the chunk's ``(pages * page_size, width)`` rows, so
each page is read once per slot, not once per head, and the grid's
fixed cost is paid once per chunk, not once per page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend as backend_lib
from repro.core.domain import make_attention_domain
from repro.kernels.flash_attention import PAGED_VMEM_BUDGET

NEG_INF = float(-1e30)

#: the kernel's name in the profiler's trace (``backend.emit(name=)``)
LATENT_KERNEL_NAME = "paged_latent_decode"

#: keys one grid step aims to take: enough rows that the grid's fixed
#: cost per step (about 0.23 us on a TPU v5e) is small next to the
#: step's copies and matmuls, few enough that a slot's last live chunk
#: computes little past its position
LATENT_CHUNK_KEYS = 512


def _chunk_vmem_bytes(pages, page_size, width, itemsize):
    """VMEM one grid step holds for a chunk of ``pages`` pages: the
    double-buffered latent rows in the pool's dtype and the chunk's
    float32 rows (the upcast and its masked copy), at the chip's tiled
    layout (lanes padded to 128, sublanes to the dtype's tile)."""
    lanes = -(-width // 128) * 128
    sub = 8 * max(1, 4 // itemsize)
    bufs = 2 * pages * (-(-page_size // sub) * sub) * lanes * itemsize
    f32 = 2 * pages * (-(-page_size // 8) * 8) * lanes * 4
    return bufs + f32


def latent_chunk_pages(page_size: int, width: int, max_pages: int,
                       itemsize: int) -> int:
    """Logical pages one grid step takes: ``LATENT_CHUNK_KEYS`` keys'
    worth (32 pages of 16 tokens), at most ``max_pages``, halved until
    the step's buffers fit ``PAGED_VMEM_BUDGET``."""
    pages = max(1, min(int(max_pages), LATENT_CHUNK_KEYS // int(page_size)))
    while pages > 1 and _chunk_vmem_bytes(
            pages, page_size, width, itemsize) > PAGED_VMEM_BUDGET:
        pages //= 2
    return pages


def latent_decode_grid(slots: int, max_pages: int, page_size: int,
                       width: int, itemsize: int):
    """The latent kernel's grid: one batch dimension over the slots and,
    per slot, the domain of its chunks of logical pages.  Returns
    ``(batch_dims, domain, pages)``, ``pages`` the logical pages of one
    chunk (the last may hold fewer)."""
    pages = latent_chunk_pages(page_size, width, max_pages, itemsize)
    chunks = -(-int(max_pages) // pages)
    return ((int(slots),), make_attention_domain("full", 1, chunks, 0),
            pages)


def latent_decode_grid_steps(slots: int, max_pages: int, layers: int = 1,
                             *, page_size: int, width: int,
                             itemsize: int) -> int:
    """Grid steps the latent kernel runs for one decode step of
    ``layers`` latent attention layers (every lowering launches the same
    count: the domain is one full row of chunks)."""
    (b,), domain, _ = latent_decode_grid(slots, max_pages, page_size,
                                         width, itemsize)
    return int(layers) * b * domain.num_blocks


def _latent_kernel(coords, q_ref, pool_ref, o_ref, bufs_ref, sems,
                   half_ref, acc_ref, m_ref, l_ref, *, scale, page_size,
                   pages, v_dim, slots):
    """One (slot, chunk) step of the online softmax.

    ``coords.refs[0]`` is the prefetched page table, ``coords.refs[1]``
    the positions.  A slot's live pages are those up to ``pos //
    page_size``; a chunk holding none is skipped (no copy, no compute).
    The live steps run in grid order, and each starts the async copies
    of the next live step (the slot's next chunk, or the next slot's
    first) into the buffer half this one does not read, before it waits
    on its own; ``half_ref`` holds the half of the current step.  Only
    live pages are copied, so the rows of a chunk's other pages hold
    whatever the buffer held: the rows past ``pos`` are zeroed before
    either product, and their scores masked."""
    table, seq_pos = coords.refs[0], coords.refs[1]
    max_pages = table.shape[1]
    slot, chunk = coords.batch[0], coords.bx
    keys = pages * page_size

    def last_page(s):
        return jnp.clip(seq_pos[s] // page_size, 0, max_pages - 1)

    def each_live_page(s, c, half, act):
        # act on the async copy of every live page of chunk c of slot s
        live = last_page(s) - c * pages

        def body(j, carry):
            @pl.when(j <= live)
            def _():
                act(pltpu.make_async_copy(
                    pool_ref.at[table[s, c * pages + j]],
                    bufs_ref.at[half, j], sems.at[half, j]))
            return carry
        jax.lax.fori_loop(0, pages, body, 0)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    @pl.when(coords.first_step)
    def _():
        half_ref[0] = 0
        each_live_page(0, 0, 0, start)

    last_chunk = last_page(slot) // pages

    @pl.when(chunk <= last_chunk)
    def _():
        cur = half_ref[0]

        @pl.when(chunk < last_chunk)
        def _():
            each_live_page(slot, chunk + 1, 1 - cur, start)

        @pl.when((chunk == last_chunk) & (slot + 1 < slots))
        def _():
            each_live_page(slot + 1, 0, 1 - cur, start)

        each_live_page(slot, chunk, cur, wait)

        @pl.when(chunk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        pos = seq_pos[slot]
        q = q_ref[0].astype(jnp.float32) * scale              # (H, w)
        kv = bufs_ref[cur].astype(jnp.float32).reshape(keys, -1)
        row = chunk * keys + jax.lax.broadcasted_iota(
            jnp.int32, (keys, 1), 0)
        kv = jnp.where(row <= pos, kv, 0.0)                   # (keys, w)
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = chunk * keys + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p, kv[:, :v_dim], preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        half_ref[0] = 1 - cur

        @pl.when(chunk == last_chunk)
        def _():
            l = l_ref[...]
            o_ref[0] = (acc_ref[...] / jnp.where(l == 0, 1.0, l)).astype(
                o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "v_dim", "grid_mode", "backend"))
def _latent_decode_impl(q, pool, page_table, seq_pos, *, scale, v_dim,
                        grid_mode, backend):
    from repro.core.paged import LANES, PagedPlan

    b, h, qw = q.shape
    _, page_size, w = pool.shape
    if w < qw or v_dim > qw:
        raise ValueError(f"latent pool rows are {w} lanes wide, queries "
                         f"{qw}, values {v_dim}")
    if not backend.interpret and w % LANES:
        raise ValueError(
            f"latent pool rows are {w} lanes wide: on the chip a page is "
            f"copied whole, so rows take a multiple of {LANES} lanes "
            f"(repro.core.paged.init_latent_pool pads them)")
    # the pool's lanes past the row are zero: so are the query's
    q = jnp.pad(q, ((0, 0), (0, 0), (0, w - qw)))
    if page_table.shape[0] != b:
        raise ValueError(
            f"page_table rows ({page_table.shape[0]}) != slots ({b})")
    m_k = page_table.shape[1]
    page_table = page_table.astype(jnp.int32)
    pos = jnp.broadcast_to(
        jnp.asarray(seq_pos, jnp.int32).reshape(-1), (b,))
    batch_dims, domain, pages = latent_decode_grid(
        b, m_k, page_size, w, jnp.dtype(pool.dtype).itemsize)
    plan = PagedPlan(domain, grid_mode, batch_dims=batch_dims,
                     backend=backend, page_table=page_table, seq_pos=pos)

    def slot_place(bx, by, slot):
        return (slot, 0, 0)

    kernel = functools.partial(_latent_kernel, scale=scale,
                               page_size=page_size, pages=pages,
                               v_dim=v_dim, slots=b)
    call = plan.pallas_call(
        kernel,
        in_specs=[plan.block_spec((1, h, w), slot_place),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=plan.block_spec((1, h, v_dim), slot_place),
        out_shape=jax.ShapeDtypeStruct((b, h, v_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((2, pages, page_size, w), pool.dtype),
                        pltpu.SemaphoreType.DMA((2, pages)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((h, v_dim), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32)],
        name=LATENT_KERNEL_NAME)
    return call(q, pool)


def paged_latent_decode(q, pool, page_table, seq_pos, *, scale: float,
                        v_dim: int, grid_mode: str = "compact",
                        backend=None, interpret: bool | None = None):
    """Absorbed-MLA decode over a paged latent pool.

    q:          (B, H, w) one query row per head and slot: the
                ``W_uk``-absorbed nope part and the roped part, ``w =
                kv_lora_rank + qk_rope_dim``.
    pool:       (P, page_size, lanes) latent pages, rows in the first
                ``w`` of ``lanes >= w`` lanes and zero past them
                (:func:`repro.core.paged.init_latent_pool`); page 0 is
                the null page.
    page_table: (B, max_pages) i32, logical page -> physical page.
    seq_pos:    (B,) i32 per-slot positions; keys past them are masked
                and pages past ``pos // page_size`` neither copied nor
                computed.
    Returns (B, H, v_dim): the softmax-weighted sums of the cached rows'
    first ``v_dim`` (= ``kv_lora_rank``) lanes."""
    from repro.core.plan import normalize_lowering
    target = backend_lib.resolve(backend, interpret)
    return _latent_decode_impl(q, pool, page_table, seq_pos,
                               scale=float(scale), v_dim=int(v_dim),
                               grid_mode=normalize_lowering(grid_mode),
                               backend=target)


def latent_decode_xla(q, pool, page_table, seq_pos, *, scale: float,
                      v_dim: int):
    """The same decode in plain XLA over the gathered latents
    (:func:`repro.core.paged.gather_latent`): the kernel's oracle and
    the degradation ladder's ``xla`` rung for latent pools."""
    from repro.core.paged import gather_latent
    b = q.shape[0]
    rows = gather_latent(pool, page_table)[..., :q.shape[-1]].astype(
        jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * scale
    pos = jnp.broadcast_to(jnp.asarray(seq_pos, jnp.int32).reshape(-1),
                           (b,))
    kpos = jnp.arange(rows.shape[1])[None, None, :]
    s = jnp.where(kpos <= pos[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bsv->bhv", p, rows[..., :v_dim])
    return o.astype(q.dtype)
