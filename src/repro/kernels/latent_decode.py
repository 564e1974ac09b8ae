"""Paged single-token decode of absorbed latent attention (MLA).

A latent-attention layer caches one ``(kv_lora_rank + qk_rope_dim)``
row per token (:func:`repro.core.paged.init_latent_pool`), shared by
every query head.  With ``W_uk`` absorbed into the query, each head's
score against a cached token is one dot product of its
``(kv_lora_rank + qk_rope_dim)`` query row with that token's latent row,
and its output is the softmax-weighted sum of the rows' first
``kv_lora_rank`` lanes (``W_uv`` is applied after the kernel).

The grid is ``slots x pages`` on a :class:`~repro.core.paged.PagedPlan`:
the page table rides the scalar-prefetch path and the latent operand's
index map turns the logical page into the physical one, as in the
fused-KV paged kernel.  One grid step takes the slot's whole
``(heads, width)`` query tile against one ``(page_size, width)`` page,
so each page is read once per slot, not once per head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend as backend_lib
from repro.core.domain import make_attention_domain

NEG_INF = float(-1e30)

#: the kernel's name in the profiler's trace (``backend.emit(name=)``)
LATENT_KERNEL_NAME = "paged_latent_decode"


def _latent_kernel(coords, q_ref, lat_ref, pos_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale, page_size, v_dim):
    """One (slot, logical page) step of the online softmax.  Pages past
    the slot's position are skipped; keys past it inside the last page
    are masked."""
    kb = coords.bx
    pos = pos_ref[coords.batch[0]]
    end = pos // page_size

    @pl.when(kb <= end)
    def _():
        @pl.when(kb == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        q = q_ref[0].astype(jnp.float32) * scale              # (H, w)
        kv = lat_ref[0].astype(jnp.float32)                   # (ps, w)
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = kb * page_size + jax.lax.broadcasted_iota(
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

        @pl.when(kb == end)
        def _():
            l = l_ref[...]
            o_ref[0] = (acc_ref[...] / jnp.where(l == 0, 1.0, l)).astype(
                o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "scale", "v_dim", "grid_mode", "backend"))
def _latent_decode_impl(q, pool, page_table, seq_pos, *, scale, v_dim,
                        grid_mode, backend):
    from repro.core.paged import PagedPlan

    b, h, w = q.shape
    _, page_size, pw = pool.shape
    if pw != w or v_dim > w:
        raise ValueError(f"latent pool rows are {pw} wide, queries {w}, "
                         f"values {v_dim}")
    if page_table.shape[0] != b:
        raise ValueError(
            f"page_table rows ({page_table.shape[0]}) != slots ({b})")
    m_k = page_table.shape[1]
    page_table = page_table.astype(jnp.int32)
    pos = jnp.broadcast_to(
        jnp.asarray(seq_pos, jnp.int32).reshape(-1), (b,))
    plan = PagedPlan(make_attention_domain("full", 1, m_k, 0), grid_mode,
                     batch_dims=(b,), backend=backend, page_table=page_table)

    def slot_place(bx, by, slot):
        return (slot, 0, 0)

    def page_index(grid_ids, refs):
        # refs[0] is the prefetched page table: logical page bx of the
        # slot -> its physical page
        _, bx, _ = plan._decode(grid_ids, refs)
        return (refs[0][grid_ids[0], bx], 0, 0)

    kernel = functools.partial(_latent_kernel, scale=scale,
                               page_size=page_size, v_dim=v_dim)
    call = plan.pallas_call(
        kernel,
        in_specs=[plan.block_spec((1, h, w), slot_place),
                  plan._index_spec((1, page_size, w), page_index),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=plan.block_spec((1, h, v_dim), slot_place),
        out_shape=jax.ShapeDtypeStruct((b, h, v_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((h, v_dim), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32)],
        name=LATENT_KERNEL_NAME)
    return call(q, pool, pos)


def paged_latent_decode(q, pool, page_table, seq_pos, *, scale: float,
                        v_dim: int, grid_mode: str = "compact",
                        backend=None, interpret: bool | None = None):
    """Absorbed-MLA decode over a paged latent pool.

    q:          (B, H, w) one query row per head and slot: the
                ``W_uk``-absorbed nope part and the roped part, ``w =
                kv_lora_rank + qk_rope_dim``.
    pool:       (P, page_size, w) latent pages; page 0 is the null page.
    page_table: (B, max_pages) i32, logical page -> physical page.
    seq_pos:    (B,) i32 per-slot positions; keys past them are masked
                and pages past ``pos // page_size`` skipped.
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
    rows = gather_latent(pool, page_table).astype(jnp.float32)
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * scale
    pos = jnp.broadcast_to(jnp.asarray(seq_pos, jnp.int32).reshape(-1),
                           (b,))
    kpos = jnp.arange(rows.shape[1])[None, None, :]
    s = jnp.where(kpos <= pos[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bsv->bhv", p, rows[..., :v_dim])
    return o.astype(q.dtype)
