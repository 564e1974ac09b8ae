"""Block-space flash attention: the paper's compact-grid technique applied
to the dominant kernel of the assigned LM architectures.

The (q_block, k_block) pairs of causal attention form a lower-triangular
block domain -- the 2-simplex case of the authors' block-space program
[Navarro et al. 2014/2016].  Instead of launching the bounding-box grid
``m_q x m_k`` and discarding invalid blocks at run time (the standard
masked-flash formulation), the compact grid launches exactly
``T(m) = m(m+1)/2`` (causal) or ``T(w) + (m-w)w`` (local window) steps
and decodes ``t -> (q_block, k_block)`` either in closed form (the
integer-sqrt inverse of the triangular enumeration -- the m=2 case of
the "order-m equation" map of related work [18]) or through the
scalar-prefetch lookup table, both emitted by the shared
:class:`~repro.core.plan.GridPlan` engine.  ``grid_mode`` selects the
lowering: ``closed_form`` (alias ``compact``) | ``prefetch_lut`` |
``bounding`` | ``mma`` (the decode table built by digit-basis matmul
chains on the MXU).

Grid layout: ``(batch*heads, T)``; the compact enumerations are
row-major in q, so all k-steps of one q row are consecutive: the online
softmax state lives in VMEM scratch and the output block is written once
per row (standard flash revisiting pattern).  GQA folds the kv-head
index inside the BlockSpec index_map.

Compact KV (the ``storage=`` axis): ``kind="local"`` also accepts
``sq < sk`` with the decode convention (queries are the last sq
positions of the key sequence -- chunked prefill / decode against a long
cache).  The rectangular BandDomain then touches only the *last*
``sq + window`` key positions, and ``storage="compact"`` reads K/V
packed to exactly that support (the sliding-window KV-cache truncation:
O(window) cache instead of O(sk)); the kv BlockSpec index maps are
rewritten to packed slots.  For causal / full / square-local the column
support is all of sk, so compact and embedded KV coincide -- the packing
is the 1-D analogue of the fractal orthotope packing.

Forward only (training uses the custom-vjp jnp path in
``repro.models.attention``; this kernel is the serving/TPU fast path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend as backend_lib
from repro.core.compact import key_block_support
from repro.core.domain import make_attention_domain
from repro.core.plan import GridPlan, normalize_storage

NEG_INF = float(-1e30)  # avoid true -inf so exp() stays nan-free
#: the paged decode kernel's name.  On the TPU a kernel's name is also
#: its XLA instruction's name in the profiler's trace; this one keeps the
#: jitted entry's name, ``_paged_impl``, as its prefix, so that readers
#: matching the kernel by its entry still find it
PAGED_KERNEL_NAME = "_paged_impl_decode"


def _row_bounds(kind, qb, m_k, wb, off_b):
    if kind == "causal":
        return 0 * qb, qb
    if kind == "local":
        return jnp.maximum(qb + off_b - (wb - 1), 0), qb + off_b
    return 0 * qb, qb * 0 + (m_k - 1)  # full


def _attn_tile_update(q, k, v, acc, m_prev, l_prev, *, kind, window, qb,
                      kb, block_q, block_k, off, seq_pos=None):
    """One online-softmax step over the (qb, kb) tile -- the kernel
    math shared by the flash and the paged decode kernels.  ``q`` is
    pre-scaled f32; k/v are f32 tiles.  ``seq_pos``
    (run-time scalar) additionally masks keys beyond the current decode
    position."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = None
    kpos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    if kind in ("causal", "local"):
        # decode convention: query row qb covers embedded token
        # positions off + qb*block_q + [0, block_q)
        qpos = off + qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = kpos <= qpos
        if kind == "local":
            mask &= kpos > qpos - window
    if seq_pos is not None:
        pm = kpos <= seq_pos
        if kind == "full" and window:
            # run-time sliding window anchored at the decode position
            pm &= kpos > seq_pos - window
        mask = pm if mask is None else mask & pm
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


def _attn_kernel(coords, *refs, kind, window, scale, block_q, block_k,
                 m_k, wb, off, h, has_pos):
    """Attention kernel: one (qb, kb) tile per grid step,
    online-softmax state in VMEM scratch across the sequential grid.
    ``pos_ref`` (when present) is the whole (B,) decode-position
    vector in SMEM; the batch row of this program is the leading grid
    id divided by the head count ``h``."""
    if has_pos:
        q_ref, k_ref, v_ref, pos_ref, o_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    kb, qb = coords.bx, coords.by
    start, end = _row_bounds(kind, qb, m_k, wb, off // block_q)
    pos = None
    if has_pos:
        pos = pos_ref[coords.batch[0] // h]
        end = jnp.minimum(end, pos // block_k)
        if kind == "full" and window:
            start = jnp.maximum(
                start, jnp.maximum(pos - window + 1, 0) // block_k)

    def body():
        @pl.when(kb == start)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, d)
        acc_new, m_new, l_new = _attn_tile_update(
            q, k, v, acc_ref[...], m_ref[...], l_ref[...], kind=kind,
            window=window, qb=qb, kb=kb, block_q=block_q,
            block_k=block_k, off=off, seq_pos=pos)
        acc_ref[...] = acc_new
        m_ref[...] = m_new
        l_ref[...] = l_new

        @pl.when(kb == end)
        def _():
            l = l_ref[...]
            l = jnp.where(l == 0, 1.0, l)
            o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)

    live = None if pos is None else ((kb <= end) & (kb >= start))
    if coords.valid is None and live is None:
        body()
    elif coords.valid is None:
        pl.when(live)(body)
    elif live is None:
        pl.when(coords.valid)(body)
    else:
        pl.when(coords.valid & live)(body)


@functools.partial(jax.jit, static_argnames=(
    "kind", "window", "scale", "block_q", "block_k", "grid_mode",
    "storage", "kv_seq_len", "backend", "mesh", "shard_axis",
    "shard_balance", "verify"))
def _flash_impl(q, k, v, seq_pos=None, *, kind, window, scale, block_q,
                block_k, grid_mode, storage, kv_seq_len, backend,
                mesh=None, shard_axis="data", shard_balance="contiguous",
                verify=False):
    b, h, sq, d = q.shape
    _, hkv, sk_arr, _ = k.shape
    group = h // hkv
    target = backend
    if scale is None:
        scale = float(1.0 / np.sqrt(d))
    storage = normalize_storage(storage)
    sk = kv_seq_len if kv_seq_len is not None else sk_arr
    if kind == "local":
        # rectangular local (sq < sk) still needs square blocks: clamp
        # both to one value instead of letting min(.., sq) / min(.., sk)
        # diverge
        block_q = block_k = min(block_q, block_k, sq, sk)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("sequence must be divisible by block size")
    m_q, m_k = sq // block_q, sk // block_k

    wb = 0
    if kind == "causal" and (sq != sk or block_q != block_k):
        raise ValueError("causal requires a square block grid")
    if kind == "local":
        if block_q != block_k or window % block_k:
            raise ValueError("local: need block_q == block_k | window")
        if (sk - sq) % block_k:
            raise ValueError("local: Sk - Sq must be block-aligned")
        wb = window // block_k + 1
    off = sk - sq if kind == "local" else 0
    has_pos = seq_pos is not None
    if has_pos and kind != "full":
        # a band row wholly beyond seq_pos would have start > end: no
        # step initializes the output -- garbage, not a defined result.
        # Decode rides kind="full"; window= gives the run-time sliding
        # window anchored at seq_pos.
        raise ValueError(
            f"seq_pos requires kind='full' (got kind={kind!r}); pass "
            f"window= for a run-time sliding window anchored at "
            f"seq_pos")
    if has_pos and mesh is not None:
        raise ValueError(
            "seq_pos (decode) does not combine with the query-row mesh "
            "partition; shard the batch axis instead (see "
            "repro.models.attention.decode_attention_flash)")

    domain = make_attention_domain(kind, m_q, m_k, wb)
    zz_perm = None
    if mesh is not None:
        from repro.core.shard import ShardedPlan, zigzag_row_order
        D = int(mesh.shape[shard_axis])
        if m_q % D:
            raise ValueError(
                f"sharded flash needs the query-block grid divisible by "
                f"the mesh axis: m_q={m_q} blocks over {D} devices")
        partition = "rows"
        if shard_balance == "zigzag":
            if kind != "causal":
                raise ValueError(
                    "shard_balance='zigzag' balances the causal "
                    "triangle; contiguous bands already balance "
                    f"kind={kind!r}")
            if m_q % (2 * D):
                raise ValueError(
                    f"zigzag needs the query-block grid ({m_q}) "
                    f"divisible by 2 * mesh axis ({2 * D}) for an "
                    f"exactly balanced snake")
            if grid_mode in ("closed_form", "compact"):
                # the snake's owned rows are scattered: decode them
                # through the LUT (bit-identical to the closed form by
                # the engine's contract)
                grid_mode = "prefetch_lut"
            partition = "zigzag"
            zz_perm = zigzag_row_order(m_q, D)
        elif shard_balance != "contiguous":
            raise ValueError(
                f"unknown shard_balance {shard_balance!r}; expected "
                f"'contiguous' or 'zigzag'")
        plan = ShardedPlan(domain, grid_mode, batch_dims=(b * h,),
                           backend=target, mesh=mesh, axis=shard_axis,
                           partition=partition)
        out_shape = (b, h, sq // D, d)
    else:
        plan = GridPlan(domain, grid_mode, batch_dims=(b * h,),
                        backend=target)
        out_shape = q.shape
    if verify:
        from repro.analysis import verify_or_raise
        verify_or_raise(plan, kernel="flash")

    # compact KV: k/v hold only the key blocks in [s0, m_k)
    s0 = key_block_support(domain)[0] if storage == "compact" else 0
    if sk_arr != sk - s0 * block_k:
        raise ValueError(
            f"{storage} storage expects k/v of {sk - s0 * block_k} key "
            f"positions (support blocks [{s0}, {m_k}) of sk={sk}), got "
            f"{sk_arr}")

    pos_operand = ()
    if has_pos:
        # normalize to a per-batch-row (B,) vector: a scalar broadcasts
        # (back-compat), a vector carries one decode position per slot.
        sp = jnp.asarray(seq_pos, jnp.int32)
        if sp.ndim == 0 or sp.shape == (1,):
            sp = jnp.broadcast_to(sp.reshape(()), (b,))
        elif sp.shape != (b,):
            raise ValueError(
                f"seq_pos must be a scalar or a ({b},) per-row vector, "
                f"got shape {sp.shape}")
        pos_operand = (sp,)

    def q_place(bx, by, bh):
        return (bh // h, bh % h, by, 0)

    def kv_place(bx, by, bh):
        kb = jnp.clip(bx - s0, 0, m_k - s0 - 1) if s0 else bx
        return (bh // h, (bh % h) // group, kb, 0)

    kernel = functools.partial(
        _attn_kernel, kind=kind, window=window, scale=scale,
        block_q=block_q, block_k=block_k, m_k=m_k, wb=wb, off=off, h=h,
        has_pos=has_pos)

    in_specs = [
        plan.block_spec((1, 1, block_q, d), q_place),
        plan.block_spec((1, 1, block_k, d), kv_place),
        plan.block_spec((1, 1, block_k, d), kv_place),
    ]
    if has_pos:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    call = plan.pallas_call(
        kernel,
        in_specs=in_specs,
        out_specs=plan.block_spec((1, 1, block_q, d), q_place),
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        name="flash_attention",
    )
    if mesh is None:
        return call(q, k, v, *pos_operand)

    # shard the query-block axis: q/o split along the sequence dim,
    # k/v replicated; each device runs its contiguous query-row band
    # (whole rows, so the online-softmax state never crosses devices).
    from jax.sharding import PartitionSpec as P

    from repro.core.shard import device_tables

    axis = shard_axis
    tbl, luts = device_tables(plan)
    qkv_specs = (P(None, None, axis, None), P(None, None, None, None),
                 P(None, None, None, None))

    def device_fn(tbl, luts, q, k, v):
        return call(tbl.reshape(-1), *luts, q, k, v)

    if zz_perm is not None:
        # shard_map splits contiguous chunks: gather the Q block rows
        # into device-concatenated snake order first, and scatter the
        # output back through the inverse permutation after.
        qr = q.reshape(b, h, m_q, block_q, d)
        q = qr[:, :, zz_perm].reshape(b, h, sq, d)
    out = jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(P(axis, None), tuple(P(axis, None) for _ in luts))
        + qkv_specs,
        out_specs=P(None, None, axis, None), check_vma=False)(
            tbl, luts, q, k, v)
    if zz_perm is not None:
        inv = np.argsort(zz_perm)
        out = out.reshape(b, h, m_q, block_q, d)[:, :, inv]
        out = out.reshape(b, h, sq, d)
    return out


def flash_attention(q, k, v, *, kind: str = "causal", window: int = 0,
                    scale: float | None = None,
                    block_q: int | str = 128, block_k: int | str = 128,
                    grid_mode: str = "compact",
                    storage: str = "embedded",
                    kv_seq_len: int | None = None, seq_pos=None,
                    backend=None, interpret: bool | None = None, mesh=None,
                    shard_axis: str = "data",
                    shard_balance: str = "contiguous",
                    verify: bool = False):
    """q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D) with Hkv | H.

    kind:      "causal" | "local" (window tokens) | "full"
    grid_mode: "closed_form" (alias "compact": the paper's block-space
               map) | "prefetch_lut" (scalar-prefetch table decode) |
               "bounding" (baseline full grid + run-time discard) |
               "mma" (digit-basis matmul decode on the matrix units;
               see :mod:`repro.core.mma`) |
               "auto" (resolve the tuned lowering -- and tuned block
               geometry, when block_q/block_k are left at "auto" --
               from the :mod:`~repro.core.tune` cache)
    storage:   "embedded" (k/v hold the full key sequence) | "compact"
               (k/v hold only the domain's key-block support, packed;
               see :func:`repro.core.compact.pack_kv`).  When the
               support is a strict suffix (rectangular local), pass the
               true key length as ``kv_seq_len``.
    seq_pos:   run-time int32 decode position -- a () scalar (every
               batch row at the same position) or a (B,) vector with
               one position per batch row (continuous batching;
               requires ``kind="full"``; combine with ``window=`` for
               a run-time sliding window): keys at ``kpos > seq_pos``
               are masked and key blocks beyond ``seq_pos // block_k``
               are predicated off (an SMEM vector).  The static grid
               still pipelines every tile and skips only their compute.
    backend:   emission target ("tpu" | "tpu-interpret" | None =
               platform default; see :mod:`repro.core.backend`).
    causal requires Sq == Sk; local accepts Sq < Sk with the decode
    convention (queries are the last Sq positions) when
    Sk - Sq >= window (full window per query block).

    ``mesh=`` shards the query-block axis of the block domain over
    ``shard_axis``: q and the output split along the sequence dim into
    contiguous query-row bands (one owner per row, so the online
    softmax never crosses devices and results are bit-identical); k/v
    stay replicated.  Requires Sq/block_q divisible by the axis size.

    ``shard_balance="zigzag"`` (causal only) replaces the contiguous
    bands with the snake assignment: device ``d`` owns query-block rows
    ``{j : min(j mod 2D, 2D-1-(j mod 2D)) == d}``, pairing light and
    heavy triangle rows so every device runs exactly the same number of
    key blocks (requires Sq/block_q divisible by 2D).  Q is permuted
    into snake order before the sharded launch and O inverse-permuted
    after, so results stay bit-identical to the contiguous split.
    """
    target = backend_lib.resolve(backend, interpret)
    from repro.core import tune

    from .sierpinski_write import resolve_auto_schedule
    b, h, sq, d = q.shape
    _, hkv, _, _ = k.shape
    sk = kv_seq_len if kv_seq_len is not None else k.shape[2]
    grid_mode, block_q, block_k = resolve_auto_schedule(
        "flash",
        tune.target_params(
            tune.shard_params(
                {"kind": kind, "batch": b, "heads": h, "kv_heads": hkv,
                 "sq": sq, "sk": sk, "d": d, "window": window},
                mesh, shard_axis),
            target),
        grid_mode=(grid_mode, "lowering", "closed_form"),
        block_q=(block_q, "block_q", 128),
        block_k=(block_k, "block_k", 128))
    return _flash_impl(q, k, v, seq_pos, kind=kind, window=window,
                       scale=scale, block_q=block_q, block_k=block_k,
                       grid_mode=grid_mode, storage=storage,
                       kv_seq_len=kv_seq_len, backend=target,
                       mesh=mesh, shard_axis=shard_axis,
                       shard_balance=shard_balance, verify=verify)


# ---------------------------------------------------------------------------
# paged decode: the page table rides the scalar-prefetch LUT mechanism
# ---------------------------------------------------------------------------

def paged_decode_grid(slots: int, max_pages: int):
    """The paged decode kernel's grid: one batch dimension over the
    slots and, per slot, the domain of its logical pages -- one grid
    step per (slot, page), each reading every head of that page.
    Returns ``(batch_dims, domain)`` for the kernel's
    :class:`~repro.core.paged.PagedPlan`."""
    return (int(slots),), make_attention_domain("full", 1, int(max_pages), 0)


def paged_decode_grid_steps(slots: int, max_pages: int,
                            layers: int = 1) -> int:
    """Grid steps the paged decode kernel runs for one decode step of
    ``layers`` paged attention layers (every lowering launches the same
    count: the domain is one full row of pages)."""
    batch_dims, domain = paged_decode_grid(slots, max_pages)
    return int(layers) * int(np.prod(batch_dims)) * domain.num_blocks


def _paged_live_pages(pos, page_size, window):
    """First and last logical page holding keys a slot at decode
    position ``pos`` attends to (``window`` 0: no sliding window)."""
    end = pos // page_size
    if window:
        return jnp.maximum(pos - window + 1, 0) // page_size, end
    return 0 * end, end


def _paged_tile_vmem_bytes(h, hkv, page_size, d, itemsize):
    """VMEM one grid step of the paged kernel holds: the double-buffered
    fused page tile and query block in the pool's dtype, and the float32
    K and V every query head reads, all at the chip's tiled layout
    (lanes padded to 128, sublanes to the dtype's tile)."""
    lanes = -(-d // 128) * 128
    sub = 8 * max(1, 4 // itemsize)
    rows = -(-page_size // sub) * sub
    tile = 2 * hkv * rows * lanes * itemsize
    qblk = -(-h // sub) * sub * lanes * itemsize
    f32 = 2 * h * (-(-page_size // 8) * 8) * lanes * 4
    return 2 * (tile + qblk) + f32


#: VMEM the paged kernel may plan for one grid step: the default
#: scoped VMEM limit of a TPU v5e core
PAGED_VMEM_BUDGET = 16 << 20


def _paged_attn_kernel(coords, q_ref, kv_ref, o_ref, acc_ref, m_ref,
                       l_ref, *, window, scale, page_size, group,
                       unroll_heads):
    """Paged decode kernel.  One grid step per (slot, logical page),
    reading every head: the query block is the slot's ``(1, h, d)``
    rows and the KV block the page's whole ``(1, 2*hkv, page_size, d)``
    fused tile, whose *physical* page the BlockSpec index map already
    resolved from the prefetched page table (``coords.refs[0]``).  K of KV head ``j`` is row ``2j`` of the tile
    and V row ``2j + 1``; query head ``i`` reads KV head ``i // group``.
    The index map clamps the logical page into the slot's live pages, so
    a step past the slot's last page (or before its window's first)
    repeats a live block index and starts no copy; its compute is
    predicated off here.  Masking uses the *logical* page id
    (``coords.bx``).

    Each head runs :func:`_attn_tile_update` on its own ``(1, d)`` row
    and ``(page_size, d)`` K/V: the contiguous kernel's step, in its
    shape and order, so results are bit-identical to the contiguous
    ``seq_pos`` path.  ``unroll_heads`` unrolls the head loop where
    Mosaic compiles it (it needs static sublane offsets); the
    interpreter keeps it rolled, so that XLA's CPU compiler builds each
    head's step once, as the contiguous kernel's, rather than
    vectorising the unrolled copies' arithmetic differently."""
    kb = coords.bx
    pos = coords.refs[1][coords.batch[0]]
    start, end = _paged_live_pages(pos, page_size, window)

    def body():
        @pl.when(kb == start)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        def head(i, carry):
            j = i // group
            rows = pl.ds(i, 1)
            q = q_ref[0, rows].astype(jnp.float32) * scale
            k = kv_ref[0, 2 * j].astype(jnp.float32)
            v = kv_ref[0, 2 * j + 1].astype(jnp.float32)
            acc_new, m_new, l_new = _attn_tile_update(
                q, k, v, acc_ref[rows], m_ref[rows], l_ref[rows],
                kind="full", window=window, qb=0 * kb, kb=kb, block_q=1,
                block_k=page_size, off=0, seq_pos=pos)
            acc_ref[rows] = acc_new
            m_ref[rows] = m_new
            l_ref[rows] = l_new
            return carry

        jax.lax.fori_loop(0, acc_ref.shape[0], head, 0,
                          unroll=unroll_heads)

        @pl.when(kb == end)
        def _():
            l = l_ref[...]
            l = jnp.where(l == 0, 1.0, l)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    live = (kb <= end) & (kb >= start)
    if coords.valid is None:
        pl.when(live)(body)
    else:
        pl.when(coords.valid & live)(body)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "grid_mode", "backend", "verify"))
def _paged_impl(q, kv_pool, page_table, seq_pos, *, window, scale,
                grid_mode, backend, verify=False):
    from repro.core.paged import PagedPlan

    b, h, sq, d = q.shape
    if sq != 1:
        raise ValueError(f"paged decode is single-token: Sq={sq}")
    num_pages, h2, page_size, dp = kv_pool.shape
    if h2 % 2 or dp != d:
        raise ValueError(
            f"kv_pool must be (P, 2*Hkv, page_size, {d}), got "
            f"{kv_pool.shape}")
    hkv = h2 // 2
    group = h // hkv
    m_k = page_table.shape[1]
    if page_table.shape[0] != b:
        raise ValueError(
            f"page_table rows ({page_table.shape[0]}) != slots ({b})")
    target = backend
    if scale is None:
        scale = float(1.0 / np.sqrt(d))
    page_table = page_table.astype(jnp.int32)
    pos = jnp.broadcast_to(
        jnp.asarray(seq_pos, jnp.int32).reshape(-1), (b,))

    batch_dims, domain = paged_decode_grid(b, m_k)
    if verify:
        from repro.analysis import verify_or_raise
        verify_or_raise(GridPlan(domain, grid_mode, batch_dims=batch_dims,
                                 backend=target), kernel="flash")

    need = _paged_tile_vmem_bytes(h, hkv, page_size, d,
                                  jnp.dtype(kv_pool.dtype).itemsize)
    if need > PAGED_VMEM_BUDGET:
        raise ValueError(
            f"one step of the paged decode kernel reads every head of a "
            f"page: {h} query heads over a (2*{hkv}, {page_size}, {d}) "
            f"{kv_pool.dtype} page tile need {need} B of VMEM; the budget "
            f"is PAGED_VMEM_BUDGET = {PAGED_VMEM_BUDGET} B.  Use a "
            f"smaller page_size.")
    plan = PagedPlan(domain, grid_mode, batch_dims=batch_dims,
                     backend=target, page_table=page_table, seq_pos=pos)

    def slot_place(bx, by, slot):
        return (slot, 0, 0)

    def kv_index(grid_ids, refs):
        # refs[0] is the prefetched page table, refs[1] the positions;
        # the decoded bx is the *logical* page, clamped into the slot's
        # live pages (a dead step repeats its neighbour's block index,
        # so it starts no copy) and translated to its physical page.
        _, bx, _ = plan._decode(grid_ids, refs)
        slot = grid_ids[0]
        start, end = _paged_live_pages(refs[1][slot], page_size, window)
        page = refs[0][slot, jnp.minimum(jnp.maximum(bx, start), end)]
        return (page, 0, 0, 0)

    kernel = functools.partial(
        _paged_attn_kernel, window=window, scale=scale,
        page_size=page_size, group=group,
        unroll_heads=not target.interpret)
    call = plan.pallas_call(
        kernel,
        in_specs=[
            plan.block_spec((1, h, d), slot_place),
            plan._index_spec((1, h2, page_size, d), kv_index),
        ],
        out_specs=plan.block_spec((1, h, d), slot_place),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
        name=PAGED_KERNEL_NAME,
    )
    return call(q.reshape(b, h, d), kv_pool).reshape(q.shape)


def paged_flash_attention(q, kv_pool, page_table, seq_pos, *,
                          window: int = 0, scale: float | None = None,
                          grid_mode: str = "compact", backend=None,
                          interpret: bool | None = None,
                          verify: bool = False):
    """Paged single-token decode attention over a fused-KV page pool.

    q:          (B, H, 1, D) -- one query per serving slot.
    kv_pool:    (P, 2*Hkv, page_size, D) physical pages, K/V heads
                interleaved ``[K0, V0, K1, V1, ...]`` (see
                :mod:`repro.core.paged`); page 0 is the null page.
    page_table: (B, max_pages) i32 logical-block -> physical-page map
                per slot (null-page entries beyond each slot's length).
    seq_pos:    (B,) int32 per-slot decode positions (a scalar
                broadcasts).  Keys beyond a slot's position are masked;
                pages beyond ``pos // page_size`` are never read.
    window:     optional run-time sliding window anchored at seq_pos.

    The grid is ``B x max_pages`` (:func:`paged_decode_grid`): one step
    per (slot, logical page), each reading the page's whole fused tile
    for every head, so a page is copied once per slot, not once per
    head.  The page table and the positions are scalar-prefetch
    operands, resolved in the KV BlockSpec index map -- the lambda-map
    indirection of the paper, pointed at physical memory -- which clamps
    each step's logical page into the slot's live pages: steps past a
    slot's end repeat its last block index and copy nothing.  A page
    tile over ``PAGED_VMEM_BUDGET`` is refused.  Bit-identical to the
    contiguous ``flash_attention(..., kind="full", seq_pos=...)`` path
    with ``block_k == page_size`` when the mapped pages hold the same
    values."""
    target = backend_lib.resolve(backend, interpret)
    from repro.core.plan import normalize_lowering
    return _paged_impl(q, kv_pool, page_table, seq_pos, window=window,
                       scale=scale,
                       grid_mode=normalize_lowering(grid_mode),
                       backend=target, verify=verify)
