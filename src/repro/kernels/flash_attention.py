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
``bounding`` | ``mma`` (digit-basis matmul decode on the MXU / tensor
cores; the gpu structure consumes a device-built row-extents operand).

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

from repro.core import backend as backend_lib
from repro.core.backend import full_spec
from repro.core.compact import key_block_support
from repro.core.domain import make_attention_domain
from repro.core.plan import GridPlan, normalize_storage

NEG_INF = float(-1e30)  # avoid true -inf so exp() stays nan-free


def _row_bounds(kind, qb, m_k, wb, off_b):
    if kind == "causal":
        return 0 * qb, qb
    if kind == "local":
        return jnp.maximum(qb + off_b - (wb - 1), 0), qb + off_b
    return 0 * qb, qb * 0 + (m_k - 1)  # full


def _attn_tile_update(q, k, v, acc, m_prev, l_prev, *, kind, window, qb,
                      kb, block_q, block_k, off, seq_pos=None):
    """One online-softmax step over the (qb, kb) tile -- the kernel
    math shared by both emission structures (TPU scratch refs, GPU loop
    carries).  ``q`` is pre-scaled f32; k/v are f32 tiles.  ``seq_pos``
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
    """Block-indexed (TPU) attention kernel: one (qb, kb) tile per grid
    step, online-softmax state in VMEM scratch across the sequential
    grid.  ``pos_ref`` (when present) is the whole (B,) decode-position
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


def _gpu_flash_call(*, target, domain, lowering, b, h, group, m_q, m_k,
                    wb, off, block_q, block_k, d, kind, window, scale,
                    out_shape, dtype, s0, sk_arr, has_pos,
                    row_extents=None, sharded=False, rows_local=None,
                    zigzag=False, num_shards=1,
                    num_warps=None, num_stages=None):
    """gpu-structured flash attention: grid ``(batch*heads, q_rows)``,
    one program per query-block row, an in-kernel ``fori_loop`` over
    that row's key-block extent with the online-softmax state in loop
    carries (parallel grids cannot persist scratch across steps).  The
    lowering picks the extent source: ``closed_form`` computes the row
    bounds inline, ``prefetch_lut`` reads the host-built row-extents
    table as an HBM operand indexed by the program id, ``mma`` reads an
    extents operand produced on device by the digit-basis matmul chain
    (:func:`repro.core.mma.row_extents_chain`, bit-identical to the
    host table), ``bounding``
    walks the full key range and where-guards non-member tiles --
    visiting exactly the tiles (in exactly the order) the block-indexed
    structure visits, so results are bit-identical per lowering.

    ``num_stages`` >= 2 software-pipelines the key loop: the loads for
    key blocks k+1 .. k+stages-1 ride the loop carry as a FIFO, so each
    iteration issues the load for block k+stages-1 *before* the softmax
    consumes block k and the tile fetches overlap the dot-products of
    earlier blocks (on a real GPU the same knob also reaches the Triton
    scheduler via compiler params).  The FIFO rotation consumes tiles
    in exactly the synchronous order, so results stay bit-identical;
    loads past the row extent clamp to the last key block and are
    discarded unread.

    Returns ``call(*tables, q, k, v[, pos])`` where ``tables`` is the
    row-extents operand under ``prefetch_lut``/``mma`` plus the
    per-device shard-table row when ``sharded`` (global query row =
    local row + ``tbl[SHARD_ROWLO]``, or the snake row rebuilt from the
    device id at ``tbl[SHARD_DEV]`` under ``zigzag``)."""
    from repro.core.shard import SHARD_DEV, SHARD_ROWLO

    n_ext = 1 if lowering in ("prefetch_lut", "mma") else 0
    n_tbl = 1 if sharded else 0
    rows = rows_local if rows_local is not None else m_q
    kv_blocks = m_k - s0
    stages = target.resolve_stages(num_stages)

    def kern(*refs):
        i = 0
        ext_ref = refs[0] if n_ext else None
        i += n_ext
        tbl_ref = refs[i] if n_tbl else None
        i += n_tbl
        q_ref, k_ref, v_ref = refs[i:i + 3]
        i += 3
        pos_ref = refs[i] if has_pos else None
        o_ref = refs[-1]

        qb = pl.program_id(1)
        if sharded and zigzag:
            two_d = 2 * num_shards
            dev = tbl_ref[SHARD_DEV]
            qb = (qb // 2) * two_d + jnp.where(
                qb % 2 == 0, dev, two_d - 1 - dev)
        elif sharded:
            qb = qb + tbl_ref[SHARD_ROWLO]
        if lowering in ("prefetch_lut", "mma"):
            start, end = ext_ref[qb, 0], ext_ref[qb, 1]
        elif lowering == "bounding":
            start, end = 0 * qb, 0 * qb + (m_k - 1)
        else:
            start, end = _row_bounds(kind, qb, m_k, wb, off // block_q)
        pos = None
        if has_pos:
            pos = pos_ref[pl.program_id(0) // h]
            end = jnp.minimum(end, pos // block_k)
            if kind == "full" and window:
                start = jnp.maximum(
                    start, jnp.maximum(pos - window + 1, 0) // block_k)

        q = q_ref[0, 0].astype(jnp.float32) * scale

        def load_kv(ref, kb):
            # clamp unconditionally: in-range reads (all the loop ever
            # consumes) are unchanged, and pipelined prefetches past
            # the row extent stay in bounds
            kv = jnp.clip(kb - s0, 0, kv_blocks - 1)
            t = backend_lib.load(ref, (pl.ds(0, 1), pl.ds(0, 1),
                                       pl.ds(kv * block_k, block_k),
                                       pl.ds(0, d)))
            return t.reshape(block_k, d).astype(jnp.float32)

        def load_tiles(kb):
            return load_kv(k_ref, kb), load_kv(v_ref, kb)

        def update(carry, kb, tiles):
            k_t, v_t = tiles
            new = _attn_tile_update(
                q, k_t, v_t, *carry, kind=kind, window=window, qb=qb,
                kb=kb, block_q=block_q, block_k=block_k, off=off,
                seq_pos=pos)
            if lowering == "bounding" and not getattr(
                    domain, "always_member", False):
                ok = domain.contains(kb, qb)
                new = tuple(jnp.where(ok, nw, old)
                            for nw, old in zip(new, carry))
            return new

        acc0 = (jnp.zeros((block_q, d), jnp.float32),
                jnp.full((block_q, 1), NEG_INF, jnp.float32),
                jnp.zeros((block_q, 1), jnp.float32))
        n_steps = end - start + 1
        if stages <= 1:
            def step(j, carry):
                kb = start + j
                return update(carry, kb, load_tiles(kb))

            acc, _, l = jax.lax.fori_loop(0, n_steps, step, acc0)
        else:
            # software-pipelined KV FIFO: the prologue issues the loads
            # for key blocks start .. start+stages-2; each iteration
            # then loads block j+stages-1 *before* the softmax consumes
            # block j, keeping stages-1 tile fetches in flight past the
            # compute.  Consumption order equals the synchronous order.
            fifo0 = tuple(load_tiles(start + i) for i in range(stages - 1))

            def step(j, carry):
                fifo, state = carry
                nxt = load_tiles(start + j + (stages - 1))
                state = update(state, start + j, fifo[0])
                return fifo[1:] + (nxt,), state

            _, (acc, _, l) = jax.lax.fori_loop(
                0, n_steps, step, (fifo0, acc0))
        l = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0, ...] = (acc / l).astype(o_ref.dtype)

    def q_spec():
        return pl.BlockSpec((1, 1, block_q, d),
                            lambda bh, qb: (bh // h, bh % h, qb, 0))

    kv_spec = pl.BlockSpec(
        (1, 1, sk_arr, d),
        lambda bh, qb: (bh // h, (bh % h) // group, 0, 0))
    in_specs = []
    if n_ext:
        in_specs.append(full_spec(row_extents.shape))
    if n_tbl:
        in_specs.append(None)  # placeholder: shape known at call time
    in_specs += [q_spec(), kv_spec, kv_spec]
    if has_pos:
        in_specs.append(full_spec((b,)))

    interp = target.interpret
    extra = target.call_kwargs(num_warps, num_stages)

    def call(*args):
        specs = list(in_specs)
        if n_tbl:
            specs[n_ext] = full_spec(args[n_ext].shape)
        c = pl.pallas_call(
            kern, grid=(b * h, rows), in_specs=specs,
            out_specs=q_spec(),
            out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
            interpret=interp, **extra)
        return c(*args)

    if n_ext:
        ext = jnp.asarray(row_extents)
        return lambda *args: call(ext, *args)
    return call


@functools.partial(jax.jit, static_argnames=(
    "kind", "window", "scale", "block_q", "block_k", "grid_mode",
    "storage", "kv_seq_len", "backend", "num_warps", "num_stages",
    "mesh", "shard_axis", "shard_balance", "verify"))
def _flash_impl(q, k, v, seq_pos=None, *, kind, window, scale, block_q,
                block_k, grid_mode, storage, kv_seq_len, backend,
                num_warps=None, num_stages=None, mesh=None,
                shard_axis="data", shard_balance="contiguous",
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
        # step initializes the output on the sequential structure and
        # the gpu loop runs empty -- garbage, not a defined result.
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
            if target.block_indexed and grid_mode in ("closed_form",
                                                      "compact"):
                # the snake's owned rows are scattered: the sequential
                # structure decodes them through the LUT (bit-identical
                # to the closed form by the engine's contract)
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

    if not target.block_indexed:
        lowering = plan.lowering
        if lowering == "prefetch_lut":
            extents = plan.row_extents()
        elif lowering == "mma":
            from repro.core import mma
            extents = mma.row_extents_chain(domain)
        else:
            extents = None
        call = _gpu_flash_call(
            target=target, domain=domain, lowering=lowering, b=b, h=h,
            group=group, m_q=m_q, m_k=m_k, wb=wb, off=off,
            block_q=block_q, block_k=block_k, d=d, kind=kind,
            window=window, scale=scale, out_shape=out_shape,
            dtype=q.dtype, s0=s0, sk_arr=sk_arr, has_pos=has_pos,
            row_extents=extents, sharded=mesh is not None,
            rows_local=(m_q // int(mesh.shape[shard_axis])
                        if mesh is not None else None),
            zigzag=zz_perm is not None,
            num_shards=(int(mesh.shape[shard_axis])
                        if mesh is not None else 1),
            num_warps=num_warps, num_stages=num_stages)
        if mesh is None:
            return call(q, k, v, *pos_operand)
    else:
        def q_place(bx, by, bh):
            return (bh // h, bh % h, by, 0)

        def kv_place(bx, by, bh):
            kb = jnp.clip(bx - s0, 0, m_k - s0 - 1) if s0 else bx
            return (bh // h, (bh % h) // group, kb, 0)

        kernel = functools.partial(
            _attn_kernel, kind=kind, window=window, scale=scale,
            block_q=block_q, block_k=block_k, m_k=m_k, wb=wb, off=off,
            h=h, has_pos=has_pos)

        in_specs = [
            plan.block_spec((1, 1, block_q, d), q_place),
            plan.block_spec((1, 1, block_k, d), kv_place),
            plan.block_spec((1, 1, block_k, d), kv_place),
        ]
        if has_pos:
            in_specs.append(target.scalar_spec())
        call = plan.pallas_call(
            kernel,
            in_specs=in_specs,
            out_specs=plan.block_spec((1, 1, block_q, d), q_place),
            out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
            scratch_shapes=[
                target.scratch((block_q, d), jnp.float32),
                target.scratch((block_q, 1), jnp.float32),
                target.scratch((block_q, 1), jnp.float32),
            ],
            num_warps=num_warps, num_stages=num_stages,
        )
        if mesh is None:
            return call(q, k, v, *pos_operand)

    # shard the query-block axis: q/o split along the sequence dim,
    # k/v replicated; each device runs its contiguous query-row band
    # (whole rows, so the online-softmax state never crosses devices).
    from jax.sharding import PartitionSpec as P

    from repro.core.shard import device_tables

    axis = shard_axis
    if target.block_indexed:
        tbl, luts = device_tables(plan)
    else:
        # gpu structure reads only the shard-table row in-kernel (the
        # prefetch_lut/mma extents table is bound inside the call), so
        # skip building/transferring the chunked decode LUT entirely
        tbl, luts = jnp.asarray(plan.shard_table_host()), ()
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
                    backend=None, num_warps: int | str | None = None,
                    num_stages: int | str | None = None,
                    interpret: bool | None = None, mesh=None,
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
               are predicated off (an SMEM vector on TPU, a regular
               operand on GPU).  The gpu structure's loop bound
               truncates the tile *reads* too; the TPU structure's
               static grid still pipelines every tile and skips only
               their compute.
    backend:   emission target ("tpu" | "gpu" | "*-interpret" | None =
               platform default; see :mod:`repro.core.backend`).  The
               gpu structure runs one program per query-block row with
               an in-kernel loop over its key extent; ``num_stages``
               >= 2 ("auto" = tuned) software-pipelines that loop (a
               KV-tile FIFO in the loop carry prefetches key block
               k+stages-1 while the softmax consumes block k;
               bit-identical to the synchronous loop) and, on a real
               GPU, also reaches the Triton scheduler together with
               ``num_warps``.  The TPU structure accepts the knob but
               keeps it at the grid level: Mosaic already
               double-buffers BlockSpec operand copies across the
               sequential grid.
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
    grid_mode, block_q, block_k, num_warps, num_stages = \
        resolve_auto_schedule(
            "flash",
            tune.target_params(
                tune.shard_params(
                    {"kind": kind, "batch": b, "heads": h,
                     "kv_heads": hkv, "sq": sq, "sk": sk, "d": d,
                     "window": window},
                    mesh, shard_axis),
                target),
            grid_mode=(grid_mode, "lowering", "closed_form"),
            block_q=(block_q, "block_q", 128),
            block_k=(block_k, "block_k", 128),
            num_warps=(num_warps, "num_warps", None),
            num_stages=(num_stages, "num_stages", None))
    return _flash_impl(q, k, v, seq_pos, kind=kind, window=window,
                       scale=scale, block_q=block_q, block_k=block_k,
                       grid_mode=grid_mode, storage=storage,
                       kv_seq_len=kv_seq_len, backend=target,
                       num_warps=num_warps, num_stages=num_stages,
                       mesh=mesh, shard_axis=shard_axis,
                       shard_balance=shard_balance, verify=verify)


# ---------------------------------------------------------------------------
# paged decode: the page table rides the scalar-prefetch LUT mechanism
# ---------------------------------------------------------------------------

def _paged_attn_kernel(coords, *refs, window, scale, page_size, h,
                       has_window):
    """Block-indexed (TPU) paged decode kernel.  One grid step per
    (slot*head, logical key block); the *physical* page was already
    resolved by the KV BlockSpec index map reading the prefetched page
    table, so the kernel sees a ``(1, 2, page_size, d)`` fused tile:
    row 0 of the head-pair axis is K, row 1 is V.  Masking uses the
    *logical* block id (``coords.bx``), so results are bit-identical to
    the contiguous ``seq_pos`` path."""
    q_ref, kv_ref, pos_ref, o_ref, acc_ref, m_ref, l_ref = refs
    kb = coords.bx
    pos = pos_ref[coords.batch[0] // h]
    start = 0 * kb
    end = pos // page_size
    if has_window:
        start = jnp.maximum(pos - window + 1, 0) // page_size

    def body():
        @pl.when(kb == start)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = kv_ref[0, 0].astype(jnp.float32)
        v = kv_ref[0, 1].astype(jnp.float32)
        acc_new, m_new, l_new = _attn_tile_update(
            q, k, v, acc_ref[...], m_ref[...], l_ref[...], kind="full",
            window=window if has_window else 0, qb=0 * kb, kb=kb,
            block_q=1, block_k=page_size, off=0, seq_pos=pos)
        acc_ref[...] = acc_new
        m_ref[...] = m_new
        l_ref[...] = l_new

        @pl.when(kb == end)
        def _():
            l = l_ref[...]
            l = jnp.where(l == 0, 1.0, l)
            o_ref[0, 0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)

    live = (kb <= end) & (kb >= start)
    if coords.valid is None:
        pl.when(live)(body)
    else:
        pl.when(coords.valid & live)(body)


def _gpu_paged_call(*, target, b, h, group, m_k, page_size, d, window,
                    scale, out_shape, dtype, num_warps=None,
                    num_stages=None):
    """gpu-structured paged decode: one program per (slot, head), the
    whole pool and page table as HBM operands, an in-kernel loop over
    the slot's logical key blocks that resolves each physical page with
    a table read and ``backend.load``\\ s the fused ``(2, page_size, d)``
    head tile at its offset.  The loop bound comes from the slot's
    ``seq_pos``, so only O(pos / page_size) pages are *read* -- the
    block-space work saving at run time."""

    def kern(pt_ref, q_ref, kv_ref, pos_ref, o_ref):
        bh = pl.program_id(0)
        slot = bh // h
        kvh = (bh % h) // group
        pos = pos_ref[slot]
        start = 0 * pos
        end = pos // page_size
        if window:
            start = jnp.maximum(pos - window + 1, 0) // page_size
        q = q_ref[0, 0].astype(jnp.float32) * scale

        def load_tiles(kb):
            page = pt_ref[slot, kb]
            t = backend_lib.load(
                kv_ref, (pl.ds(page, 1), pl.ds(2 * kvh, 2),
                         pl.ds(0, page_size), pl.ds(0, d)))
            t = t.reshape(2, page_size, d).astype(jnp.float32)
            return t[0], t[1]

        def step(j, carry):
            kb = start + j
            k_t, v_t = load_tiles(kb)
            return _attn_tile_update(
                q, k_t, v_t, *carry, kind="full", window=window,
                qb=0 * kb, kb=kb, block_q=1, block_k=page_size, off=0,
                seq_pos=pos)

        acc0 = (jnp.zeros((1, d), jnp.float32),
                jnp.full((1, 1), NEG_INF, jnp.float32),
                jnp.zeros((1, 1), jnp.float32))
        acc, _, l = jax.lax.fori_loop(0, end - start + 1, step, acc0)
        l = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0, ...] = (acc / l).astype(o_ref.dtype)

    q_spec = pl.BlockSpec((1, 1, 1, d), lambda bh: (bh // h, bh % h, 0, 0))
    extra = target.call_kwargs(num_warps, num_stages)

    def call(pt, q, kv_pool, pos):
        c = pl.pallas_call(
            kern, grid=(b * h,),
            in_specs=[full_spec(pt.shape), q_spec,
                      full_spec(kv_pool.shape), full_spec((b,))],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
            interpret=target.interpret, **extra)
        return c(pt, q, kv_pool, pos)

    return call


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "grid_mode", "backend", "num_warps",
    "num_stages", "verify"))
def _paged_impl(q, kv_pool, page_table, seq_pos, *, window, scale,
                grid_mode, backend, num_warps=None, num_stages=None,
                verify=False):
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

    domain = make_attention_domain("full", 1, m_k, 0)
    if verify:
        from repro.analysis import verify_or_raise
        verify_or_raise(GridPlan(domain, grid_mode, batch_dims=(b * h,),
                                 backend=target), kernel="flash")

    if not target.block_indexed:
        call = _gpu_paged_call(
            target=target, b=b, h=h, group=group, m_k=m_k,
            page_size=page_size, d=d, window=window, scale=scale,
            out_shape=q.shape, dtype=q.dtype, num_warps=num_warps,
            num_stages=num_stages)
        return call(page_table, q, kv_pool, pos)

    plan = PagedPlan(domain, grid_mode, batch_dims=(b * h,),
                     backend=target, page_table=page_table)

    def q_place(bx, by, bh):
        return (bh // h, bh % h, 0, 0)

    def kv_index(grid_ids, refs):
        # refs[0] is the prefetched page table; the decoded bx is the
        # *logical* key block, translated here to its physical page.
        _, bx, _ = plan._decode(grid_ids, refs)
        bh = grid_ids[0]
        page = refs[0][bh // h, bx]
        return (page, (bh % h) // group, 0, 0)

    kernel = functools.partial(
        _paged_attn_kernel, window=window, scale=scale,
        page_size=page_size, h=h, has_window=bool(window))
    call = plan.pallas_call(
        kernel,
        in_specs=[
            plan.block_spec((1, 1, 1, d), q_place),
            plan._index_spec((1, 2, page_size, d), kv_index),
            target.scalar_spec(),
        ],
        out_specs=plan.block_spec((1, 1, 1, d), q_place),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            target.scratch((1, d), jnp.float32),
            target.scratch((1, 1), jnp.float32),
            target.scratch((1, 1), jnp.float32),
        ],
        num_warps=num_warps, num_stages=num_stages,
    )
    return call(q, kv_pool, pos)


def paged_flash_attention(q, kv_pool, page_table, seq_pos, *,
                          window: int = 0, scale: float | None = None,
                          grid_mode: str = "compact", backend=None,
                          num_warps: int | None = None,
                          num_stages: int | None = None,
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
                pages beyond ``pos // page_size`` are never touched on
                the gpu structure and compute-predicated off on the TPU
                structure.
    window:     optional run-time sliding window anchored at seq_pos.

    The page table travels exactly like the engine's decode LUT: a
    scalar-prefetch operand on block-indexed targets (resolved in the
    KV BlockSpec index map -- the lambda-map indirection of the paper,
    pointed at physical memory), a leading HBM operand read in-kernel
    on gpu structures.  Bit-identical to the contiguous
    ``flash_attention(..., kind="full", seq_pos=...)`` path with
    ``block_k == page_size`` when the mapped pages hold the same
    values."""
    target = backend_lib.resolve(backend, interpret)
    from repro.core.plan import normalize_lowering
    return _paged_impl(q, kv_pool, page_table, seq_pos, window=window,
                       scale=scale,
                       grid_mode=normalize_lowering(grid_mode),
                       backend=target, num_warps=num_warps,
                       num_stages=num_stages, verify=verify)
