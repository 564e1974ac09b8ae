"""The paper's SS IV microbenchmark as Pallas kernels, lowered through
the unified :class:`~repro.core.plan.GridPlan` engine on any
:mod:`~repro.core.backend` target (TPU Mosaic, GPU Triton, or either
under the interpreter).

Three lowerings, extending the paper's A/B to the LUT variant of the
follow-up work:

* ``closed_form`` (alias ``compact``) -- the lambda(w) map: the grid has
  ``domain.num_blocks`` steps and ``BlockSpec.index_map`` computes
  lambda inline on the scalar core (the TPU-native realization of the
  paper's per-block map).
* ``prefetch_lut`` -- the same enumeration shipped as a host-built
  coordinate table via scalar prefetch: the decode becomes an O(1)
  table read instead of the O(r) digit unrolling.
* ``bounding`` -- the bounding-box baseline: n_b x n_b grid steps, with
  the run-time discard ``pl.when(block is member)``.
* ``auto`` -- resolve the lowering (and coarsening, when left at
  ``"auto"``) from the :mod:`~repro.core.tune` cache for this problem
  and backend; falls back to ``closed_form`` when never tuned.

Two storages (the ``storage=`` axis of GridPlan):

* ``embedded`` -- the state array is the dense n x n bounding-box
  layout (O(n^2) memory); blocks never visited by a compact grid keep
  their previous contents via input/output aliasing.
* ``compact`` -- the state array lives in the packed orthotope layout
  of Lemma 2 (O(n^H) memory, ``CompactLayout``); the same kernels run
  with their storage-operand index maps rewritten to packed slots.

Superblock coarsening (the ``coarsen=`` axis): each grid step owns an
s x s tile of fine blocks -- lambda decoded once per superblock, the
per-cell embedded offsets baked into the (static) supertile offset
grids -- amortizing the decode by the tile's member count.

Intra-block threads use the paper's *bounding sub-boxes* option: a VPU
mask from ``broadcasted_iota`` (or, under packed coarsening, the static
offset grids) evaluating the domain's cell-membership test (the
gasket's ``x & (n-1-y) == 0`` bit test, or the generalized base-m digit
test for carpet / Vicsek / any registered FractalSpec).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import backend as backend_lib
from repro.core.backend import full_spec
from repro.core.domain import BlockDomain, make_fractal_domain
from repro.core.plan import GridPlan, normalize_storage


def resolve_fractal_domain(fractal: str, n: int, block: int) -> BlockDomain:
    """Validated block-grid domain for an embedded n x n fractal state.

    Raises a clear ValueError when ``block`` does not divide ``n`` (a
    truncated block grid would silently drop fractal coverage: e.g. a
    16 x 16 gasket at block=6 only reaches 45 of its 81 member cells) or
    when the resulting blocks-per-side is not a power of the fractal's
    subdivision factor.
    """
    if n % block:
        raise ValueError(
            f"block={block} must divide n={n} (remainder {n % block}): "
            f"the {n // block}-block grid would silently truncate "
            f"fractal coverage")
    n_b = n // block
    try:
        return make_fractal_domain(fractal, n_b)
    except ValueError as e:
        raise ValueError(
            f"n/block = {n_b} blocks per side is not a valid scale level "
            f"of fractal {fractal!r}: {e}") from None


def resolve_storage_args(m, block, fractal, storage, n, domain):
    """Shared entry-point validation for the fractal-state kernels.

    Returns (domain, n, block, storage) with the state array ``m``
    checked against the storage layout's expected shape.  ``n`` (the
    embedded side length) must be passed explicitly under compact
    storage when no ``domain`` is given, since the packed array's shape
    no longer determines it.
    """
    storage = normalize_storage(storage)
    if domain is None:
        if n is None:
            if storage == "compact":
                raise ValueError(
                    "storage='compact' needs the embedded size n= (or an "
                    "explicit domain=): the packed array shape does not "
                    "determine it")
            if m.ndim != 2 or m.shape[0] != m.shape[1]:
                raise ValueError(f"expected square 2-D state, got {m.shape}")
            n = m.shape[0]
        block = min(block, n)
        domain = resolve_fractal_domain(fractal, n, block)
    else:
        nbx, nby = domain.bounding_box
        if n is None:
            n = nby * block
    plan = GridPlan(domain, storage=storage)
    want = plan.layout.array_shape(block) if storage == "compact" \
        else plan.layout.embedded_shape(block)
    if tuple(m.shape) != want:
        raise ValueError(
            f"{storage} state shape {tuple(m.shape)} does not match the "
            f"expected {want} for block={block}")
    return domain, n, block, storage


def resolve_auto_schedule(kernel: str, params: dict, **knobs):
    """Resolve ``"auto"`` scheduling knobs from the tune cache.

    ``knobs`` maps knob name -> (current value, config key, default);
    returns the knob values with every ``"auto"`` replaced by the tuned
    value (or the default when this problem was never tuned).  Values
    the caller fixed explicitly are passed through untouched, so a
    tuned lowering never overrides an explicit ``coarsen=``.
    """
    if not any(v == "auto" for v, _, _ in knobs.values()):
        return tuple(v for v, _, _ in knobs.values())
    from repro.core import tune
    cfg = tune.best(kernel, params) or {}
    return tuple(cfg.get(key, default) if value == "auto" else value
                 for value, key, default in knobs.values())


def _cell_mask(domain: BlockDomain, bx, by, block: int, n: int):
    """VPU cell-membership mask for the (bx, by) fine tile (bounding
    sub-boxes intra-block option); (bx, by) are embedded block coords
    under either storage."""
    iy = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    ix = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    gx = bx * block + ix
    gy = by * block + iy
    return domain.cell_member(gx, gy, n)


def _tile_mask(plan: GridPlan, bx, by, block: int, n: int):
    """Cell-membership mask over one storage supertile of the plan.

    (bx, by) are the *scheduled* (coarse) block coords.  For the
    trivial layouts this is exactly :func:`_cell_mask`; under packed
    coarsening the static offset grids bake the fine-block permutation
    in, so the mask is evaluated directly in packed arrangement."""
    span = plan.coarsen * block
    tm = plan.tile_map()
    th, tw = plan.supertile_shape((block, block))
    if tm is None:
        oy = jax.lax.broadcasted_iota(jnp.int32, (th, tw), 0)
        ox = jax.lax.broadcasted_iota(jnp.int32, (th, tw), 1)
        return plan.domain.cell_member(bx * span + ox, by * span + oy, n)
    # packed coarsening: evaluate per fine sub-block (static loop over
    # the tile permutation -- Pallas kernels cannot capture host array
    # constants, so the offsets enter as scalar adds on iota)
    iy = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    ix = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    at = dict(tm)
    off = jnp.zeros((block, block), jnp.bool_)
    return tile_grid([[plan.domain.cell_member(
        bx * span + at[(py, px)][1] * block + ix,
        by * span + at[(py, px)][0] * block + iy, n)
        if (py, px) in at else off
        for px in range(tw // block)] for py in range(th // block)])


def tile_grid(rows):
    """Assemble a 2-D grid of tiles (a list of rows, each a list of
    arrays of equal height) by concatenation -- the Mosaic-lowerable
    way to build a larger array from static pieces."""
    return jnp.concatenate([jnp.concatenate(r, axis=1) for r in rows],
                           axis=0)


def _write_kernel(coords, m_ref, o_ref, *, value, block, n, plan):
    def body():
        mask = _tile_mask(plan, coords.bx, coords.by, block, n)
        o_ref[...] = jnp.where(mask, jnp.asarray(value, o_ref.dtype),
                               m_ref[...])

    def pass_through():
        o_ref[...] = m_ref[...]

    coords.when_valid(body, pass_through if plan.skip_fills_output
                      else None)


def _stream_storage_tile(coords, m_ref, bufs_ref, sems, plan, stages):
    """This grid step's storage supertile, streamed out of the
    ``pl.ANY``-resident state through the rotating async-copy
    buffers (the copy for step t+stages-1 starts before this step's
    compute; see :func:`repro.core.backend.stream_tiles`)."""
    lin = plan.linear_step(coords.grid_ids)

    def srcs_for(step):
        return [plan.storage_index(plan.grid_ids_at(step), coords.refs)]

    return backend_lib.stream_tiles(
        m_ref, bufs_ref, sems, srcs_for=srcs_for, lin=lin,
        total=plan.steps_per_launch, stages=stages)[0]


def _write_kernel_dma(coords, m_ref, alias_ref, o_ref, bufs_ref, sems,
                      *, value, block, n, plan, stages):
    """Async-copy pipelined write (TPU structure, ``num_stages`` >= 2):
    the state is parked in ``pl.ANY`` and each step's input tile
    streams through rotating VMEM DMA buffers while the next step's
    copy is in flight.  ``alias_ref`` is the same state routed as a
    BlockSpec operand purely to alias the unwritten remainder to the
    output; the kernel never reads it."""
    del alias_ref
    tile = _stream_storage_tile(coords, m_ref, bufs_ref, sems, plan,
                                stages)

    def body():
        mask = _tile_mask(plan, coords.bx, coords.by, block, n)
        o_ref[...] = jnp.where(mask, jnp.asarray(value, o_ref.dtype),
                               tile.astype(o_ref.dtype))

    def pass_through():
        o_ref[...] = tile.astype(o_ref.dtype)

    coords.when_valid(body, pass_through if plan.skip_fills_output
                      else None)


def _write_kernel_gpu(coords, m_ref, o_ref, *, value, block, n, plan):
    """gpu-structured write: the state arrives whole; the kernel
    resolves its supertile offset itself (the plan's storage index,
    reading the HBM LUT operand under ``prefetch_lut``) and
    loads/stores with computed offsets."""
    th, tw = plan.supertile_shape((block, block))

    def body():
        iy, ix = plan.storage_index(coords.grid_ids, coords.refs)
        idx = (pl.ds(iy * th, th), pl.ds(ix * tw, tw))
        tile = backend_lib.load(m_ref, idx)
        mask = _tile_mask(plan, coords.bx, coords.by, block, n)
        backend_lib.store(
            o_ref, idx,
            jnp.where(mask, jnp.asarray(value, o_ref.dtype), tile))

    coords.when_valid(body)


def _emit_write(plan: GridPlan, shape, dtype, *, value, block, n,
                stages=1):
    """The write pallas_call for either emission structure: BlockSpec
    tiles on block-indexed (TPU) targets, whole-array refs + in-kernel
    addressing on GPU targets.  The unwritten remainder keeps the input
    through the output alias either way.  ``stages >= 2`` on an
    async-copy target streams the input tiles through rotating DMA
    buffers instead (:func:`_write_kernel_dma`); on the GPU structure
    it only feeds the Triton scheduler."""
    target = plan.target
    stages = target.resolve_stages(stages)
    plan.check_output_writeback()
    if target.block_indexed and stages > 1:
        spec = plan.storage_spec((block, block))
        th, tw = plan.supertile_shape((block, block))
        call = plan.pallas_call(
            functools.partial(_write_kernel_dma, value=value,
                              block=block, n=n, plan=plan,
                              stages=stages),
            in_specs=[target.any_spec(), spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            scratch_shapes=[
                target.scratch((stages, 1, th, tw), dtype),
                target.dma_sems((stages, 1)),
            ],
            input_output_aliases={1: 0},
        )
        # the state rides twice: ANY (DMA source) + BlockSpec (alias)
        return lambda *args: call(*args[:-1], args[-1], args[-1])
    if target.block_indexed:
        spec = plan.storage_spec((block, block))
        return plan.pallas_call(
            functools.partial(_write_kernel, value=value, block=block,
                              n=n, plan=plan),
            in_specs=[spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            input_output_aliases={0: 0},
        )
    return plan.pallas_call(
        functools.partial(_write_kernel_gpu, value=value, block=block,
                          n=n, plan=plan),
        in_specs=[full_spec(shape)],
        out_specs=full_spec(shape),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        input_output_aliases={0: 0},
        num_stages=stages if stages > 1 else None,
    )


@functools.partial(jax.jit,
                   static_argnames=("value", "block", "grid_mode",
                                    "fractal", "storage", "n", "domain",
                                    "coarsen", "backend", "stages",
                                    "verify"))
def _write_impl(m, value, *, block, grid_mode, fractal, storage, n,
                domain, coarsen, backend, stages=1, verify=False):
    domain, n, block, storage = resolve_storage_args(
        m, block, fractal, storage, n, domain)
    plan = GridPlan(domain, grid_mode, storage=storage, coarsen=coarsen,
                    backend=backend)
    if verify:
        from repro.analysis import verify_or_raise
        verify_or_raise(plan, kernel="write")
    call = _emit_write(plan, m.shape, m.dtype, value=value, block=block,
                       n=n, stages=stages)
    return call(m)


def _sharded_setup(m, *, block, grid_mode, fractal, storage, n, domain,
                   coarsen, mesh, shard_axis, backend):
    """Shared ShardedPlan + per-device-table construction for the
    sharded write/sum drivers."""
    from repro.core.shard import ShardedPlan, device_tables

    domain, n, block, storage = resolve_storage_args(
        m, block, fractal, storage, n, domain)
    plan = ShardedPlan(domain, grid_mode, storage=storage,
                       coarsen=coarsen, backend=backend, mesh=mesh,
                       axis=shard_axis)
    tbl, luts = device_tables(plan)
    return plan, domain, n, block, storage, tbl, luts


@functools.partial(jax.jit,
                   static_argnames=("value", "block", "grid_mode",
                                    "fractal", "storage", "n", "domain",
                                    "coarsen", "backend", "mesh",
                                    "shard_axis", "stages", "verify"))
def _write_sharded_impl(m, value, *, block, grid_mode, fractal, storage,
                        n, domain, coarsen, backend, mesh, shard_axis,
                        stages=1, verify=False):
    """Sharded write: each device writes its share of the domain.
    Compact storage writes its orthotope row slab in place; embedded
    storage combines the replicated per-device results with a disjoint
    ownership-mask psum (member blocks have exactly one owner, the rest
    pass the input through)."""
    from jax.sharding import PartitionSpec as P

    plan, domain, n, block, storage, tbl, luts = _sharded_setup(
        m, block=block, grid_mode=grid_mode, fractal=fractal,
        storage=storage, n=n, domain=domain, coarsen=coarsen, mesh=mesh,
        shard_axis=shard_axis, backend=backend)
    if verify:
        from repro.analysis import verify_or_raise
        verify_or_raise(plan, kernel="write")
    call = _emit_write(plan, plan.local_storage_shape(block), m.dtype,
                       value=value, block=block, n=n, stages=stages)
    axis = shard_axis
    lut_specs = tuple(P(axis, None) for _ in luts)
    if storage == "compact":
        a = plan.pad_rows(m, block)
        out = jax.shard_map(
            lambda tbl, luts, a: call(tbl.reshape(-1), *luts, a),
            mesh=mesh,
            in_specs=(P(axis, None), lut_specs, P(axis, None)),
            out_specs=P(axis, None), check_vma=False)(tbl, luts, a)
        return plan.unpad_rows(out, block)

    def device_fn(tbl, luts, a):
        tbl1 = tbl.reshape(-1)
        part = call(tbl1, *luts, a)
        owned = plan.owned_cell_mask(tbl1, n, block)
        member = plan.member_cell_block_mask(n, block)
        return jax.lax.psum(jnp.where(owned, part, 0), axis) \
            + jnp.where(member, 0, a).astype(part.dtype)

    return jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(P(axis, None), lut_specs, P(None, None)),
        out_specs=P(None, None), check_vma=False)(tbl, luts, m)


def sierpinski_write(m: jnp.ndarray, value: float = 1.0, *,
                     block: int = 128, grid_mode: str = "compact",
                     fractal: str = "sierpinski-gasket",
                     storage: str = "embedded", n: int | None = None,
                     domain: BlockDomain | None = None,
                     coarsen: int | str = 1,
                     num_stages: int | str = "auto", backend=None,
                     interpret: bool | None = None, mesh=None,
                     shard_axis: str = "data",
                     verify: bool = False) -> jnp.ndarray:
    """Write ``value`` to every fractal cell of the (n, n) state.

    grid_mode: closed_form (alias compact) | prefetch_lut | bounding |
    mma (digit-basis matmul decode, :mod:`repro.core.mma`) |
    auto (tune-cache lookup); fractal: any registered FractalSpec name;
    storage: embedded (m is the dense n x n array) | compact (m is the
    packed orthotope array, pass n= or domain=); coarsen: superblock
    side in fine blocks (or "auto"); backend: emission target
    ("tpu" | "gpu" | "*-interpret" | None = platform default, see
    :mod:`repro.core.backend`); num_stages: software-pipeline depth
    (">= 2" streams input tiles through async-copy DMA buffers on
    capable targets, "auto" = tuned; bit-identical either way);
    mesh/shard_axis: shard the write across
    a mesh axis (embarrassing: disjoint block ownership, psum combine
    under embedded storage); verify: statically verify the emitted plan
    (coverage / races / tables / bounds, :mod:`repro.analysis`) at
    trace time, raising on any violation -- a debug flag, off by
    default."""
    target = backend_lib.resolve(backend, interpret)
    from repro.core import tune
    grid_mode, coarsen, num_stages = resolve_auto_schedule(
        "write",
        tune.target_params(
            tune.shard_params(
                {"fractal": fractal, "n": n or m.shape[0],
                 "block": block},
                mesh, shard_axis),
            target),
        grid_mode=(grid_mode, "lowering", "closed_form"),
        coarsen=(coarsen, "coarsen", 1),
        num_stages=(num_stages, "stages", 1))
    kw = dict(block=block, grid_mode=grid_mode, fractal=fractal,
              storage=storage, n=n, domain=domain, coarsen=coarsen,
              backend=target, stages=target.resolve_stages(num_stages),
              verify=verify)
    if mesh is not None:
        return _write_sharded_impl(m, value, mesh=mesh,
                                   shard_axis=shard_axis, **kw)
    return _write_impl(m, value, **kw)


def _sum_kernel(coords, m_ref, o_ref, *, block, n, plan):
    @pl.when(coords.first_step)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def body():
        mask = _tile_mask(plan, coords.bx, coords.by, block, n)
        tile = jnp.where(mask, m_ref[...], 0).astype(jnp.float32)
        # a (1, 1) vector add: Mosaic cannot store a scalar into VMEM
        o_ref[...] += jnp.sum(tile, keepdims=True)

    coords.when_valid(body)


def _sum_kernel_dma(coords, m_ref, o_ref, bufs_ref, sems, *, block, n,
                    plan, stages):
    """Async-copy pipelined sum: the sequential accumulate of
    :func:`_sum_kernel` with the input tile streamed through rotating
    DMA buffers, so the next tile's copy flies during this tile's
    reduction.  Same grid, same accumulation order: bit-identical."""
    tile = _stream_storage_tile(coords, m_ref, bufs_ref, sems, plan,
                                stages)

    @pl.when(coords.first_step)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    def body():
        mask = _tile_mask(plan, coords.bx, coords.by, block, n)
        o_ref[...] += jnp.sum(
            jnp.where(mask, tile, 0).astype(jnp.float32), keepdims=True)

    coords.when_valid(body)


def _sum_kernel_gpu(coords, m_ref, o_ref, *, block, n, plan):
    """gpu-structured sum: a parallel grid cannot revisit one
    accumulator, so each step stores its per-tile partial at its step
    slot; the driver reduces the slots *in step order*, reproducing the
    sequential grid's accumulation bit-for-bit."""
    th, tw = plan.supertile_shape((block, block))
    t = plan.linear_step(coords.grid_ids)
    out_idx = (pl.ds(t, 1), pl.ds(0, 1))
    backend_lib.store(o_ref, out_idx, jnp.zeros((1, 1), jnp.float32))

    def body():
        iy, ix = plan.storage_index(coords.grid_ids, coords.refs)
        tile = backend_lib.load(
            m_ref, (pl.ds(iy * th, th), pl.ds(ix * tw, tw)))
        mask = _tile_mask(plan, coords.bx, coords.by, block, n)
        part = jnp.sum(jnp.where(mask, tile, 0).astype(jnp.float32))
        backend_lib.store(o_ref, out_idx, part.reshape(1, 1))

    coords.when_valid(body)


def _emit_sum(plan: GridPlan, shape, *, block, n, stages=1,
              dtype=jnp.float32):
    """The sum pallas_call for either structure.  Returns
    ``(call, finish)`` where ``finish`` maps the raw kernel output to
    the (1, 1) f32 total: identity on sequential-grid targets (the
    kernel accumulated in place), an in-step-order partials reduction
    on parallel-grid targets.  ``stages >= 2`` streams the input tiles
    through async-copy DMA buffers on capable targets."""
    target = plan.target
    stages = target.resolve_stages(stages)
    if target.sequential_grid and stages > 1 and target.async_copy:
        th, tw = plan.supertile_shape((block, block))
        call = plan.pallas_call(
            functools.partial(_sum_kernel_dma, block=block, n=n,
                              plan=plan, stages=stages),
            in_specs=[target.any_spec()],
            out_specs=plan.block_spec((1, 1), lambda bx, by: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
            scratch_shapes=[
                target.scratch((stages, 1, th, tw), dtype),
                target.dma_sems((stages, 1)),
            ],
        )
        return call, lambda out: out
    if target.sequential_grid:
        call = plan.pallas_call(
            functools.partial(_sum_kernel, block=block, n=n, plan=plan),
            in_specs=[plan.storage_spec((block, block))],
            out_specs=plan.block_spec((1, 1), lambda bx, by: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        )
        return call, lambda out: out
    steps = plan.steps_per_launch
    call = plan.pallas_call(
        functools.partial(_sum_kernel_gpu, block=block, n=n, plan=plan),
        in_specs=[full_spec(shape)],
        out_specs=full_spec((steps, 1)),
        out_shape=jax.ShapeDtypeStruct((steps, 1), jnp.float32),
        num_stages=stages if stages > 1 else None,
    )

    def finish(partials):
        total = jax.lax.fori_loop(
            0, steps, lambda i, acc: acc + partials[i, 0],
            jnp.float32(0))
        return total.reshape(1, 1)
    return call, finish


@functools.partial(jax.jit, static_argnames=("block", "grid_mode",
                                             "fractal", "storage", "n",
                                             "domain", "coarsen",
                                             "backend", "stages",
                                             "verify"))
def _sum_impl(m, *, block, grid_mode, fractal, storage, n, domain,
              coarsen, backend, stages=1, verify=False):
    domain, n, block, storage = resolve_storage_args(
        m, block, fractal, storage, n, domain)
    plan = GridPlan(domain, grid_mode, storage=storage, coarsen=coarsen,
                    backend=backend)
    if verify:
        from repro.analysis import verify_or_raise
        verify_or_raise(plan, kernel="sum")
    call, finish = _emit_sum(plan, m.shape, block=block, n=n,
                             stages=stages, dtype=m.dtype)
    return finish(call(m))[0, 0]


@functools.partial(jax.jit, static_argnames=("block", "grid_mode",
                                             "fractal", "storage", "n",
                                             "domain", "coarsen",
                                             "backend", "mesh",
                                             "shard_axis", "stages",
                                             "verify"))
def _sum_sharded_impl(m, *, block, grid_mode, fractal, storage, n,
                      domain, coarsen, backend, mesh, shard_axis,
                      stages=1, verify=False):
    """Sharded sum: each device accumulates its owned blocks, one psum
    reduces across the axis.  The per-device accumulation order differs
    from the single-device grid order, so results agree to float
    tolerance (exactly, for integer-valued states)."""
    from jax.sharding import PartitionSpec as P

    plan, domain, n, block, storage, tbl, luts = _sharded_setup(
        m, block=block, grid_mode=grid_mode, fractal=fractal,
        storage=storage, n=n, domain=domain, coarsen=coarsen, mesh=mesh,
        shard_axis=shard_axis, backend=backend)
    if verify:
        from repro.analysis import verify_or_raise
        verify_or_raise(plan, kernel="sum")
    local_shape = plan.local_storage_shape(block)
    call, finish = _emit_sum(plan, local_shape, block=block, n=n,
                             stages=stages, dtype=m.dtype)
    axis = shard_axis
    lut_specs = tuple(P(axis, None) for _ in luts)
    state_spec = P(axis, None) if storage == "compact" else P(None, None)
    a = plan.pad_rows(m, block) if storage == "compact" else m

    def device_fn(tbl, luts, a):
        part = finish(call(tbl.reshape(-1), *luts, a))
        return jax.lax.psum(part, axis)

    out = jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(P(axis, None), lut_specs, state_spec),
        out_specs=P(None, None), check_vma=False)(tbl, luts, a)
    return out[0, 0]


def sierpinski_sum(m: jnp.ndarray, *, block: int = 128,
                   grid_mode: str = "compact",
                   fractal: str = "sierpinski-gasket",
                   storage: str = "embedded", n: int | None = None,
                   domain: BlockDomain | None = None,
                   coarsen: int | str = 1,
                   num_stages: int | str = "auto", backend=None,
                   interpret: bool | None = None, mesh=None,
                   shard_axis: str = "data",
                   verify: bool = False) -> jnp.ndarray:
    """f32 sum over fractal cells, sequential accumulate over the plan's
    grid (any lowering; the output block is revisited every step).  The
    grid enumeration -- and therefore the accumulation order -- depends
    only on (domain, grid_mode), so compact and embedded storage are
    bit-identical per lowering.  ``coarsen`` changes the per-step
    reduction tile, so coarsened sums agree to float tolerance, not
    bit-exactly."""
    target = backend_lib.resolve(backend, interpret)
    from repro.core import tune
    grid_mode, coarsen, num_stages = resolve_auto_schedule(
        "write",
        tune.target_params(
            tune.shard_params(
                {"fractal": fractal, "n": n or m.shape[0],
                 "block": block},
                mesh, shard_axis),
            target),
        grid_mode=(grid_mode, "lowering", "closed_form"),
        coarsen=(coarsen, "coarsen", 1),
        num_stages=(num_stages, "stages", 1))
    kw = dict(block=block, grid_mode=grid_mode, fractal=fractal,
              storage=storage, n=n, domain=domain, coarsen=coarsen,
              backend=target, stages=target.resolve_stages(num_stages),
              verify=verify)
    if mesh is not None:
        return _sum_sharded_impl(m, mesh=mesh, shard_axis=shard_axis,
                                 **kw)
    return _sum_impl(m, **kw)
