"""Cellular-automaton / diffusion stepping on an embedded fractal, as a
temporally-fused block-space Pallas kernel (the application class the
paper motivates: nearest-neighbour data-parallel simulation over the
fractal).

One launch advances a (super)block by up to ``fuse`` steps: the kernel
assembles the block plus a ``fuse``-cell halo ring from the 8 neighbour
tiles (corners matter from the second step on, when the dependency
footprint grows past the von-Neumann cross), then advances the classic
*shrinking trapezoid* in an in-kernel ``fori_loop`` -- after k
iterations the outer k rings of the working array are stale, and after
``fuse`` iterations the interior block is exact.  The per-launch step
count is a run-time SMEM scalar, so the final partial launch of a
``steps % fuse`` remainder reuses the same trace.

:func:`ca_run` drives T steps as ``ceil(T / fuse)`` such launches
inside a single jitted ``lax.scan`` with rotating double buffers: one
trace and ceil(T/fuse) launches total, where the old driver paid T
launches and (first call) T Python dispatches.  :func:`ca_step` is the
``steps=1`` special case and keeps its original signature.

Halo exchange: the kernel receives nine views of the state array
(center + 8 neighbour supertiles) via BlockSpecs emitted by the plan.
Under ``storage="embedded"`` the neighbour index_maps are the decoded
block coordinate shifted (clamped); under ``storage="compact"`` the
state lives in the packed orthotope layout and each neighbour index_map
resolves the *embedded* neighbour's packed slot through lambda^-1
(inline for closed_form / bounding, or as an O(1) read of the
host-built 8-neighbour slot table shipped through the scalar-prefetch
LUT).  Out-of-range and non-member neighbour tiles are masked
in-kernel at fine-block granularity (matching the unfused kernel's
semantics exactly, so fused and per-step runs are bit-identical).

Superblock coarsening composes: ``coarsen=s`` makes the center tile an
s x s superblock (lambda decoded once per superblock); under compact
storage the supertile arrives in packed fine-block arrangement and the
kernel permutes it through the plan's static ``tile_map`` before
stencilling.

All three GridPlan lowerings apply: the compact ones visit only member
blocks; a *stale* buffer (zeros outside the fractal) is aliased to the
output so unvisited blocks stay zero -- the double-buffer CA scheme
that keeps the compact grids applicable to stencils, not just
pointwise writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import backend as backend_lib
from repro.core.compact import NEIGHBOR_OFFSETS8
from repro.core.domain import BlockDomain
from repro.core.plan import GridPlan
from repro.runtime.trace import span
from .sierpinski_write import (entry_counters, resolve_auto_schedule,
                               resolve_storage_args, tile_grid)

#: trace/build telemetry the schedule-equivalence tests read: "kernel"
#: counts fused-kernel body traces, "build" counts pallas_call
#: constructions.  A T-step ca_run must bump each exactly once.
TRACE_COUNTER = {"kernel": 0, "build": 0}
#: the fused kernel's name in the profiler's trace
KERNEL_NAME = "sierpinski_ca_fused"


def auto_schedule(*, fractal: str = "sierpinski-gasket", n: int,
                  block: int, rule: str = "parity",
                  grid_mode: str = "auto", fuse: int | str = "auto",
                  coarsen: int | str = "auto",
                  num_stages: int | str = "auto", mesh=None,
                  shard_axis: str = "data", target=None):
    """Resolve the (grid_mode, fuse, coarsen, num_stages) schedule for
    a CA problem from the tune cache -- the exact lookup
    :func:`ca_run` / :func:`ca_step` perform, exposed so drivers can
    report the schedule they are about to run without re-deriving the
    cache key.  A sharded run (``mesh=``) consults the
    shard-count-qualified key; a non-default emission ``target``
    consults the target-qualified key."""
    from repro.core import tune
    return resolve_auto_schedule(
        "ca",
        tune.target_params(
            tune.shard_params(
                {"fractal": fractal, "n": n, "block": block,
                 "rule": rule},
                mesh, shard_axis),
            target),
        grid_mode=(grid_mode, "lowering", "closed_form"),
        fuse=(fuse, "fuse", 1),
        coarsen=(coarsen, "coarsen", 1),
        num_stages=(num_stages, "stages", 1))


def effective_fuse(fuse: int, steps: int, block: int,
                   coarsen: int = 1) -> int:
    """The fuse depth :func:`ca_run` actually executes: clamped so the
    halo ring fits one neighbour supertile (``coarsen * block``) and
    never exceeds the step count."""
    return max(1, min(int(fuse), coarsen * block,
                      steps if steps else 1))


def launch_schedule(steps: int, fuse: int) -> list:
    """Per-launch step counts for T steps at fuse depth k:
    ``ceil(T/k)`` launches of k steps, the last carrying the
    remainder."""
    steps, fuse = int(steps), int(fuse)
    if steps < 0:
        raise ValueError(f"steps must be >= 0, got {steps}")
    if fuse < 1:
        raise ValueError(f"fuse must be >= 1, got {fuse}")
    full, rem = divmod(steps, fuse)
    return [fuse] * full + ([rem] if rem else [])


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _trapezoid_update(tiles, bx, by, steps, *, rule, alpha, block, n,
                      plan, halo):
    """The fused-CA math, shared by the synchronous and the
    DMA-pipelined kernels: assemble the working array from the center +
    8 neighbour supertiles (embedded-storage arrangement; packed
    fine-block arrangement under compact coarsening), advance the
    shrinking trapezoid ``steps`` times, and return the output
    supertile in storage arrangement.

    ``tiles``: 9 arrays in [center] + NEIGHBOR_OFFSETS8 order, each the
    plan's storage-supertile shape.  ``(bx, by)``: scheduled (coarse)
    block coords.

    Everything here is a static slice, a concatenation, a select, a
    roll or an elementwise op, which Mosaic lowers.  The row halo is
    widened to the 8-row sublane tiling (``hr`` rows above and below).
    The column halo is one lane-aligned pad group of ``pw`` columns
    after the interior, read as a ring (the rolls are cyclic): the
    right neighbour's first columns at its start, the left
    neighbour's last columns at its end, so every piece and the final
    interior slice stay tile-aligned.  Whatever sits between them
    changes nothing inside, because after ``steps <= halo`` iterations
    only the outer ``steps`` cells of each halo are stale."""
    domain = plan.domain
    span = plan.coarsen * block        # embedded superblock side, cells
    hr = min(span, _round_up(halo, 8))
    # both column halos in one pad group: 128 lanes while they fit,
    # else the whole left and right neighbours side by side
    pw = _round_up(2 * halo, 128)
    if pw > span:
        pw = 2 * span
    wr, wc = span + 2 * hr, span + pw   # working (trapezoid base)
    tm = plan.tile_map()

    def fine(t, r, c):
        return t[r * block:(r + 1) * block, c * block:(c + 1) * block]

    def embed(t):
        """Packed supertile -> embedded arrangement (identity when the
        storage tile is already embedded-ordered)."""
        if tm is None:
            return t
        at = {e: p for p, e in tm}
        z = jnp.zeros((block, block), t.dtype)
        s = plan.coarsen
        return tile_grid([[fine(t, *at[(ey, ex)]) if (ey, ex) in at
                           else z for ex in range(s)] for ey in range(s)])

    def unembed(e):
        if tm is None:
            return e
        at = dict(tm)
        z = jnp.zeros((block, block), e.dtype)
        th, tw = plan.supertile_shape((block, block))
        return tile_grid([[fine(e, *at[(py, px)]) if (py, px) in at
                           else z for px in range(tw // block)]
                          for py in range(th // block)])

    # strip geometry: which rows/cols of a neighbour's embedded view
    # land in the padded working array (relative offset -1/0/+1)
    rows = {-1: slice(span - hr, span), 0: slice(0, span), 1: slice(0, hr)}
    by_offset = {(0, 0): embed(tiles[0])}
    for j, off in enumerate(NEIGHBOR_OFFSETS8):
        by_offset[off] = embed(tiles[1 + j])

    def pad(dy):
        right = by_offset[(1, dy)][rows[dy]]
        left = by_offset[(-1, dy)][rows[dy]]
        if pw == 2 * span:
            return jnp.concatenate([right, left], axis=1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (right.shape[0], pw), 1)
        return jnp.where(lane < pw // 2, right[:, :pw],
                         left[:, span - pw:])

    P = tile_grid([[by_offset[(0, dy)][rows[dy]], pad(dy)]
                   for dy in (-1, 0, 1)])

    iy = jax.lax.broadcasted_iota(jnp.int32, (wr, wc), 0)
    ix = jax.lax.broadcasted_iota(jnp.int32, (wr, wc), 1)
    # the pad group's second half holds the left neighbour's columns
    gx = bx * span + ix - jnp.where(ix >= span + pw // 2, wc, 0)
    gy = by * span - hr + iy
    inr = (gx >= 0) & (gx < n) & (gy >= 0) & (gy < n)
    gxc = jnp.clip(gx, 0, n - 1)
    gyc = jnp.clip(gy, 0, n - 1)
    # contributions are discarded at fine-*block* granularity (the
    # unfused kernel's nbr_ok), values at *cell* granularity: a
    # member block's non-member cells pass raw into the first
    # neighbour sum (zero by the CA invariant) and are re-zeroed by
    # the output mask every step.
    cell_ok = inr & domain.cell_member(gxc, gyc, n)
    block_ok = inr & domain.contains(gxc // block, gyc // block)
    P = jnp.where(block_ok, P, 0)

    def nsum_of(a):
        # cyclic shifts: what wraps in at the edge of the working array
        # lands in the stale outer ring, never in the interior
        up = pltpu.roll(a, 1, 0)            # a[i-1, j]
        down = pltpu.roll(a, wr - 1, 0)     # a[i+1, j]
        left = pltpu.roll(a, 1, 1)          # a[i, j-1]
        right = pltpu.roll(a, wc - 1, 1)    # a[i, j+1]
        return up + down + left + right

    if rule == "parity":
        def one(pv):
            return jnp.where(cell_ok, jnp.mod(pv + nsum_of(pv), 2), 0)
    else:  # diffusion: graph Laplacian over member neighbours
        deg = nsum_of(cell_ok.astype(P.dtype))
        al = jnp.asarray(alpha, P.dtype)

        def one(pv):
            new = pv + al * (nsum_of(pv) - deg * pv)
            return jnp.where(cell_ok, new, 0)

    P2 = jax.lax.fori_loop(0, steps, lambda i, pv: one(pv), P)
    return unembed(P2[hr:hr + span, :span])


def _ca_fused_kernel(coords, c_ref, n_ref, s_ref, w_ref, e_ref, nw_ref,
                     ne_ref, sw_ref, se_ref, buf_ref, steps_ref, o_ref,
                     *, rule, alpha, block, n, plan, halo):
    """Advance one (super)block by ``steps_ref[0] <= halo`` CA steps
    (the 9 supertiles arrive as BlockSpec-fed operand views)."""
    TRACE_COUNTER["kernel"] += 1
    nbr_refs = (n_ref, s_ref, w_ref, e_ref, nw_ref, ne_ref, sw_ref,
                se_ref)

    def body():
        tiles = [c_ref[...]] + [r[...] for r in nbr_refs]
        o_ref[...] = _trapezoid_update(
            tiles, coords.bx, coords.by, steps_ref[0], rule=rule,
            alpha=alpha, block=block, n=n, plan=plan,
            halo=halo).astype(o_ref.dtype)

    def keep_stale():
        o_ref[...] = buf_ref[...]

    coords.when_valid(body, keep_stale if plan.skip_fills_output
                      else None)


def _ca_fused_kernel_dma(coords, c_ref, buf_ref, steps_ref, o_ref,
                         bufs_ref, sems, *, rule, alpha, block, n, plan,
                         halo, stages):
    """Async-copy pipelined fused CA (``num_stages`` >= 2): the state
    is parked whole in ``pl.ANY`` and the kernel streams each step's 9
    supertiles (center + 8 lambda^-1-resolved neighbours) into rotating
    VMEM buffers with explicit DMA -- the copies for grid step
    t+stages-1 start before step t's trapezoid runs, hiding the tile
    fetches behind compute.  Tile addressing, visit order and the
    trapezoid math are exactly the synchronous kernel's, so results are
    bit-identical."""
    TRACE_COUNTER["kernel"] += 1
    refs = coords.refs
    total = plan.steps_per_launch
    lin = plan.linear_step(coords.grid_ids)

    def srcs_for(step):
        gi = plan.grid_ids_at(step)
        srcs = [plan.storage_index(gi, refs)]
        for j in range(8):
            srcs.append(plan.neighbor_index(j, gi, refs))
        return srcs

    tiles = backend_lib.stream_tiles(
        c_ref, bufs_ref, sems, srcs_for=srcs_for, lin=lin, total=total,
        stages=stages)

    def body():
        o_ref[...] = _trapezoid_update(
            tiles, coords.bx, coords.by, steps_ref[0], rule=rule,
            alpha=alpha, block=block, n=n, plan=plan,
            halo=halo).astype(o_ref.dtype)

    def keep_stale():
        o_ref[...] = buf_ref[...]

    coords.when_valid(body, keep_stale if plan.skip_fills_output
                      else None)


def _build_launch(plan, *, rule, alpha, block, n, halo, shape, dtype,
                  stages=1):
    """One fused pallas_call: (state, stale, steps[1]) -> new state.

    The kernel receives nine BlockSpec views of the state; with
    ``stages >= 2`` the state instead arrives whole (``pl.ANY``) and the
    kernel streams the nine tiles through rotating VMEM DMA buffers
    (:func:`_ca_fused_kernel_dma`)."""
    TRACE_COUNTER["build"] += 1
    stages = plan.target.resolve_stages(stages)
    plan.check_output_writeback()
    kernel_kw = dict(rule=rule, alpha=alpha, block=block, n=n, plan=plan,
                     halo=halo)
    steps_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    if stages > 1:
        tile = plan.storage_spec((block, block))
        th, tw = plan.supertile_shape((block, block))
        call = plan.pallas_call(
            functools.partial(_ca_fused_kernel_dma, **kernel_kw,
                              stages=stages),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), tile,
                      steps_spec],
            out_specs=tile,
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            scratch_shapes=[
                pltpu.VMEM((stages, 9, th, tw), dtype),
                pltpu.SemaphoreType.DMA((stages, 9)),
            ],
            input_output_aliases={1: 0},
            name=KERNEL_NAME,
        )

        def launch(a, b, steps_scalar, prefetch=()):
            return call(*prefetch, a, b, steps_scalar)
        return launch

    tile = plan.storage_spec((block, block))
    in_specs = [tile]
    in_specs += [plan.neighbor_spec((block, block), j) for j in range(8)]
    in_specs += [tile]                       # stale buffer
    in_specs += [steps_spec]                 # step count
    call = plan.pallas_call(
        functools.partial(_ca_fused_kernel, **kernel_kw),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        input_output_aliases={9: 0},
        name=KERNEL_NAME,
    )

    def launch(a, b, steps_scalar, prefetch=()):
        return call(*prefetch, a, a, a, a, a, a, a, a, a, b, steps_scalar)
    return launch


def _ca_run_impl(state, stale_buf, *, steps, fuse, rule, alpha, block,
                 grid_mode, fractal, storage, n, domain, coarsen,
                 backend, stages=1, verify=False):
    domain, n, block, storage = resolve_storage_args(
        state, block, fractal, storage, n, domain)
    plan = GridPlan(domain, grid_mode, storage=storage, coarsen=coarsen,
                    backend=backend)
    if verify:
        from repro.analysis import verify_or_raise
        verify_or_raise(plan, kernel="ca")
    fuse = effective_fuse(fuse, steps, block, plan.coarsen)
    sched = launch_schedule(steps, fuse)
    if not sched:
        return state
    launch = _build_launch(plan, rule=rule, alpha=alpha, block=block,
                           n=n, halo=fuse, shape=state.shape,
                           dtype=state.dtype, stages=stages)

    def body(carry, per_launch):
        a, b = carry
        new = launch(a, b, jnp.reshape(per_launch, (1,)))
        return (new, a), None

    (a, _), _ = jax.lax.scan(body, (state, stale_buf),
                             jnp.asarray(sched, jnp.int32))
    return a


_CA_STATIC = ("steps", "fuse", "rule", "alpha", "block", "grid_mode",
              "fractal", "storage", "n", "domain", "coarsen", "backend",
              "stages", "verify")
_CA_RUN_JIT = {
    False: jax.jit(_ca_run_impl, static_argnames=_CA_STATIC),
    True: jax.jit(_ca_run_impl, static_argnames=_CA_STATIC,
                  donate_argnums=(0, 1)),
}


def _ca_run_sharded_impl(state, stale_buf, *, steps, fuse, rule, alpha,
                         block, grid_mode, fractal, storage, n, domain,
                         coarsen, backend, mesh, shard_axis, stages=1,
                         verify=False):
    """ca_run across a mesh axis: each device advances its share of the
    domain; compact storage is slab-sharded with a ppermute ghost-row
    exchange before every launch, embedded storage is replicated and
    combined by a disjoint-ownership-mask psum after every launch.
    Bit-identical to the single-device scan (every block is computed by
    exactly one device with the same operands)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.shard import ShardedPlan, device_tables

    domain, n, block, storage = resolve_storage_args(
        state, block, fractal, storage, n, domain)
    plan = ShardedPlan(domain, grid_mode, storage=storage,
                       coarsen=coarsen, backend=backend, mesh=mesh,
                       axis=shard_axis, halo=(storage == "compact"))
    if verify:
        from repro.analysis import verify_or_raise
        verify_or_raise(plan, kernel="ca")
    fuse = effective_fuse(fuse, steps, block, plan.coarsen)
    sched = launch_schedule(steps, fuse)
    if not sched:
        return state
    local_shape = plan.local_storage_shape(block)
    launch = _build_launch(plan, rule=rule, alpha=alpha, block=block,
                           n=n, halo=fuse, shape=local_shape,
                           dtype=state.dtype, stages=stages)
    tbl, luts = device_tables(plan)
    sched_arr = jnp.asarray(sched, jnp.int32)
    axis = shard_axis
    tbl_spec = P(axis, None)
    lut_specs = tuple(P(axis, None) for _ in luts)

    if storage == "compact":
        halo = plan.halo
        sr = tuple(tuple(jnp.asarray(t) for t in tabs)
                   for tabs in halo.send_recv_host())
        sr_specs = tuple(tuple(P(axis, None) for _ in tabs)
                         for tabs in sr)
        a = plan.pad_rows(state, block)
        b = plan.pad_rows(stale_buf, block)
        # halo/compute overlap: with pipelining on and a step-indexed
        # lowering, split each launch into an interior phase (no ghost
        # reads -- runs while the ppermute is in flight) and a boundary
        # phase that waits for the exchanged ghost rows.  Falls back to
        # the synchronous single launch when a phase is empty.
        phases = plan.phase_tables_host() \
            if stages > 1 and plan.lowering != "bounding" else None
        if phases is not None:
            int_h, bnd_h = phases
            launch_int = _build_launch(
                plan.phase_view("interior"), rule=rule, alpha=alpha,
                block=block, n=n, halo=fuse, shape=local_shape,
                dtype=state.dtype, stages=stages)
            launch_bnd = _build_launch(
                plan.phase_view("boundary"), rule=rule, alpha=alpha,
                block=block, n=n, halo=fuse, shape=local_shape,
                dtype=state.dtype, stages=stages)
            itb, btb = jnp.asarray(int_h), jnp.asarray(bnd_h)

            def device_fn(tbl, luts, itb, btb, sr, a, b):
                pre = (tbl.reshape(-1),) + luts
                pi = pre + (itb.reshape(-1),)
                pb = pre + (btb.reshape(-1),)

                def body(carry, per_launch):
                    x, y = carry
                    s = jnp.reshape(per_launch, (1,))
                    ghost = halo.exchange(plan, x, sr, h=fuse)
                    ext0 = halo.cat(plan, x, jnp.zeros_like(ghost))
                    mid = launch_int(ext0, y, s, pi)
                    new = launch_bnd(halo.cat(plan, x, ghost), mid, s,
                                     pb)
                    return (new, x), None

                (xa, _), _ = jax.lax.scan(body, (a, b), sched_arr)
                return xa

            out = jax.shard_map(
                device_fn, mesh=mesh,
                in_specs=(tbl_spec, lut_specs, P(axis, None),
                          P(axis, None), sr_specs, P(axis, None),
                          P(axis, None)),
                out_specs=P(axis, None), check_vma=False)(
                    tbl, luts, itb, btb, sr, a, b)
            return plan.unpad_rows(out, block)

        def device_fn(tbl, luts, sr, a, b):
            pre = (tbl.reshape(-1),) + luts

            def body(carry, per_launch):
                x, y = carry
                ext = halo.extend(plan, x, sr, h=fuse)
                new = launch(ext, y, jnp.reshape(per_launch, (1,)), pre)
                return (new, x), None

            (xa, _), _ = jax.lax.scan(body, (a, b), sched_arr)
            return xa

        out = jax.shard_map(
            device_fn, mesh=mesh,
            in_specs=(tbl_spec, lut_specs, sr_specs, P(axis, None),
                      P(axis, None)),
            out_specs=P(axis, None), check_vma=False)(tbl, luts, sr, a, b)
        return plan.unpad_rows(out, block)

    def device_fn(tbl, luts, a, b):
        tbl1 = tbl.reshape(-1)
        pre = (tbl1,) + luts
        mask = plan.owned_cell_mask(tbl1, n, block)

        def body(carry, per_launch):
            x, y = carry
            part = launch(x, y, jnp.reshape(per_launch, (1,)), pre)
            new = jax.lax.psum(jnp.where(mask, part, 0), axis)
            return (new, x), None

        (xa, _), _ = jax.lax.scan(body, (a, b), sched_arr)
        return xa

    return jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(tbl_spec, lut_specs, P(None, None), P(None, None)),
        out_specs=P(None, None), check_vma=False)(
            tbl, luts, state, stale_buf)


_CA_SHARD_STATIC = _CA_STATIC + ("mesh", "shard_axis")
_CA_RUN_SHARD_JIT = {
    False: jax.jit(_ca_run_sharded_impl, static_argnames=_CA_SHARD_STATIC),
    True: jax.jit(_ca_run_sharded_impl, static_argnames=_CA_SHARD_STATIC,
                  donate_argnums=(0, 1)),
}


def ca_run(state: jnp.ndarray, stale_buf: jnp.ndarray, steps: int, *,
           fuse: int | str = "auto", rule: str = "parity",
           alpha: float = 0.25, block: int = 128,
           grid_mode: str = "compact",
           fractal: str = "sierpinski-gasket",
           storage: str = "embedded", n: int | None = None,
           domain: BlockDomain | None = None, coarsen: int | str = 1,
           num_stages: int | str = "auto", backend=None,
           interpret: bool | None = None, donate: bool | None = None,
           mesh=None, shard_axis: str = "data",
           verify: bool = False) -> jnp.ndarray:
    """Advance the CA ``steps`` steps and return the final state.

    ``fuse=k`` executes k steps per kernel launch (one in-kernel
    trapezoid loop), so the whole run costs ceil(steps/k) launches
    driven by a single jitted ``lax.scan`` -- bit-identical to
    ``steps`` sequential :func:`ca_step` calls.  ``fuse`` is clamped to
    ``coarsen * block`` (the halo ring must fit one neighbour
    supertile) and to ``steps`` -- see :func:`effective_fuse`.
    ``fuse="auto"`` / ``grid_mode="auto"`` / ``coarsen="auto"`` resolve
    from the :mod:`~repro.core.tune` cache (defaults: 1 / closed_form /
    1; see :func:`auto_schedule`).

    ``stale_buf`` must be zero outside the fractal (the double-buffer
    invariant); both buffers are donated on accelerators unless
    ``donate=False``.  Under ``storage="compact"`` both arrays are
    packed orthotope-resident (pass ``n=`` or ``domain=``).

    ``mesh=`` (a ``jax.sharding.Mesh``) shards the run over
    ``shard_axis``: compact state splits into orthotope row slabs
    (per-device memory O(n^H / D) + halo) with a lambda^-1-resolved
    ppermute ghost exchange between launches (trimmed to the fuse-deep
    strip and the occupied column window of each ghost row; see
    :class:`repro.core.shard.HaloPlan`); embedded state stays
    replicated and devices psum their disjoint block shares.  Both are
    bit-identical to the single-device run.

    ``num_stages`` >= 2 ("auto" = tuned) software-pipelines each
    launch (see README "Pipelining"): the kernel streams the 9 halo
    supertiles through rotating async-copy VMEM buffers so step t+1's
    fetches overlap step t's trapezoid; under a sharded compact run the
    scan also splits each launch into interior and boundary phases so
    the ppermute ghost exchange overlaps interior compute.
    Bit-identical to the synchronous path.

    ``backend`` selects the emission target ("tpu" | "tpu-interpret" |
    None = platform default; see :mod:`repro.core.backend`).
    ``verify=True`` statically verifies the emitted plan (coverage /
    races / tables / bounds / aliasing; :mod:`repro.analysis`) at trace
    time and raises on any violation."""
    target = backend_lib.resolve(backend, interpret)
    with span("kernels.ca_run") as entry:
        with span("kernels.ca_run.schedule"):
            grid_mode, fuse, coarsen, num_stages = auto_schedule(
                fractal=fractal, n=n or state.shape[0], block=block,
                rule=rule, grid_mode=grid_mode, fuse=fuse,
                coarsen=coarsen, num_stages=num_stages, mesh=mesh,
                shard_axis=shard_axis, target=target)
        run_fuse = effective_fuse(fuse, steps, block, coarsen)
        entry.set_metadata(**entry_counters(
            state, fractal=fractal, storage=storage, n=n, domain=domain,
            block=block, grid_mode=grid_mode, coarsen=coarsen, mesh=mesh,
            steps=int(steps), fuse=run_fuse,
            launches=len(launch_schedule(steps, run_fuse))))
        if donate is None:
            donate = not target.interpret and \
                jax.default_backend() != "cpu"
        kw = dict(steps=int(steps), fuse=fuse, rule=rule, alpha=alpha,
                  block=block, grid_mode=grid_mode, fractal=fractal,
                  storage=storage, n=n, domain=domain, coarsen=coarsen,
                  backend=target, stages=target.resolve_stages(num_stages),
                  verify=verify)
        with span("kernels.ca_run.dispatch"):
            if mesh is not None:
                return _CA_RUN_SHARD_JIT[bool(donate)](
                    state, stale_buf, mesh=mesh, shard_axis=shard_axis,
                    **kw)
            return _CA_RUN_JIT[bool(donate)](state, stale_buf, **kw)


def ca_step(state: jnp.ndarray, stale_buf: jnp.ndarray, *,
            rule: str = "parity", alpha: float = 0.25, block: int = 128,
            grid_mode: str = "compact",
            fractal: str = "sierpinski-gasket",
            storage: str = "embedded", n: int | None = None,
            domain: BlockDomain | None = None, coarsen: int | str = 1,
            num_stages: int | str = "auto", backend=None,
            interpret: bool | None = None, mesh=None,
            shard_axis: str = "data",
            verify: bool = False) -> jnp.ndarray:
    """One CA step (the ``steps=1`` slice of :func:`ca_run`).

    ``stale_buf`` must be zero outside the fractal (e.g. the state from
    two steps ago, or zeros); it is aliased to the output buffer so
    blocks a compact grid never visits remain valid."""
    target = backend_lib.resolve(backend, interpret)
    grid_mode, _, coarsen, num_stages = auto_schedule(
        fractal=fractal, n=n or state.shape[0], block=block, rule=rule,
        grid_mode=grid_mode, fuse=1, coarsen=coarsen,
        num_stages=num_stages, mesh=mesh, shard_axis=shard_axis,
        target=target)
    kw = dict(steps=1, fuse=1, rule=rule, alpha=alpha, block=block,
              grid_mode=grid_mode, fractal=fractal, storage=storage,
              n=n, domain=domain, coarsen=coarsen, backend=target,
              stages=target.resolve_stages(num_stages), verify=verify)
    if mesh is not None:
        return _CA_RUN_SHARD_JIT[False](
            state, stale_buf, mesh=mesh, shard_axis=shard_axis, **kw)
    return _CA_RUN_JIT[False](state, stale_buf, **kw)
