"""CA application benchmark: one nearest-neighbour step on the embedded
gasket, compact vs embedded storage.

Three strategies:

  * embedded: roll-based XLA stencil over the full n x n matrix
    (bounding box memory and work, n^2) -- skipped above
    ``--embedded-max-r`` where the dense buffers stop fitting the
    memory budget;
  * packed:   state stored in the compact linear-lambda layout with
    host-built lambda^-1 neighbour index tables
    (``repro.core.compact.cell_neighbor_tables``, sort-based: no dense
    scratch even at build time); touches only the n^H live cells, so it
    runs at n = 2^14..2^16 where the embedded array cannot be
    allocated;
  * kernel:   the Pallas ``ca_step`` storage A/B (embedded vs
    orthotope-resident compact blocks) at moderate n -- interpret mode
    on CPU, compiled Mosaic on TPU.

Each row reports the bytes the step must move (state read + write) next
to the time.
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fractal as F
from repro.core.compact import CompactLayout, cell_neighbor_tables
from repro.core.domain import make_fractal_domain
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh
from .common import row, time_fn

# keep the dense path under ~0.5 GiB of f32 buffers by default
EMBEDDED_MAX_R = 12


@jax.jit
def packed_parity_step(state, tables):
    s = jnp.concatenate([state, jnp.zeros((1,), state.dtype)])
    nsum = s[tables[0]] + s[tables[1]] + s[tables[2]] + s[tables[3]]
    return jnp.mod(state + nsum, 2)


@functools.partial(jax.jit, static_argnames=("n",))
def embedded_parity_step(state, n):
    return ref.ca_step_ref(state, "parity")


def run_sched_ab(iters: int = 3, steps: int = 16, cases=((128, 8),)):
    """Fused/coarsened schedule A/B: T x ca_step (the old per-step
    driver) vs one scanned ca_run at several fuse/coarsen settings.

    Every row carries ``speedup_vs_bounding`` (the paper's baseline:
    per-step bounding-box grid) and the fused/coarsened rows also carry
    ``speedup`` vs the per-step closed-form driver -- the launch-count
    arithmetic is ceil(T/fuse) launches instead of T."""
    print("# CA schedule A/B: fused ca_run vs per-step driver "
          f"(T={steps} parity steps)")
    for n, block in cases:
        mask = F.membership_grid(n)
        rng = np.random.default_rng(0)
        a0 = jnp.asarray((rng.integers(0, 2, (n, n)) * mask)
                         .astype(np.float32))
        z0 = jnp.zeros_like(a0)

        def per_step(a, b, gm):
            for _ in range(steps):
                new = ops.ca_step(a, b, rule="parity", block=block,
                                  grid_mode=gm)
                b, a = a, new
            return a

        t_bound = time_fn(per_step, a0, z0, "bounding", warmup=1,
                          iters=iters)
        t_step = time_fn(per_step, a0, z0, "closed_form", warmup=1,
                         iters=iters)
        row(f"ca_sched/per_step/bounding/n={n}/rho={block}", t_bound,
            f"launches={steps};speedup_vs_bounding=1.00")
        row(f"ca_sched/per_step/closed_form/n={n}/rho={block}", t_step,
            f"launches={steps};"
            f"speedup_vs_bounding={t_bound / t_step:.2f}")

        def fused(fuse, coarsen):
            return time_fn(
                lambda a, b: ops.ca_run(a, b, steps, fuse=fuse,
                                        rule="parity", block=block,
                                        grid_mode="closed_form",
                                        coarsen=coarsen, donate=False),
                a0, z0, warmup=1, iters=iters)

        for fuse in (4, min(16, block)):
            t_f = fused(fuse, 1)
            launches = len(ops.launch_schedule(steps, fuse))
            row(f"ca_sched/fused/fuse={fuse}/n={n}/rho={block}", t_f,
                f"launches={launches};speedup={t_step / t_f:.2f};"
                f"speedup_vs_bounding={t_bound / t_f:.2f}")
        for s in (2, 4):
            if (n // block) % s or s >= n // block:
                continue
            t_c = fused(1, s)
            row(f"ca_sched/coarsen/s={s}/n={n}/rho={block}", t_c,
                f"launches={steps};speedup={t_step / t_c:.2f};"
                f"speedup_vs_bounding={t_bound / t_c:.2f}")
        t_fc = fused(4, 2)
        launches = len(ops.launch_schedule(steps, 4))
        row(f"ca_sched/fused+coarsen/fuse=4/s=2/n={n}/rho={block}", t_fc,
            f"launches={launches};speedup={t_step / t_fc:.2f};"
            f"speedup_vs_bounding={t_bound / t_fc:.2f}")


def run_overlap_ab(iters: int = 3, steps: int = 8,
                   cases=((1024, 128), (4096, 128))):
    """Pipelining A/B: the fused CA launch with ``num_stages=2`` (DMA
    double buffers) vs the synchronous ``num_stages=1`` path, at sizes
    where tile traffic matters.  Outputs are asserted bit-identical
    before timing.  With >= 2 devices the sharded run is also A/B'd --
    there ``num_stages=2`` additionally overlaps the ppermute halo
    exchange with interior compute -- and each sharded row reports the
    ghost bytes the exchange ships (minimal strips vs the full-row
    scheme).  ``REPRO_OVERLAP_QUICK=1`` shrinks the case list for CI
    runners."""
    import os
    if os.environ.get("REPRO_OVERLAP_QUICK"):
        cases = ((1024, 128),)
    fuse = 8
    print(f"# CA pipelining A/B: num_stages=2 vs synchronous "
          f"(T={steps} parity steps, fuse={fuse})")
    for n, block in cases:
        mask = F.membership_grid(n)
        rng = np.random.default_rng(0)
        a0 = jnp.asarray((rng.integers(0, 2, (n, n)) * mask)
                         .astype(np.float32))
        lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                                n // block))
        a = lay.pack(a0, block)
        b = jnp.zeros_like(a)

        def run1(a, b, stages, mesh=None):
            return ops.ca_run(a, b, steps, fuse=fuse, rule="parity",
                              block=block, grid_mode="prefetch_lut",
                              storage="compact", n=n, num_stages=stages,
                              mesh=mesh, donate=False)

        assert np.array_equal(np.asarray(run1(a, b, 1)),
                              np.asarray(run1(a, b, 2)))
        t_sync = time_fn(run1, a, b, 1, warmup=1, iters=iters)
        t_pipe = time_fn(run1, a, b, 2, warmup=1, iters=iters)
        row(f"ca_overlap/sync/n={n}/rho={block}", t_sync, "stages=1")
        row(f"ca_overlap/pipelined/n={n}/rho={block}", t_pipe,
            f"stages=2;speedup={t_sync / t_pipe:.2f}")
        if jax.device_count() >= 2:
            from repro.core.shard import ShardedPlan
            D = jax.device_count()
            mesh = make_mesh((D,), ("data",))
            plan = ShardedPlan(lay.domain, "prefetch_lut",
                               storage="compact", mesh=mesh,
                               axis="data", halo=True)
            by = plan.halo.bytes_exchanged(plan, block, h=fuse)
            assert np.array_equal(np.asarray(run1(a, b, 1, mesh)),
                                  np.asarray(run1(a, b, 2, mesh)))
            ts = time_fn(run1, a, b, 1, mesh, warmup=1, iters=iters)
            tp = time_fn(run1, a, b, 2, mesh, warmup=1, iters=iters)
            row(f"ca_overlap/shard_sync/D={D}/n={n}/rho={block}", ts,
                f"stages=1;halo_bytes={by['strips']};"
                f"halo_bytes_full_rows={by['full_rows']}")
            row(f"ca_overlap/shard_pipelined/D={D}/n={n}/rho={block}",
                tp, f"stages=2;halo_bytes={by['strips']};"
                f"halo_bytes_full_rows={by['full_rows']};"
                f"speedup={ts / tp:.2f}")


def run_shard_ab(iters: int = 3, steps: int = 8, cases=((128, 8),)):
    """Mesh-scaling A/B: single-device ca_run vs the sharded run at
    every power-of-two device count the host exposes (compact storage
    slab-shards the orthotope with ppermute halos; embedded replicates
    and psums).  Emits one row per (storage, D) with the per-device
    state bytes next to the time; skipped on single-device hosts."""
    ndev = jax.device_count()
    if ndev < 2:
        print("# ca_shard: single device, skipping mesh-scaling A/B")
        return
    print(f"# CA mesh-scaling A/B: sharded ca_run over 1..{ndev} "
          f"devices (T={steps} parity steps)")
    sizes = []
    d = 2
    while d <= ndev:
        sizes.append(d)
        d *= 2
    for n, block in cases:
        mask = F.membership_grid(n)
        rng = np.random.default_rng(0)
        a0 = jnp.asarray((rng.integers(0, 2, (n, n)) * mask)
                         .astype(np.float32))
        z0 = jnp.zeros_like(a0)
        lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                                n // block))
        ap, zp = lay.pack(a0, block), lay.pack(z0, block)
        for storage, (a, b) in (("embedded", (a0, z0)),
                                ("compact", (ap, zp))):
            base = time_fn(
                lambda a, b: ops.ca_run(a, b, steps, fuse=1,
                                        rule="parity", block=block,
                                        grid_mode="closed_form",
                                        storage=storage, n=n,
                                        donate=False),
                a, b, warmup=1, iters=iters)
            bytes_dev = 2 * 4 * (lay.num_cells(block)
                                 if storage == "compact" else n * n)
            row(f"ca_shard/{storage}/D=1/n={n}/rho={block}", base,
                f"bytes_per_device={bytes_dev};speedup=1.00")
            for D in sizes:
                mesh = make_mesh((D,), ("data",))
                t = time_fn(
                    lambda a, b: ops.ca_run(a, b, steps, fuse=1,
                                            rule="parity", block=block,
                                            grid_mode="closed_form",
                                            storage=storage, n=n,
                                            mesh=mesh, donate=False),
                    a, b, warmup=1, iters=iters)
                if storage == "compact":
                    from repro.core.shard import ShardedPlan
                    plan = ShardedPlan(
                        lay.domain, "closed_form", storage="compact",
                        mesh=mesh, axis="data", halo=True)
                    lh, lw = plan.local_storage_shape(block)
                    bytes_dev = 2 * 4 * lh * lw
                row(f"ca_shard/{storage}/D={D}/n={n}/rho={block}", t,
                    f"bytes_per_device={bytes_dev};"
                    f"speedup={base / t:.2f}")


def run_kernel_storage_ab(iters: int = 5):
    """Pallas ca_step: embedded vs orthotope-resident compact storage."""
    print("# Pallas ca_step storage A/B (embedded n^2 vs compact n^H blocks)")
    for n, block in ((64, 8), (128, 8), (256, 16)):
        mask = F.membership_grid(n)
        rng = np.random.default_rng(0)
        s = jnp.asarray((rng.integers(0, 2, (n, n)) * mask)
                        .astype(np.float32))
        z = jnp.zeros_like(s)
        lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                                n // block))
        sp, zp = lay.pack(s, block), lay.pack(z, block)
        b_emb = 2 * 4 * n * n
        b_pk = 2 * 4 * lay.num_cells(block)
        t_emb = time_fn(functools.partial(
            ops.ca_step, rule="parity", block=block), s, z,
            warmup=2, iters=iters)
        t_pk = time_fn(functools.partial(
            ops.ca_step, rule="parity", block=block, storage="compact",
            n=n), sp, zp, warmup=2, iters=iters)
        row(f"ca_kernel/embedded/n={n}/rho={block}", t_emb,
            f"bytes={b_emb}")
        row(f"ca_kernel/compact/n={n}/rho={block}", t_pk,
            f"bytes={b_pk};bytes_saved={1 - b_pk / b_emb:.3f};"
            f"speedup={t_emb / t_pk:.2f}")


def run(max_r: int = 11, storage: str = "both",
        embedded_max_r: int = EMBEDDED_MAX_R, kernel_ab: bool = True,
        sched_ab: bool = True):
    if sched_ab:
        run_sched_ab()
    if kernel_ab:
        run_kernel_storage_ab()
    print("# CA step: embedded n^2 stencil vs packed n^H gather (XLA)")
    for r in range(6, max_r + 1):
        n = 2 ** r
        t_emb = None
        if storage in ("both", "embedded"):
            if r > embedded_max_r:
                row(f"ca_embedded/n={n}", 0.0,
                    f"skipped=embedded {4 * n * n} B state over budget")
            else:
                mask = F.membership_grid(n)
                rng = np.random.default_rng(0)
                s_emb = jnp.asarray((rng.integers(0, 2, (n, n)) * mask)
                                    .astype(np.float32))
                t_emb = time_fn(embedded_parity_step, s_emb, n, iters=10)
                row(f"ca_embedded/n={n}", t_emb,
                    f"cells={n * n};bytes={2 * 4 * n * n}")
        if storage in ("both", "compact"):
            vol = 3 ** r
            tables = jnp.asarray(cell_neighbor_tables(r))
            rng = np.random.default_rng(0)
            s_pack = jnp.asarray(rng.integers(0, 2, vol).astype(np.float32))
            t_pack = time_fn(packed_parity_step, s_pack, tables, iters=10)
            derived = f"cells={vol};bytes={2 * 4 * vol}"
            if t_emb is not None:
                derived += f";speedup={t_emb / t_pack:.2f}"
                # correctness cross-check against the embedded oracle
                i = np.arange(vol)
                lx, ly = F.lambda_map_linear(i, r)
                lx, ly = np.asarray(lx), np.asarray(ly)
                s_cmp = jnp.asarray(np.asarray(s_emb)[ly, lx])
                want = np.asarray(ref.ca_step_ref(s_emb, "parity"))[ly, lx]
                got = np.asarray(packed_parity_step(s_cmp, tables))
                assert np.array_equal(got, want), r
            row(f"ca_packed/n={n}", t_pack, derived)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--storage", default="both",
                    choices=["both", "embedded", "compact"])
    ap.add_argument("--max-r", type=int, default=11)
    ap.add_argument("--embedded-max-r", type=int, default=EMBEDDED_MAX_R)
    ap.add_argument("--no-kernel-ab", action="store_true")
    ap.add_argument("--no-sched-ab", action="store_true")
    args = ap.parse_args()
    run(max_r=args.max_r, storage=args.storage,
        embedded_max_r=args.embedded_max_r,
        kernel_ab=not args.no_kernel_ab,
        sched_ab=not args.no_sched_ab)


if __name__ == "__main__":
    main()
