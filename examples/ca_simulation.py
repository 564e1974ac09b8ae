"""Cellular-automaton simulation on the embedded Sierpinski gasket --
the data-parallel application class from the paper's introduction
(Wolfram-style parity CA + heat diffusion), running on the block-space
Pallas kernels with the classic double-buffer scheme.

The whole run is ONE jitted, scanned, buffer-donating driver
(``ca_run``): ``--fuse k`` advances k steps per kernel launch (in-kernel
trapezoid loop), so ``--steps T`` costs ceil(T/k) launches and a single
trace -- the old version dispatched T separate ``ca_step`` calls from a
Python loop.  ``--coarsen s`` makes every launch step own an s x s
superblock (lambda decoded once per superblock).  ``--autotune`` first
searches lowering x storage x fuse x coarsen for this (n, block, rule)
and uses (and persists) the winner.

With ``--storage compact`` (the default) the state never materializes
the dense n x n array after the initial seed: both CA buffers live in
the packed orthotope layout of Lemma 2 (O(n^H) memory), and the kernels
resolve their halo gathers through lambda^-1.  ``--storage embedded``
keeps the dense layout for A/B.

Run:  PYTHONPATH=src python examples/ca_simulation.py [--steps 16]
"""
import argparse

import jax.numpy as jnp
import numpy as np

from repro.core import fractal as F
from repro.core import tune
from repro.core.compact import CompactLayout
from repro.core.domain import make_fractal_domain
from repro.kernels import ops, sierpinski_ca


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--block", type=int, default=8)
    ap.add_argument("--rule", default="parity",
                    choices=["parity", "diffusion"])
    ap.add_argument("--storage", default="compact",
                    choices=["embedded", "compact"])
    ap.add_argument("--fuse", default="auto",
                    help="steps per kernel launch (int, or 'auto' for "
                         "the tuned value; untuned default 1)")
    ap.add_argument("--coarsen", default="auto",
                    help="superblock side in blocks (int or 'auto')")
    ap.add_argument("--grid-mode", default="compact",
                    choices=["compact", "closed_form", "prefetch_lut",
                             "bounding", "auto"])
    ap.add_argument("--autotune", action="store_true",
                    help="search the schedule axes for this problem "
                         "first, persist the winner, and run with it")
    ap.add_argument("--shard", type=int, default=0, metavar="D",
                    help="shard the run over D devices (0 = single "
                         "device; D devices must exist, e.g. via "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=D on CPU)")
    args = ap.parse_args()
    n = args.n
    fuse = args.fuse if args.fuse == "auto" else int(args.fuse)
    coarsen = args.coarsen if args.coarsen == "auto" else int(args.coarsen)
    grid_mode = args.grid_mode

    if args.autotune:
        cfg, us, trials = tune.autotune_ca(
            n=n, block=args.block, rule=args.rule,
            storages=(args.storage,), force=False)
        why = f"measured {us:.0f} us over {len(trials)} configs" \
            if us is not None else "tune-cache hit"
        print(f"autotuned: {cfg} ({why})")
        grid_mode, fuse, coarsen = cfg["lowering"], cfg["fuse"], \
            cfg["coarsen"]

    # the same cache lookup ca_run performs, done here so the driver
    # can report the schedule it is about to run
    grid_mode, fuse, coarsen, num_stages = sierpinski_ca.auto_schedule(
        n=n, block=args.block, rule=args.rule, grid_mode=grid_mode,
        fuse=fuse, coarsen=coarsen)

    mask = F.membership_grid(n)
    # seed: single live cell at the bottom-left corner of the gasket
    state = np.zeros((n, n), np.float32)
    state[n - 1, 0] = 1.0
    if args.rule == "diffusion":
        state[n - 1, 0] = 100.0
    a = jnp.asarray(state * mask)
    b = jnp.zeros_like(a)

    layout = None
    if args.storage == "compact":
        layout = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                                   n // args.block))
        a, b = layout.pack(a, args.block), layout.pack(b, args.block)
        emb, pk = n * n, layout.num_cells(args.block)
        print(f"orthotope-resident: {pk} cells ({4 * pk} B f32) instead "
              f"of {emb} ({4 * emb} B), x{emb / pk:.2f} smaller")

    mesh = None
    if args.shard:
        import jax
        if jax.device_count() < args.shard:
            raise SystemExit(
                f"--shard {args.shard} needs {args.shard} devices, have "
                f"{jax.device_count()} (on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.shard})")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((args.shard,), ("data",))
        print(f"sharded over {args.shard} devices "
              f"({'orthotope row slabs + ppermute halo' if args.storage == 'compact' else 'replicated state, disjoint psum'})")

    total0 = float(jnp.sum(a))
    final = ops.ca_run(a, b, args.steps, fuse=fuse, rule=args.rule,
                       block=args.block, grid_mode=grid_mode,
                       storage=args.storage, n=n, coarsen=coarsen,
                       mesh=mesh)
    eff = sierpinski_ca.effective_fuse(fuse, args.steps, args.block,
                                       int(coarsen))
    launches = len(ops.launch_schedule(args.steps, eff))
    print(f"{args.steps} steps in {launches} fused launches "
          f"(one trace, scanned double buffers)")
    live = int(jnp.sum(final > 0))
    print(f"final active cells = {live}")

    if args.rule == "diffusion":
        total = float(jnp.sum(final))
        print(f"heat conserved: {total0:.3f} -> {total:.3f}")
    # zero outside the fractal is an invariant of the kernel
    emb_final = layout.unpack(final, args.block) if layout is not None \
        else final
    assert (np.asarray(emb_final)[~mask] == 0).all()
    print("invariant OK: state is zero outside the gasket")


if __name__ == "__main__":
    main()
