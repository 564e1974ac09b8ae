"""DMA-vs-compute overlap profile for the pipelined fused CA kernel.

The harness separates the launch time into a *traffic* estimate and a
*compute* estimate, then measures how much of the traffic the
``num_stages >= 2`` software pipeline actually hides.  Traffic is
measured directly: the fused launch is rerun with ``steps_scalar = 0``,
which streams every supertile through the same DMA path but runs zero
trapezoid iterations; ``compute = sync - traffic``.

Reported:

  ``occupancy = (traffic + compute) / pipelined`` -- how many seconds
  of serialized work each pipelined second retires (1.0 = nothing
  hidden, 2.0 = perfect double-buffering at traffic == compute);
  ``hidden_frac = clip((sync - pipelined) / traffic, 0, 1)`` -- the
  fraction of the traffic estimate the pipeline removed from the
  critical path.

Interpret-mode numbers characterize the emulated kernel (the
interpreter serializes real DMA), so on CPU the value of this harness
is the trend across stages and sizes, not absolute microseconds; on
real accelerators the same rows measure true overlap.

Run:  PYTHONPATH=src python -m benchmarks.profile_overlap [--json PATH]
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from .common import dump_json, row, time_fn


def _occupancy_rows(name: str, traffic: float, sync: float,
                    pipe: float, extra: str = ""):
    compute = max(sync - traffic, 0.0)
    occ = (traffic + compute) / pipe if pipe else 0.0
    hidden = min(max((sync - pipe) / traffic, 0.0), 1.0) \
        if traffic else 0.0
    row(f"{name}/traffic", traffic, extra)
    row(f"{name}/compute", compute, extra)
    row(f"{name}/sync", sync, f"stages=1;{extra}")
    row(f"{name}/pipelined", pipe,
        f"occupancy={occ:.2f};hidden_frac={hidden:.2f};{extra}")


def profile_ca(n: int = 1024, block: int = 128, fuse: int = 8,
               steps: int = 8, stages: int = 2, iters: int = 3):
    from repro.core import fractal as F
    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    from repro.core.plan import GridPlan
    from repro.kernels import ops
    from repro.kernels.sierpinski_ca import _build_launch

    print(f"# profile_overlap ca: n={n} rho={block} fuse={fuse} "
          f"stages={stages}")
    mask = F.membership_grid(n)
    rng = np.random.default_rng(0)
    a0 = jnp.asarray((rng.integers(0, 2, (n, n)) * mask)
                     .astype(np.float32))
    dom = make_fractal_domain("sierpinski-gasket", n // block)
    lay = CompactLayout(dom)
    a = lay.pack(a0, block)
    b = jnp.zeros_like(a)

    def run1(a, b, s):
        return ops.ca_run(a, b, steps, fuse=fuse, rule="parity",
                          block=block, grid_mode="prefetch_lut",
                          storage="compact", n=n, num_stages=s,
                          donate=False)

    assert np.array_equal(np.asarray(run1(a, b, 1)),
                          np.asarray(run1(a, b, stages)))
    t_sync = time_fn(run1, a, b, 1, warmup=1, iters=iters)
    t_pipe = time_fn(run1, a, b, stages, warmup=1, iters=iters)

    # traffic ablation: same launch, zero trapezoid iterations
    plan = GridPlan(dom, "prefetch_lut", storage="compact")
    launch = _build_launch(plan, rule="parity", alpha=0.25, block=block,
                           n=n, halo=fuse, shape=a.shape, dtype=a.dtype,
                           stages=1)
    zero = jnp.zeros((1,), jnp.int32)
    stream = jax.jit(lambda a, b: launch(a, b, zero))
    t_traffic = time_fn(stream, a, b, warmup=1, iters=iters)
    t_traffic = min(t_traffic, t_sync)
    _occupancy_rows(f"profile_overlap/ca/n={n}/rho={block}", t_traffic,
                    t_sync, t_pipe, f"fuse={fuse};stages={stages}")


def run(quick: bool = False):
    if quick:
        profile_ca(n=256, block=32, fuse=4, steps=4)
    else:
        profile_ca()


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small sizes (CI)")
    ap.add_argument("--json", default=None,
                    help="artifact path (default: "
                         "PROFILE_overlap_<tag>.json at the repo root)")
    ap.add_argument("--no-json", action="store_true")
    ap.add_argument("--tag", default=None)
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run(quick=args.quick)
    if not args.no_json:
        path = args.json
        if path is None:
            tag = args.tag or jax.default_backend()
            root = os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))
            path = os.path.join(root, f"PROFILE_overlap_{tag}.json")
        dump_json(path)


if __name__ == "__main__":
    main()
