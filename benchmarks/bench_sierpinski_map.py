"""Paper SS IV microbenchmark (Fig. 8): write a constant to every cell of
the embedded Sierpinski gasket -- lambda(w) compact map vs bounding-box.

On this CPU container the CUDA kernels are stood in for by their XLA
lowerings of the SAME two algorithms:

  * bounding-box: evaluate the membership bit test over all n^2 cells
    and masked-write (the run-time-discard baseline);
  * lambda(w):    compute the compact map for the 3^r_b blocks inside
    the timed region (the map cost is part of the measurement, as in the
    paper) and tile-scatter the value -- touching only n^H cells.

The block-size sweep rho in {1,2,4,8,16,32} mirrors the paper's
configuration space: blocks are rho x rho tiles scattered per mapped
block coordinate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fractal as F
from repro.core.plan import LOWERINGS
from repro.kernels import ops
from .common import row, time_fn


@functools.partial(jax.jit, static_argnames=("n",))
def bb_write(m, n):
    y, x = jnp.mgrid[0:n, 0:n]
    member = (x & (n - 1 - y)) == 0
    return jnp.where(member, jnp.float32(7.0), m)


@functools.partial(jax.jit, static_argnames=("r_b", "block"))
def lam_write(m, r_b, block):
    i = jnp.arange(3 ** r_b, dtype=jnp.int32)
    lx, ly = F.lambda_map_linear(i, r_b)           # the paper's map
    iy = jnp.arange(block)
    ix = jnp.arange(block)
    rows = (ly[:, None, None] * block + iy[None, :, None])
    cols = (lx[:, None, None] * block + ix[None, None, :])
    gx = cols
    gy = rows
    n = (2 ** r_b) * block
    member = (gx & (n - 1 - gy)) == 0              # intra-block sub-box test
    vals = jnp.where(member, jnp.float32(7.0), 0.0)
    return m.at[rows, cols].set(vals)


@functools.partial(jax.jit, static_argnames=("r", "block"))
def lam_write_packed(mp, r, block):
    """The compact-parallel-space analogue: the state lives in the packed
    layout (3^r_b compact blocks of rho x rho), so the write touches
    exactly the n^H live cells with unit stride -- what the lambda grid
    achieves on an accelerator by never scheduling dead blocks."""
    i = jnp.arange(3 ** r, dtype=jnp.int32)
    lx, ly = F.lambda_map_linear(i, r)     # map still computed (timed)
    sel = ((lx + ly) >= 0)[:, None, None]  # consume the map
    return jnp.where(sel, jnp.float32(7.0), mp)


def run_lowering_ab(iters: int = 5):
    """GridPlan lowering A/B on the Pallas write kernel (interpret on
    CPU): the paper-family lambda-vs-LUT-vs-bounding-box comparison,
    per domain and block size.  On TPU the same sweep times the
    compiled Mosaic kernels."""
    print("# GridPlan lowering A/B (Pallas write kernel):")
    print("#   closed_form = inline lambda decode in index_maps")
    print("#   prefetch_lut = scalar-prefetch coordinate table")
    print("#   bounding     = full grid + run-time discard")
    cases = (
        ("sierpinski-gasket", 64, (8, 16, 32)),
        ("sierpinski-carpet", 27, (3, 9)),
        ("vicsek-cross", 27, (3, 9)),
    )
    for fractal, n, blocks in cases:
        m = jnp.zeros((n, n), jnp.float32)
        for rho in blocks:
            t_closed = None
            for low in LOWERINGS:
                fn = functools.partial(ops.sierpinski_write, value=7.0,
                                       block=rho, grid_mode=low,
                                       fractal=fractal)
                t = time_fn(fn, m, warmup=2, iters=iters)
                if t_closed is None:
                    t_closed = t
                row(f"gridplan_write/{fractal}/n={n}/rho={rho}/{low}", t,
                    f"speedup_vs_closed_form={t_closed / t:.2f}")


def run_storage_ab(iters: int = 5):
    """Compact-vs-embedded *storage* A/B on the Pallas write kernel: the
    same compact grid, with the state array either the dense n x n
    matrix or the packed Lemma 2 orthotope; reports bytes the write
    touches next to the time."""
    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    print("# storage A/B (Pallas write kernel): embedded n^2 array vs")
    print("#   compact orthotope-resident (Lemma 2) state")
    for n, rho in ((64, 8), (256, 16), (512, 32)):
        m = jnp.zeros((n, n), jnp.float32)
        lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                                n // rho))
        mp = jnp.zeros(lay.array_shape(rho), jnp.float32)
        t_emb = time_fn(functools.partial(
            ops.sierpinski_write, value=7.0, block=rho), m,
            warmup=2, iters=iters)
        t_pk = time_fn(functools.partial(
            ops.sierpinski_write, value=7.0, block=rho,
            storage="compact", n=n), mp, warmup=2, iters=iters)
        b_emb, b_pk = 4 * n * n, 4 * lay.num_cells(rho)
        row(f"write_storage/embedded/n={n}/rho={rho}", t_emb,
            f"bytes={b_emb}")
        row(f"write_storage/compact/n={n}/rho={rho}", t_pk,
            f"bytes={b_pk};bytes_saved={1 - b_pk / b_emb:.3f};"
            f"speedup={t_emb / t_pk:.2f}")


def run_backend_ab(iters: int = 5):
    """lambda(omega)-vs-bounding A/B on the Pallas write and CA kernels
    -- the paper's figure-level comparison -- on the default emission
    target (:mod:`repro.core.backend`)."""
    from repro.core import backend as backend_lib
    from repro.kernels.sierpinski_ca import ca_run

    targets = (backend_lib.resolve(None).name,)
    print("# backend A/B: lambda(omega) compact grids vs bounding-box,")
    print(f"#   per emission target ({', '.join(targets)})")
    n, rho = 64, 8
    m = jnp.zeros((n, n), jnp.float32)
    state = jnp.zeros((n, n), jnp.float32)
    for tname in targets:
        times = {}
        for low in LOWERINGS:
            fn = functools.partial(ops.sierpinski_write, value=7.0,
                                   block=rho, grid_mode=low,
                                   backend=tname)
            times[low] = time_fn(fn, m, warmup=2, iters=iters)
        for low in LOWERINGS:
            extra = "" if low == "bounding" else \
                f"speedup_vs_bounding={times['bounding'] / times[low]:.2f}"
            row(f"backend_write/{tname}/n={n}/rho={rho}/{low}",
                times[low], extra)
        ca_times = {}
        for low in ("closed_form", "bounding"):
            fn = functools.partial(ca_run, steps=8, rule="parity",
                                   block=rho, grid_mode=low, fuse=4,
                                   donate=False, backend=tname)
            ca_times[low] = time_fn(fn, state, state, warmup=1,
                                    iters=iters)
        row(f"backend_ca/{tname}/n={n}/rho={rho}/closed_form",
            ca_times["closed_form"],
            f"speedup_vs_bounding="
            f"{ca_times['bounding'] / ca_times['closed_form']:.2f}")
        row(f"backend_ca/{tname}/n={n}/rho={rho}/bounding",
            ca_times["bounding"], "")
    run_map_mma_ab(iters=iters)


def run_map_mma_ab(iters: int = 5):
    """``map_mma/*``: the raw lambda decode itself, digit-basis matmul
    (:mod:`repro.core.mma`, what the ``mma`` lowering computes on the
    MXU) vs the integer closed form -- the map cost in
    isolation, without a kernel around it."""
    from repro.core import mma

    print("# map_mma A/B: digit-basis matmul lambda decode vs the")
    print("#   integer closed form (all 3^r blocks, jitted)")

    @functools.partial(jax.jit, static_argnames=("r",))
    def dec_int(i, r):
        lx, ly = F.lambda_map_linear(i, r)
        return lx + ly

    @functools.partial(jax.jit, static_argnames=("r",))
    def dec_mma(i, r):
        bx, by = mma.decode_linear(F.SIERPINSKI, r, i)
        return bx + by

    for r in (6, 8, 10):
        i = jnp.arange(3 ** r, dtype=jnp.int32)
        t_int = time_fn(dec_int, i, r, warmup=2, iters=iters)
        t_mma = time_fn(dec_mma, i, r, warmup=2, iters=iters)
        row(f"map_mma/r={r}/closed_form", t_int, f"blocks={3 ** r}")
        row(f"map_mma/r={r}/mma", t_mma,
            f"blocks={3 ** r};"
            f"speedup_vs_closed_form={t_int / t_mma:.2f}")


def run(max_r: int = 11):
    run_lowering_ab()
    run_storage_ab()
    run_backend_ab()
    print("# paper Fig.8 analogue: lambda vs bounding-box write, CPU/XLA")
    print("# lam_scatter = embedded-layout scatter (CPU-hostile, kept as")
    print("# the documented negative result); lam_packed = compact layout")
    print("# name,us_per_call,derived")
    for rho in (1, 4, 16, 32):
        for r in range(6, max_r + 1):
            n = 2 ** r
            if n < rho or (n // rho) < 1:
                continue
            r_b = r - int(np.log2(rho))
            m = jnp.zeros((n, n), jnp.float32)
            mp = jnp.zeros((3 ** r_b, rho, rho), jnp.float32)
            t_bb = time_fn(bb_write, m, n, iters=10)
            t_lam = time_fn(lam_write, m, r_b, rho, iters=10)
            t_pk = time_fn(lam_write_packed, mp, r_b, rho, iters=10)
            row(f"sierpinski_write_bb/n={n}/rho={rho}", t_bb,
                f"touch={n * n}")
            row(f"sierpinski_write_lam_scatter/n={n}/rho={rho}", t_lam,
                f"touch={3 ** r_b * rho * rho};speedup={t_bb / t_lam:.2f}")
            row(f"sierpinski_write_lam_packed/n={n}/rho={rho}", t_pk,
                f"touch={3 ** r_b * rho * rho};speedup={t_bb / t_pk:.2f}")
    # parallel-space table (exact, Lemma 1)
    for r in range(4, 17):
        n = 2 ** r
        eff = F.gasket_volume(n) / (n * n)
        row(f"parallel_space/n={n}", 0.0,
            f"blocks_lambda={3 ** r};blocks_bb={n * n};"
            f"efficiency={eff:.5f}")


if __name__ == "__main__":
    run()
