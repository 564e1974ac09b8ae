"""CLI: statically verify every plan the execution engine can emit.

``python -m repro.analysis.verify --matrix`` sweeps the registered
domain zoo across every lowering, storage, coarsening factor and
(emulated) shard count, runs the five static checks of
:mod:`repro.analysis.verifier` on each resulting plan, then drives the
interpret-mode access sanitizer (:mod:`repro.analysis.sanitizer`) over
real kernel launches under the interpreter.  The result is a JSON
report (``--out``) and a nonzero exit status when any combination
produced a finding -- which is what lets CI gate merges on it.

``--smoke`` cuts the sweep to a representative subset so the gate runs
in seconds; the nightly/full run drops the flag.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Iterator, Optional, Tuple

from .verifier import HostMesh, verify_plan

#: domains whose lambda map is a digit-unrolled fractal -- the ones
#: with a compact storage layout and a coarsening axis.
FRACTAL_DOMAINS = ("sierpinski", "carpet", "vicsek")

#: coarsening factor exercised per fractal (one supertile level: the
#: gasket contracts by 2, the k=8/k=5 carpets by 3).
COARSEN = {"sierpinski": 2, "carpet": 3, "vicsek": 3}

#: shard counts emulated through :class:`HostMesh` (no devices needed).
SHARD_COUNTS = (1, 2, 3)


def registered_domains(size: str = "small") -> dict:
    """The domain zoo the matrix sweeps: every BlockDomain family the
    repo ships, at sizes small enough that exhaustive host enumeration
    of the grid stays fast."""
    from repro.core import fractal as F
    from repro.core.domain import (BandDomain, BoundingBoxDomain,
                                   GeneralizedFractalDomain,
                                   SierpinskiDomain, TriangularDomain)
    if size != "small":
        raise ValueError(f"unknown matrix size {size!r}")
    return {
        "sierpinski": SierpinskiDomain(8),
        "carpet": GeneralizedFractalDomain(F.CARPET, 9),
        "vicsek": GeneralizedFractalDomain(F.VICSEK, 9),
        "triangular": TriangularDomain(6),
        "band": BandDomain(8, 3),
        "bounding-box": BoundingBoxDomain(4, 3),
    }


def matrix_plans(smoke: bool = False) -> Iterator[Tuple[str, object, str]]:
    """Yield ``(label, plan, kernel_model)`` for every combination the
    matrix covers: unsharded x {lowering, storage}, coarsened fractals,
    and sharded plans across partitions / halo modes / shard counts."""
    from repro.core.plan import LOWERINGS, GridPlan
    from repro.core.shard import ShardedPlan

    domains = registered_domains("small")
    names = ("sierpinski", "triangular") if smoke else tuple(domains)
    # -- unsharded: every domain x lowering x applicable storage -------------
    for name in names:
        dom = domains[name]
        storages = ("embedded", "compact") if name in FRACTAL_DOMAINS \
            else ("embedded",)
        for lowering in LOWERINGS:
            for storage in storages:
                plan = GridPlan(dom, lowering, storage=storage)
                yield (f"{name}/{lowering}/{storage}", plan, "write")
    # -- coarsened fractals --------------------------------------------------
    coarse = ("sierpinski",) if smoke else FRACTAL_DOMAINS
    for name in coarse:
        dom, c = domains[name], COARSEN[name]
        for lowering in LOWERINGS:
            for storage in ("embedded", "compact"):
                plan = GridPlan(dom, lowering, storage=storage, coarsen=c)
                yield (f"{name}/{lowering}/{storage}/coarsen={c}",
                       plan, "write")
    # -- sharded: emulated meshes, every partition x halo mode ---------------
    sharded = ("sierpinski",) if smoke else ("sierpinski", "carpet")
    counts = (1, 2) if smoke else SHARD_COUNTS
    variants = (("compact", "storage-rows", True),
                ("compact", "storage-rows", False),
                ("embedded", "linear", False))
    for name in sharded:
        dom = domains[name]
        for d in counts:
            mesh = HostMesh(d, axis="data")
            for lowering in LOWERINGS:
                for storage, partition, halo in variants:
                    plan = ShardedPlan(dom, lowering, storage=storage,
                                       mesh=mesh, axis="data",
                                       partition=partition, halo=halo)
                    tag = f"halo={int(halo)}" if partition == \
                        "storage-rows" else partition
                    yield (f"{name}/{lowering}/{storage}/D={d}/{tag}",
                           plan, "write")


def run_static_matrix(smoke: bool = False, verbose: bool = True) -> list:
    """Verify every matrix plan; returns ``[(label, Report)]``."""
    out = []
    for label, plan, kernel in matrix_plans(smoke=smoke):
        report = verify_plan(plan, kernel=kernel)
        out.append((label, report))
        if verbose:
            status = "ok" if report.ok else \
                f"FAIL ({len(report.findings)} findings)"
            print(f"  static {label}: {status}")
            for f in report.findings:
                print(f"    - {f}")
    return out


def run_sanitizer_smoke(smoke: bool = False, verbose: bool = True) -> list:
    """Drive real kernel launches under the access sanitizer on the
    interpreter; returns ``[(label, findings)]``."""
    import jax.numpy as jnp

    from repro.core.compact import compact_layout
    from repro.core.domain import make_fractal_domain
    from repro.kernels.sierpinski_ca import ca_run
    from repro.kernels.sierpinski_write import sierpinski_write
    from .sanitizer import verify_launches

    dom = make_fractal_domain("sierpinski-gasket", 8)
    lay = compact_layout(dom)
    block = 3
    operands = {"embedded": jnp.zeros((24, 24), jnp.float32),
                "compact": jnp.zeros(lay.array_shape(block), jnp.float32)}
    grid_modes = ("closed_form", "mma") if smoke \
        else ("closed_form", "prefetch_lut", "bounding", "mma")
    out = []
    for storage in ("embedded", "compact"):
        for gm in grid_modes:
            label = f"write/{storage}/{gm}"
            _, findings = verify_launches(
                sierpinski_write, operands[storage], 1.0, block=block,
                grid_mode=gm, storage=storage, domain=dom, num_stages=1,
                backend="tpu-interpret", strict=False)
            out.append((label, findings))
            _say(label, findings, verbose)
    state = operands["compact"]
    label = "ca/compact/closed_form"
    _, findings = verify_launches(
        ca_run, state, jnp.zeros_like(state), 2, fuse=1, block=block,
        grid_mode="closed_form", storage="compact", domain=dom,
        num_stages=1, backend="tpu-interpret", strict=False)
    out.append((label, findings))
    _say(label, findings, verbose)
    return out


def _say(label: str, findings: list, verbose: bool) -> None:
    if verbose:
        status = "ok" if not findings else f"FAIL ({len(findings)})"
        print(f"  sanitize {label}: {status}")
        for f in findings:
            print(f"    - {f}")


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.verify",
        description=__doc__.splitlines()[0])
    ap.add_argument("--matrix", action="store_true",
                    help="sweep the full domain/lowering/storage/shard "
                         "matrix")
    ap.add_argument("--smoke", action="store_true",
                    help="representative subset (CI gate)")
    ap.add_argument("--no-sanitize", action="store_true",
                    help="static checks only, skip interpret-mode "
                         "sanitizer launches")
    ap.add_argument("--out", default=None,
                    help="write the JSON report here")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    if not args.matrix:
        ap.error("nothing to do: pass --matrix")
    verbose = not args.quiet

    static = run_static_matrix(smoke=args.smoke, verbose=verbose)
    sanitized = [] if args.no_sanitize else \
        run_sanitizer_smoke(smoke=args.smoke, verbose=verbose)

    n_findings = sum(len(r.findings) for _, r in static) + \
        sum(len(fs) for _, fs in sanitized)
    report = {
        "ok": n_findings == 0,
        "num_static": len(static),
        "num_sanitized": len(sanitized),
        "num_findings": n_findings,
        "static": [{"label": label, **r.to_json()} for label, r in static],
        "sanitizer": [{"label": label, "ok": not fs,
                       "findings": [f.to_json() for f in fs]}
                      for label, fs in sanitized],
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    print(f"verified {len(static)} plans statically, "
          f"{len(sanitized)} sanitized launches: "
          f"{n_findings} findings")
    return 0 if n_findings == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
