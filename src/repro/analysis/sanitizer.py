"""Interpret-mode access sanitizer: the dynamic backstop to the static
verifier.

:class:`AccessTrace` installs itself as the emit hook of
:mod:`repro.core.backend`, so every *interpreted* ``pallas_call`` the
engine lowers while the trace is active gets instrumented:

* every ``BlockSpec`` index map is wrapped to record, per grid step,
  the block index it actually returned (``jax.debug.callback`` fires
  with the concrete runtime values even under ``jit``);
* :func:`repro.core.backend.load` / ``store`` are shimmed for the
  duration, so the
  gpu structure's computed-offset accesses -- the ones no BlockSpec
  describes -- are recorded as concrete (offset, size) windows per ref
  shape.

``crosscheck()`` then compares the recorded traces against the
*statically* computed access sets:

* each operand's recorded index-map trace must equal the host
  evaluation of the original index map over the full grid (the
  block-indexed structure's complete read/write set);
* every recorded load/store window must be in-bounds for its ref;
* for kernels with a storage access model ("write", "ca"), the set of
  dynamically stored tiles must equal the static write set
  (``plan.storage_index`` over live steps), and every loaded tile must
  lie in the static read set (center + valid-clamped neighbours).

Launches of :class:`~repro.core.shard.ShardedPlan` are observed but not
instrumented: under ``shard_map`` one trace serves every device, so a
single record stream cannot be attributed to a device; the static
verifier covers those per-device.

``verify_launches(fn, *args, kernel=...)`` is the convenience wrapper:
run ``fn`` under a trace and raise
:class:`~repro.analysis.verifier.PlanVerificationError` on any
mismatch.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.experimental import pallas as pl

from repro.core import backend as backend_lib
from repro.core.shard import ShardedPlan

from .verifier import (ACCESS_MODELS, Finding, host_prefetch_refs,
                       neighbor_tiles, plan_signature, storage_grid,
                       storage_tiles)


def _full_steps(plan) -> Tuple[np.ndarray, ...]:
    """Every grid-step id tuple of one launch, batch dims included."""
    grids = np.meshgrid(*[np.arange(int(g)) for g in plan.grid],
                        indexing="ij") if plan.grid else []
    return tuple(g.ravel().astype(np.int64) for g in grids)


class _Launch:
    """One instrumented emission and everything recorded about it."""

    def __init__(self, lid: int, record):
        self.lid = lid
        self.record = record
        self.specs: List[Tuple[str, Any]] = []   # (opid, original spec)
        self.im_trace: Dict[str, set] = {}       # opid -> {(ids + idx)}
        self.accesses: set = set()   # (kind, shape, starts, sizes)
        self.operand_shapes: Optional[Tuple] = None
        self.out_shapes: Tuple = ()

    @property
    def plan(self):
        return self.record.plan


class AccessTrace:
    """Context manager recording the accesses of every interpreted
    launch emitted (and executed) inside the ``with`` block.

    >>> with AccessTrace() as tr:
    ...     out = sierpinski_write(8, block=4)
    >>> findings = tr.crosscheck(kernel="write")
    """

    def __init__(self, kernel: str = "generic"):
        self.kernel = kernel
        self.launches: List[_Launch] = []
        self._active = False
        self._prev_hook = None
        self._orig_load = None
        self._orig_store = None
        self._stack: List[_Launch] = []

    # -- lifecycle -----------------------------------------------------------

    def __enter__(self) -> "AccessTrace":
        self._prev_hook = backend_lib.set_emit_hook(self)
        self._orig_load = backend_lib.load
        self._orig_store = backend_lib.store
        backend_lib.load = self._shim_load
        backend_lib.store = self._shim_store
        self._active = True
        # previously traced configs would reuse cached, un-instrumented
        # executables: force a re-trace of everything run in the block
        jax.clear_caches()
        return self

    def __exit__(self, *exc):
        self._active = False
        backend_lib.set_emit_hook(self._prev_hook)
        backend_lib.load = self._orig_load
        backend_lib.store = self._orig_store
        # drop the instrumented executables so later calls re-trace
        # clean (the recording callbacks hold a reference to us)
        jax.clear_caches()
        return False

    # -- emit-hook protocol --------------------------------------------------

    def instrument(self, record, kernel, in_specs, out_specs):
        launch = _Launch(len(self.launches), record)
        self.launches.append(launch)
        if isinstance(record.plan, ShardedPlan):
            return kernel, in_specs, out_specs

        def kernel_wrapped(coords, *refs):
            self._stack.append(launch)
            try:
                kernel(coords, *refs)
            finally:
                self._stack.pop()

        new_in = [self._wrap_spec(launch, f"in{i}", s)
                  for i, s in enumerate(in_specs)]
        if isinstance(out_specs, (list, tuple)):
            new_out = type(out_specs)(
                self._wrap_spec(launch, f"out{i}", s)
                for i, s in enumerate(out_specs))
        else:
            new_out = self._wrap_spec(launch, "out0", out_specs)
        return kernel_wrapped, new_in, new_out

    def wrap_call(self, record, fn):
        launch = next(ln for ln in reversed(self.launches)
                      if ln.record is record)
        if isinstance(record.plan, ShardedPlan):
            return fn

        def call(*operands):
            if launch.operand_shapes is None:
                launch.operand_shapes = tuple(
                    tuple(op.shape) for op in operands)
                shp = record.out_shape
                if not isinstance(shp, (list, tuple)):
                    shp = (shp,)
                launch.out_shapes = tuple(tuple(s.shape) for s in shp)
            return fn(*operands)

        return call

    # -- recording -----------------------------------------------------------

    def _wrap_spec(self, launch, opid, spec):
        bs = getattr(spec, "block_shape", None)
        im = getattr(spec, "index_map", None)
        if bs is None or im is None:
            return spec          # SMEM / ANY / whole-operand specs
        launch.specs.append((opid, spec))
        launch.im_trace.setdefault(opid, set())
        ngrid = len(launch.plan.grid)
        trace = self

        def index_map(*args):
            idx = im(*args)
            idx_t = idx if isinstance(idx, tuple) else (idx,)
            jax.debug.callback(trace._on_im, launch.lid, opid,
                               *args[:ngrid], *idx_t)
            return idx

        return pl.BlockSpec(bs, index_map)

    def _on_im(self, lid, opid, *vals):
        if not self._active:
            return
        launch = self.launches[int(lid)]
        launch.im_trace[opid].add(
            tuple(int(np.asarray(v)) for v in vals))

    def _shim_load(self, ref, idx):
        self._record_access("load", ref, idx)
        return self._orig_load(ref, idx)

    def _shim_store(self, ref, idx, val):
        self._record_access("store", ref, idx)
        return self._orig_store(ref, idx, val)

    def _record_access(self, kind, ref, idx):
        if not self._stack or not self._active:
            return
        launch = self._stack[-1]
        shape = tuple(int(s) for s in ref.shape)
        if idx is None:
            idx = tuple(slice(None) for _ in shape)
        starts, sizes = [], []
        for dim, i in zip(shape, idx):
            if isinstance(i, slice):
                starts.append(0 if i.start is None else i.start)
                sizes.append(dim if i.stop is None else i.stop)
            elif hasattr(i, "start") and hasattr(i, "size"):
                starts.append(i.start)
                sizes.append(int(i.size))
            else:
                starts.append(i)
                sizes.append(1)
        trace = self

        def rec(*vals):
            if not trace._active:
                return
            launch.accesses.add(
                (kind, shape, tuple(int(np.asarray(v)) for v in vals),
                 tuple(sizes)))

        jax.debug.callback(rec, *starts)

    # -- crosscheck ----------------------------------------------------------

    def crosscheck(self, kernel: Optional[str] = None) -> List[Finding]:
        """Diff every launch's recorded trace against its static access
        sets; returns the findings (empty = traces match)."""
        jax.effects_barrier()
        model = ACCESS_MODELS[kernel or self.kernel]
        findings: List[Finding] = []
        for launch in self.launches:
            if isinstance(launch.plan, ShardedPlan):
                continue
            if launch.operand_shapes is None:
                continue         # emitted but never called
            self._check_im_trace(launch, findings)
            self._check_accesses(launch, model, findings)
        return findings

    def _host_im(self, launch, spec, ids):
        refs = host_prefetch_refs(launch.plan)
        idx = spec.index_map(*ids, *refs)
        idx_t = idx if isinstance(idx, tuple) else (idx,)
        return [np.broadcast_to(np.asarray(v).astype(np.int64),
                                ids[-1].shape) for v in idx_t]

    def _check_im_trace(self, launch, findings):
        ids = _full_steps(launch.plan)
        sig = plan_signature(launch.plan)
        for opid, spec in launch.specs:
            exp_idx = self._host_im(launch, spec, ids)
            expected = set(zip(*[a.tolist() for a in ids],
                               *[a.tolist() for a in exp_idx]))
            got = launch.im_trace[opid]
            if got == expected:
                continue
            for t in sorted(got - expected)[:2]:
                findings.append(Finding(
                    "sanitizer", f"{sig}: operand {opid} index map "
                    f"returned {t[len(ids):]} at step {t[:len(ids)]}; "
                    f"the static evaluation never produces it"))
            for t in sorted(expected - got)[:2]:
                findings.append(Finding(
                    "sanitizer", f"{sig}: operand {opid} never "
                    f"recorded the statically expected block index "
                    f"{t[len(ids):]} at step {t[:len(ids)]}"))

    def _check_accesses(self, launch, model, findings):
        sig = plan_signature(launch.plan)
        for kind, shape, starts, sizes in launch.accesses:
            for dim, s, z in zip(shape, starts, sizes):
                if s < 0 or s + z > dim:
                    findings.append(Finding(
                        "sanitizer", f"{sig}: {kind} window "
                        f"[{s}, {s + z}) out of bounds for axis of "
                        f"extent {dim} (ref shape {shape})"))
        if not model["storage"] or not model["race"]:
            return
        plan = launch.plan
        nr, nc = storage_grid(plan)
        refs = host_prefetch_refs(plan)
        from .verifier import decode_steps
        ids, bx, by, live = decode_steps(plan, refs)
        r, c = storage_tiles(plan, refs, ids)
        write_tiles = set(zip(r[live].tolist(), c[live].tolist()))
        read_tiles = set(write_tiles)
        if model["neighbors"]:
            from repro.core.compact import NEIGHBOR_OFFSETS8
            for j in range(len(NEIGHBOR_OFFSETS8)):
                jr, jc = neighbor_tiles(plan, refs, ids, j)
                read_tiles |= set(zip(jr[live].tolist(),
                                      jc[live].tolist()))
        for kind, shape, starts, sizes in launch.accesses:
            if len(shape) != 2:
                continue
            th, tw = sizes
            if th <= 0 or tw <= 0 or shape[0] % th or shape[1] % tw:
                continue
            if (shape[0] // th, shape[1] // tw) != (nr, nc):
                continue         # not the storage-tiled state array
            if starts[0] % th or starts[1] % tw:
                findings.append(Finding(
                    "sanitizer", f"{sig}: {kind} at {starts} is not "
                    f"tile-aligned to the ({th}, {tw}) storage tiling"))
                continue
            tile = (starts[0] // th, starts[1] // tw)
            if kind == "store" and tile not in write_tiles:
                findings.append(Finding(
                    "sanitizer", f"{sig}: store to tile {tile} is "
                    f"outside the static write set"))
            if kind == "load" and tile not in read_tiles:
                findings.append(Finding(
                    "sanitizer", f"{sig}: load of tile {tile} is "
                    f"outside the static read set"))
        # completeness: every static write tile must have been stored
        stored = set()
        for kind, shape, starts, sizes in launch.accesses:
            if kind != "store" or len(shape) != 2:
                continue
            th, tw = sizes
            if th > 0 and tw > 0 and not (shape[0] % th or shape[1] % tw) \
                    and (shape[0] // th, shape[1] // tw) == (nr, nc) \
                    and not (starts[0] % th or starts[1] % tw):
                stored.add((starts[0] // th, starts[1] // tw))
        if stored and stored != write_tiles:
            missing = sorted(write_tiles - stored)[:3]
            if missing:
                findings.append(Finding(
                    "sanitizer", f"{sig}: static write set expects "
                    f"stores to tiles {missing} that never happened"))


def verify_launches(fn, *args, kernel: str = "generic",
                    strict: bool = True, **kwargs):
    """Run ``fn(*args, **kwargs)`` under an :class:`AccessTrace` and
    cross-check.  Returns ``(result, findings)``; with ``strict`` (the
    default) raises on any finding instead."""
    with AccessTrace(kernel=kernel) as tr:
        out = fn(*args, **kwargs)
        out = jax.block_until_ready(out)
    findings = tr.crosscheck()
    if strict and findings:
        from .verifier import PlanVerificationError
        lines = "\n  ".join(str(f) for f in findings)
        raise PlanVerificationError(
            f"access sanitizer found mismatches:\n  {lines}")
    return out, findings
