"""Interpret-mode access sanitizer: the dynamic backstop to the static
verifier.

:class:`AccessTrace` installs itself as the emit hook of
:mod:`repro.core.backend`, so every *interpreted* ``pallas_call`` the
engine lowers while the trace is active gets instrumented: every
``BlockSpec`` index map is wrapped to record, per grid step, the
block index it actually returned (``jax.debug.callback`` fires with the
concrete runtime values even under ``jit``).  ``crosscheck()`` then
compares each operand's recorded index-map trace with the host
evaluation of the original index map over the full grid: the launch's
complete BlockSpec read/write set.

Launches of :class:`~repro.core.shard.ShardedPlan` are observed but not
instrumented: under ``shard_map`` one trace serves every device, so a
single record stream cannot be attributed to a device; the static
verifier covers those per-device.

``verify_launches(fn, *args)`` is the convenience wrapper: run ``fn``
under a trace and raise
:class:`~repro.analysis.verifier.PlanVerificationError` on any mismatch.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.experimental import pallas as pl

from repro.core import backend as backend_lib
from repro.core.shard import ShardedPlan

from .verifier import Finding, host_prefetch_refs, plan_signature


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
    >>> findings = tr.crosscheck()
    """

    def __init__(self):
        self.launches: List[_Launch] = []
        self._active = False
        self._prev_hook = None

    # -- lifecycle -----------------------------------------------------------

    def __enter__(self) -> "AccessTrace":
        self._prev_hook = backend_lib.set_emit_hook(self)
        self._active = True
        # previously traced configs would reuse cached, un-instrumented
        # executables: force a re-trace of everything run in the block
        jax.clear_caches()
        return self

    def __exit__(self, *exc):
        self._active = False
        backend_lib.set_emit_hook(self._prev_hook)
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
        new_in = [self._wrap_spec(launch, f"in{i}", s)
                  for i, s in enumerate(in_specs)]
        if isinstance(out_specs, (list, tuple)):
            new_out = type(out_specs)(
                self._wrap_spec(launch, f"out{i}", s)
                for i, s in enumerate(out_specs))
        else:
            new_out = self._wrap_spec(launch, "out0", out_specs)
        return kernel, new_in, new_out

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

    # -- crosscheck ----------------------------------------------------------

    def crosscheck(self) -> List[Finding]:
        """Diff every launch's recorded index-map trace against its
        static evaluation; returns the findings (empty = traces
        match)."""
        jax.effects_barrier()
        findings: List[Finding] = []
        for launch in self.launches:
            if isinstance(launch.plan, ShardedPlan):
                continue
            if launch.operand_shapes is None:
                continue         # emitted but never called
            self._check_im_trace(launch, findings)
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


def verify_launches(fn, *args, strict: bool = True, **kwargs):
    """Run ``fn(*args, **kwargs)`` under an :class:`AccessTrace` and
    cross-check.  Returns ``(result, findings)``; with ``strict`` (the
    default) raises on any finding instead."""
    with AccessTrace() as tr:
        out = fn(*args, **kwargs)
        out = jax.block_until_ready(out)
    findings = tr.crosscheck()
    if strict and findings:
        from .verifier import PlanVerificationError
        lines = "\n  ".join(str(f) for f in findings)
        raise PlanVerificationError(
            f"access sanitizer found mismatches:\n  {lines}")
    return out, findings
