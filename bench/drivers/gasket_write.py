"""The paper's map kernel: ``ops.sierpinski_write`` of a value to every
cell of the compact gasket, called back to back.

Traffic parameters: ``values`` (written in turn, call j writes
``values[j % len(values)]``; fixed, so every seed compiles the same
programs), ``trace_seconds``, ``limit``.
The state starts with random values in every stored cell, so the cells
off the gasket must keep theirs.
``cell_updates_per_s`` counts the gasket's own cells times the calls
completed, over the whole window.

The comparison: the window's last ``len(values)`` calls, one of each
program, every stored cell of each output against the reference write of
that call's value on its input (max |difference|, exact: limit 0).  The
control computes the reference write in bfloat16.
"""
from __future__ import annotations

import jax
import numpy as np

from bench import counts
from bench.drivers._gasket import Gasket, timed_calls
from bench.harness import Check, Result


def run(h) -> Result:
    from repro.kernels import ops

    tr, cfg = h.traffic, h.config
    values = [float(v) for v in tr["values"]]
    g = Gasket(cfg, h.seed)
    kw = dict(block=g.block, grid_mode=cfg["grid_mode"],
              storage=cfg["storage"], n=g.n)

    def call(state, j):
        with h.span("ops.sierpinski_write"):
            return ops.sierpinski_write(state, values[j % len(values)], **kw)

    state = g.initial_state(members_only=False)
    for j in range(len(values)):     # one program per value: set-up
        state = call(state, j - len(values))
    jax.block_until_ready(state)
    state, calls, seconds, pairs = timed_calls(h, call, state,
                                               compared=len(values))
    h.read_memory()
    del state

    err = float(np.max([g.write_error(before, after,
                                      values[j % len(values)],
                                      control=h.control)
                        for j, before, after in pairs]))   # keeps a NaN
    per_call = counts.write(stored_bytes=g.stored_bytes)
    return Result(
        metrics={"cell_updates_per_s": (g.members * calls / seconds,
                                        "cells/s")},
        attempted=calls, failed=0,
        checks=[Check("write_max_abs_err", err, float(tr["limit"]))],
        work={"kernel": {"entry": "_write_impl",
                         "ops": per_call["ops"] * calls,
                         "bytes": per_call["bytes"] * calls}})
