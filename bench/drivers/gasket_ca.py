"""Heat diffusion on the compact gasket through ``ops.ca_run``.

Traffic parameters (workload ``traffic``): ``rule`` (``diffusion``),
``alpha``, ``fuse`` (steps per kernel launch), ``steps_per_call`` (steps
of one ``ca_run`` call), ``trace_seconds``, ``limit``.  The window calls
``ca_run`` back to back on one state made from the seed; each call gets a
fresh stale buffer, as a caller of the entry must give it.
``cell_updates_per_s`` counts the gasket's own cells times the steps
completed, over the whole window.

The comparison: the window's last call, every stored cell of its output
against ``steps_per_call`` steps of the reference from its input,
computed in f32 (max |difference|).  The control computes the same steps
in bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import counts
from bench.drivers._gasket import Gasket, timed_calls
from bench.harness import Check, Result


def run(h) -> Result:
    from repro.kernels import ops

    tr, cfg = h.traffic, h.config
    steps, fuse = int(tr["steps_per_call"]), int(tr["fuse"])
    g = Gasket(cfg, h.seed)
    if tr["rule"] != "diffusion" or steps % fuse or steps > g.block:
        raise ValueError("the cell runs diffusion in whole launches, at "
                         "most one block of steps a call (the reference's "
                         "window reaches one block beyond each block)")
    kw = dict(fuse=fuse, rule=tr["rule"], alpha=float(tr["alpha"]),
              block=g.block, grid_mode=cfg["grid_mode"],
              storage=cfg["storage"], n=g.n)

    def call(state, j):
        with h.span("ops.ca_run"):
            return ops.ca_run(state, jnp.zeros_like(state), steps, **kw)

    state = g.initial_state()
    state = call(state, -1)          # compiles: set-up
    jax.block_until_ready(state)
    state, calls, seconds, pairs = timed_calls(h, call, state, compared=1)
    h.read_memory()
    del state

    (_, before, after), = pairs
    value = g.diffusion_error(before, after, steps=steps,
                              alpha=float(tr["alpha"]), control=h.control)
    launches = calls * (steps // fuse)
    per_launch = counts.ca_fused(n=g.n, steps=fuse,
                                 stored_bytes=g.stored_bytes)
    return Result(
        metrics={"cell_updates_per_s": (g.members * steps * calls / seconds,
                                        "cells/s")},
        attempted=calls, failed=0,
        checks=[Check("ca_max_abs_err", value, float(tr["limit"]))],
        work={"kernel": {"entry": "_ca_run_impl",
                         "ops": per_launch["ops"] * launches,
                         "bytes": per_launch["bytes"] * launches}})
