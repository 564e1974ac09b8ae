"""Elastic scaling: rebuild the mesh from whatever devices are alive and
re-lay-out a checkpoint onto it.

The checkpoint format stores parameters unsharded by tree path
(repro.checkpoint), and the sharding rules are pure functions of
(param tree, mesh), so scaling from e.g. 256 -> 192 chips after losing
a host is: build the largest valid mesh, recompute specs, restore with
``shardings=``.  The only constraint is that the model axis keeps
dividing the TP-sharded dims -- `candidate_meshes` enumerates valid
shapes largest-first.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax

from . import sharding as shard_lib


def candidate_meshes(n_devices: int, max_model: int = 16
                     ) -> List[Tuple[int, int]]:
    """(data, model) shapes using as many devices as possible, preferring
    larger model-parallel degree (keeps per-device weight shards small)."""
    out = []
    for model in range(min(max_model, n_devices), 0, -1):
        data = n_devices // model
        if data * model >= 1:
            out.append((data, model))
    out.sort(key=lambda dm: (-(dm[0] * dm[1]), -dm[1]))
    return out


def make_elastic_mesh(devices=None, max_model: int = 16):
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    data, model = candidate_meshes(n, max_model)[0]
    used = data * model
    from repro.launch.mesh import make_mesh
    return make_mesh((data, model), ("data", "model"),
                     devices=devices[:used])


def elastic_restore(ckpt_manager, params_template, cfg=None, *,
                    mesh=None, fsdp: bool = False, step: Optional[int] = None):
    """Restore the latest checkpoint onto a (possibly different) mesh."""
    mesh = mesh or make_elastic_mesh()
    specs = shard_lib.param_spec_tree(params_template, cfg, fsdp=fsdp)
    shardings = shard_lib.named_sharding_tree(specs, mesh)
    step, params, opt, meta = ckpt_manager.restore(
        step, params_template, None, shardings=shardings)
    return mesh, step, params, meta
