"""Mesh construction for the production topology.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; smoke tests see
the single real CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: sharding rules and
    ``shard_map`` specs steer the partitioner, and untyped ops such as
    the embedding gather keep working on sharded operands (``Explicit``
    axes, the ``jax.make_mesh`` default, would demand an out-sharding
    for each of them)."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod ('data' x 'model'); 2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (CPU smoke / elastic restart)."""
    n = jax.device_count()
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by tp={model_parallel}")
    return make_mesh((n // model_parallel, model_parallel),
                     ("data", "model"))


def resolve_cli_mesh(spec: str):
    """One mesh for the whole process, from a CLI flag.

    '' -> None (single device); 'host' -> every visible device as
    (data, model=1); 'DxM' -> an explicit (data, model) shape.  The
    returned mesh is the one :mod:`repro.distributed.sharding` rules
    partition over AND the one the block-space kernels shard over (their
    ``shard_axis`` defaults to this mesh's 'data' axis), so serving and
    training never build a second mesh for the fractal side."""
    if not spec:
        return None
    if spec == "host":
        return make_host_mesh()
    try:
        data, model = (int(x) for x in spec.lower().split("x"))
    except ValueError:
        raise ValueError(
            f"--mesh expects '', 'host' or 'DATAxMODEL' (e.g. '4x2'); "
            f"got {spec!r}") from None
    return make_mesh((data, model), ("data", "model"))
