"""Shared fixtures (none is autouse)."""
import pytest


@pytest.fixture
def chip_semantics(monkeypatch):
    """Run every interpreted pallas_call under ``pltpu.InterpretParams``,
    which models the chip where the plain interpreter does not: output
    blocks start uninitialized (NaN) in VMEM instead of being read from
    HBM before each grid step."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    orig = pl.pallas_call

    def faithful(*args, interpret=False, **kwargs):
        if interpret is True:
            interpret = pltpu.InterpretParams()
        return orig(*args, interpret=interpret, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", faithful)
    jax.clear_caches()            # drop executables traced without it
    yield
    monkeypatch.undo()
    jax.clear_caches()
