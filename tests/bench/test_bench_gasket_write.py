"""The map-write cell rehearsed on the CPU at a tiny size: a sound run is
correct, and the control and each fault the cell can have come out not
correct."""
import pytest

import tiny_cells

CELL = "gasket.map-write"


def test_sound_run_is_correct():
    line = tiny_cells.run(CELL)
    assert line["correct"], line["checks"]
    assert line["checks"]["write_max_abs_err"] == {"value": 0.0,
                                                   "limit": 0.0}
    assert set(line["metrics"]) == {"cell_updates_per_s", "setup_s"}


def test_traced_run_reports_the_layer_metrics():
    line = tiny_cells.run(CELL, trace=1)
    assert line["correct"]
    assert "device_idle.gasket" in line["metrics"]
    assert "device_idle.serve" not in line["metrics"]


def test_control_is_not_correct():
    assert not tiny_cells.run(CELL, control=1)["correct"]


def _unchanged(orig):
    return lambda m, value, **kw: m


def _altered(orig):
    return lambda m, value, **kw: orig(m, value + 1.0, **kw)


def _half(orig):
    def call(m, value, **kw):
        keep = m[:m.shape[0] // 2]
        return orig(m, value, **kw).at[:keep.shape[0]].set(keep)
    return call


def _one_block(orig):
    """One packed block left as it was: the last, at the deepest level of
    the lambda map."""
    def call(m, value, **kw):
        b = kw["block"]
        return orig(m, value, **kw).at[-b:, -b:].set(m[-b:, -b:])
    return call


@pytest.mark.parametrize("fault", [_unchanged, _altered, _half, _one_block])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "sierpinski_write",
                        fault(ops.sierpinski_write))
    assert not tiny_cells.run(CELL)["correct"]
