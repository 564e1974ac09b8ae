"""The heat-diffusion cell rehearsed on the CPU at a tiny size: a sound
run is correct, and the control and each fault the cell can have come
out not correct."""
import pytest

import tiny_cells

CELL = "gasket.heat-fused8"


def test_sound_run_is_correct_and_complete():
    line = tiny_cells.run(CELL)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    m = line["metrics"]
    assert set(m) == {"cell_updates_per_s", "setup_s"}
    assert m["cell_updates_per_s"]["value"] > 0
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_traced_run_reports_the_layer_metrics():
    line = tiny_cells.run(CELL, trace=1)
    assert line["correct"], line["checks"]
    assert "device_idle.gasket" in line["metrics"]
    assert "setup_s" not in line["metrics"]
    assert line["device"]["busy_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_is_not_correct():
    assert not tiny_cells.run(CELL, control=1)["correct"]


def _unchanged(orig):
    return lambda state, stale, steps, **kw: state


def _altered(orig):
    return lambda state, stale, steps, **kw: orig(
        state, stale, steps, **kw) * 1.001


def _half(orig):
    def call(state, stale, steps, **kw):
        keep = state[:state.shape[0] // 2]
        return orig(state, stale, steps, **kw).at[:keep.shape[0]].set(keep)
    return call


def _one_block(orig):
    """One packed block off by a little: the last, at the deepest level
    of the lambda map."""
    def call(state, stale, steps, **kw):
        b = kw["block"]
        return orig(state, stale, steps, **kw).at[-b:, -b:].add(1e-3)
    return call


@pytest.mark.parametrize("fault", [_unchanged, _altered, _half, _one_block])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.kernels import ops
    monkeypatch.setattr(ops, "ca_run", fault(ops.ca_run))
    line = tiny_cells.run(CELL)
    assert not line["correct"]
    assert line["checks"]["ca_max_abs_err"]["value"] > 0
