"""Tier-1: the static plan verifier and interpret-mode access sanitizer.

Three layers:

* clean-matrix: every plan the smoke matrix emits verifies with zero
  findings (no false positives);
* mutation: seed one fault of each class the verifier claims to catch
  -- a corrupted LUT row, a mis-wired neighbour slot, a shifted or
  colliding storage index map, a dropped/duplicated grid step, an
  unsafe in-place alias declaration, a corrupted ghost-map entry --
  and assert the matching check flags it (no false negatives);
* sanitizer: real kernel launches under the interpreter, synchronous
  and DMA-pipelined, traced index maps cross-checked against their
  static evaluation.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.analysis import (PlanVerificationError, verify_launches,
                            verify_or_raise, verify_plan)
from repro.analysis.verifier import ACCESS_MODELS, HostMesh
from repro.core.domain import SierpinskiDomain, make_fractal_domain
from repro.core.plan import _LUT_NBR, GridPlan
from repro.core.shard import SHARD_GMAP, ShardedPlan

DOM = SierpinskiDomain(8)          # 27 member blocks: fast to enumerate
N = DOM.num_blocks


def _plan(lowering="prefetch_lut", storage="embedded", **kw):
    return GridPlan(SierpinskiDomain(8), lowering, storage=storage, **kw)


def _sharded(d=2, halo=True, lowering="closed_form"):
    return ShardedPlan(SierpinskiDomain(8), lowering, storage="compact",
                       mesh=HostMesh(d), axis="data",
                       partition="storage-rows", halo=halo)


# ---------------------------------------------------------------------------
# clean matrix: no false positives
# ---------------------------------------------------------------------------

def test_smoke_matrix_is_clean():
    from repro.analysis.verify import matrix_plans
    for label, plan, kernel in matrix_plans(smoke=True):
        report = verify_plan(plan, kernel=kernel)
        assert report.ok, f"{label}: {[str(f) for f in report.findings]}"


def test_report_json_roundtrip():
    report = verify_plan(_plan(), kernel="write")
    blob = json.loads(json.dumps(report.to_json()))
    assert blob["ok"] and blob["findings"] == []
    assert set(blob["checks"]) == {"coverage", "race", "table", "bounds",
                                   "alias", "hull"}


def test_verify_or_raise_is_value_error():
    plan = _plan()
    lut = np.array(plan.lut_host())
    lut[0, 0] += 1
    plan.lut_host = lambda: lut
    with pytest.raises(PlanVerificationError) as ei:
        verify_or_raise(plan, kernel="write")
    assert isinstance(ei.value, ValueError)   # the autotune skip path
    assert "table" in str(ei.value)


# ---------------------------------------------------------------------------
# mutation: seeded faults are flagged by the matching check
# ---------------------------------------------------------------------------

def _checks(plan, kernel="write"):
    return {f.check for f in verify_plan(plan, kernel=kernel).findings}


def _corrupt_lut_row(row):
    plan = _plan("prefetch_lut", "embedded")
    lut = np.array(plan.lut_host())
    lut[row, 0] ^= 1                      # flip one decoded coordinate
    plan.lut_host = lambda: lut
    return plan


def test_corrupt_lut_row_flagged():
    assert "table" in _checks(_corrupt_lut_row(0))
    assert "table" in _checks(_corrupt_lut_row(N - 1))


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=N - 1))
def test_corrupt_lut_row_flagged_any_row(row):
    assert "table" in _checks(_corrupt_lut_row(row))


def _corrupt_neighbor_slot(row, offset):
    plan = _plan("prefetch_lut", "compact")
    lut = np.array(plan.lut_host())
    base = _LUT_NBR + 3 * offset
    if lut[row, base + 2] == 1:
        # valid neighbour: point its slot somewhere else entirely
        lut[row, base] = (lut[row, base] + 1) % plan.layout.grid_shape[0]
    else:
        lut[row, base + 2] = 1            # claim validity membership denies
    plan.lut_host = lambda: lut
    return plan


def test_corrupt_neighbor_slot_flagged():
    assert "table" in _checks(_corrupt_neighbor_slot(0, 0), kernel="ca")
    assert "table" in _checks(_corrupt_neighbor_slot(N - 1, 7),
                              kernel="ca")


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=N - 1),
       st.integers(min_value=0, max_value=7))
def test_corrupt_neighbor_slot_flagged_any(row, offset):
    assert "table" in _checks(_corrupt_neighbor_slot(row, offset),
                              kernel="ca")


def test_shifted_storage_index_flagged_as_bounds():
    plan = _plan("closed_form", "compact")
    orig = plan.storage_index

    def shifted(ids, refs=()):
        r, c = orig(ids, refs)
        return r + 100, c                 # hull leaves the tile grid
    plan.storage_index = shifted
    assert "bounds" in _checks(plan)


def test_colliding_storage_index_flagged_as_race():
    plan = _plan("closed_form", "compact")
    orig = plan.storage_index

    def collapsed(ids, refs=()):
        r, c = orig(ids, refs)
        return np.zeros_like(np.asarray(r)), np.zeros_like(np.asarray(c))
    plan.storage_index = collapsed
    assert "race" in _checks(plan)


def test_dropped_step_flagged_as_coverage():
    plan = _plan("closed_form", "embedded")
    orig = plan._step_valid

    def drop_first(ids, bx, by, refs=()):
        v = orig(ids, bx, by, refs)
        v = np.ones(np.asarray(ids[-1]).shape, bool) if v is None \
            else np.array(np.broadcast_to(np.asarray(v),
                                          np.asarray(ids[-1]).shape))
        live = np.nonzero(v.ravel())[0]
        v.ravel()[live[0]] = False        # one member block goes dark
        return v
    plan._step_valid = drop_first
    findings = verify_plan(plan, kernel="write").findings
    assert any(f.check == "coverage" and "never covered" in f.detail
               for f in findings)


def test_duplicated_decode_flagged_as_coverage():
    plan = _plan("closed_form", "embedded")
    orig = plan._decode

    def duped(ids, refs=()):
        batch, bx, by = orig(ids, refs)
        bx = np.array(np.broadcast_to(np.asarray(bx),
                                      np.asarray(ids[-1]).shape))
        by = np.array(np.broadcast_to(np.asarray(by),
                                      np.asarray(ids[-1]).shape))
        bx.ravel()[1] = bx.ravel()[0]     # two steps decode one block
        by.ravel()[1] = by.ravel()[0]
        return batch, bx, by
    plan._decode = duped
    assert "coverage" in _checks(plan)


def test_inplace_alias_on_stencil_flagged():
    """The 'corrupted alias entry' fault: a kernel that declares its
    stencil input donated/aliased in place.  Reading neighbour tiles
    that other steps write is a RAW hazard within the launch."""
    ACCESS_MODELS["_test_inplace_stencil"] = {
        "race": True, "neighbors": True, "storage": True,
        "alias_reads": ("center+neighbors",)}
    try:
        plan = _plan("closed_form", "compact")
        assert "alias" in _checks(plan, kernel="_test_inplace_stencil")
        # the safe declaration of the same plan stays clean
        assert _checks(plan, kernel="ca") == set()
    finally:
        del ACCESS_MODELS["_test_inplace_stencil"]


def test_corrupt_ghost_map_flagged():
    plan = _sharded(d=2, halo=True)
    tbl = np.array(plan.shard_table_host())
    gmap = tbl[0, SHARD_GMAP:]
    ghost = np.nonzero(gmap >= plan.rpd)[0]     # a ghost/dump slot
    gmap[ghost[0]] = 0                          # alias it onto row 0
    plan.shard_table_host = lambda: tbl
    assert "table" in _checks(plan)


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=1, max_value=3),
       st.integers(min_value=0, max_value=10 ** 6))
def test_corrupt_ghost_map_flagged_any(d, seed):
    plan = _sharded(d=d, halo=True)
    tbl = np.array(plan.shard_table_host())
    dev = seed % d
    gmap = tbl[dev, SHARD_GMAP:]
    i = seed % len(gmap)
    gmap[i] = gmap[i] + 1                       # any off-by-one slot
    plan.shard_table_host = lambda: tbl
    assert "table" in _checks(plan)


def test_sharded_plans_clean_and_phase_views_checked():
    for d in (1, 2, 3):
        for halo in (True, False):
            report = verify_plan(_sharded(d=d, halo=halo), kernel="ca")
            assert report.ok, [str(f) for f in report.findings]


def test_corrupt_mma_basis_flagged(monkeypatch):
    """A corrupted digit-basis matrix must not survive verification:
    the mma decode table is re-derived from the integer ground truth,
    so a mis-weighted digit shows up as a table finding."""
    from repro.core import memo, mma
    orig = mma.coords_basis

    def corrupted(spec, r):
        b = np.array(orig(spec, r))
        b[0, 1, 0] += 1.0            # mis-weight digit 1 at level 1
        return b

    memo.clear()                     # drop any clean cached tables
    monkeypatch.setattr(mma, "coords_basis", corrupted)
    try:
        plan = _plan("mma", "embedded", backend="tpu-interpret")
        assert "table" in _checks(plan)
    finally:
        memo.clear()                 # drop the corrupted tables too


def test_corrupt_flash_hull_flagged():
    from repro.core.domain import TriangularDomain
    plan = GridPlan(TriangularDomain(8), "prefetch_lut")
    ext = np.array(plan.row_extents())
    ext[0, 1] += 1                   # widen row 0 past its membership
    plan.row_extents = lambda: ext
    assert "hull" in _checks(plan, kernel="flash")


# ---------------------------------------------------------------------------
# the kernels' verify= debug flag and the autotune rejection path
# ---------------------------------------------------------------------------

def test_kernel_verify_flag():
    from repro.kernels.sierpinski_write import sierpinski_write
    dom = make_fractal_domain("sierpinski-gasket", 8)
    m = jnp.zeros((24, 24), jnp.float32)
    verified = sierpinski_write(m, 1.0, block=3, domain=dom,
                                num_stages=1, interpret=True, verify=True)
    plain = sierpinski_write(m, 1.0, block=3, domain=dom,
                             num_stages=1, interpret=True)
    # the flag verifies, it must never change what the kernel computes
    np.testing.assert_array_equal(np.asarray(verified), np.asarray(plain))
    assert float(verified.sum()) > 0


def test_autotune_rejects_failing_candidates(tmp_path):
    from repro.core.tune import TuneCache, autotune
    measured = []

    def build(cfg):
        def fn():
            measured.append(cfg["x"])
        return fn

    def vfy(cfg):
        if cfg["x"] == "bad":
            raise PlanVerificationError("seeded verification failure")

    cfg, us, trials = autotune(
        "_test", {"p": 1}, [{"x": "bad"}, {"x": "good"}], build,
        cache=TuneCache(str(tmp_path / "t.json")), verify=vfy)
    assert cfg == {"x": "good"}
    assert all(t[0] == {"x": "good"} for t in trials)
    assert "bad" not in measured              # rejected before measuring


def test_autotune_all_rejected_raises(tmp_path):
    from repro.core.tune import TuneCache, autotune

    def vfy(cfg):
        raise PlanVerificationError("seeded")

    with pytest.raises(ValueError, match="no viable candidate"):
        autotune("_test", {"p": 1}, [{"x": 1}], lambda cfg: (lambda: None),
                 cache=TuneCache(str(tmp_path / "t.json")), verify=vfy)


# ---------------------------------------------------------------------------
# interpret-mode access sanitizer
# ---------------------------------------------------------------------------

#: the synchronous launch, and the DMA-pipelined one (the state parked
#: in ``pl.ANY``; only the aliased and output tiles ride BlockSpecs)
_STAGES = [pytest.param(1, id="tpu-interpret"),
           pytest.param(2, id="tpu-interpret-dma")]


@pytest.mark.parametrize("num_stages", _STAGES)
@pytest.mark.parametrize("storage", ["embedded", "compact"])
def test_sanitizer_write_clean(num_stages, storage):
    from repro.core.compact import compact_layout
    from repro.kernels.sierpinski_write import sierpinski_write
    dom = make_fractal_domain("sierpinski-gasket", 8)
    m = jnp.zeros((24, 24), jnp.float32) if storage == "embedded" else \
        jnp.zeros(compact_layout(dom).array_shape(3), jnp.float32)
    out, findings = verify_launches(
        sierpinski_write, m, 1.0, block=3, grid_mode="closed_form",
        storage=storage, domain=dom, num_stages=num_stages,
        backend="tpu-interpret", strict=True)
    assert findings == []
    assert float(out.sum()) > 0


@pytest.mark.parametrize("num_stages", _STAGES)
def test_sanitizer_ca_clean(num_stages):
    from repro.core.compact import compact_layout
    from repro.kernels.sierpinski_ca import ca_run
    dom = make_fractal_domain("sierpinski-gasket", 8)
    state = jnp.zeros(compact_layout(dom).array_shape(3), jnp.float32)
    _, findings = verify_launches(
        ca_run, state, jnp.zeros_like(state), 2, fuse=1, block=3,
        grid_mode="closed_form", storage="compact", domain=dom,
        num_stages=num_stages, backend="tpu-interpret", strict=True)
    assert findings == []


# ---------------------------------------------------------------------------
# CLI + benchmark harness satellites
# ---------------------------------------------------------------------------

def test_verify_cli_static_smoke(tmp_path):
    from repro.analysis.verify import main
    out = tmp_path / "report.json"
    rc = main(["--matrix", "--smoke", "--no-sanitize", "--quiet",
               "--out", str(out)])
    assert rc == 0
    blob = json.loads(out.read_text())
    assert blob["ok"] and blob["num_findings"] == 0
    assert blob["num_static"] == len(blob["static"]) > 0


def test_bench_only_rejects_unknown_suite(capsys):
    from benchmarks.run import main
    with pytest.raises(SystemExit):
        main(["--only", "bogus", "--no-json"])
    err = capsys.readouterr().err
    assert "unknown suite" in err and "bogus" in err
    assert "map" in err and "attn" in err     # lists what is available


def test_bench_metadata_stamps_git():
    from benchmarks.common import git_revision
    rev = git_revision()
    if not rev:
        pytest.skip("git unavailable")
    assert len(rev["commit"]) == 40
    assert isinstance(rev["dirty"], bool)
