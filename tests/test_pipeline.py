"""Pipelined (``num_stages >= 2``) vs synchronous bit-identity.

The software pipeline must be a pure scheduling change: every kernel x
storage x lowering x fuse x stages point returns the same bits as the
synchronous path under the interpreter, and the sharded overlap
(interior compute concurrent with the halo exchange, boundary steps
after) must propagate a slab-crossing impulse identically.  Covered:

  * backend plumbing: the ``pipeline_stages`` depth and the
    ``resolve_stages`` clamp;
  * the first-iteration LUT-stall fix: ``_lut_row0`` is a host constant
    equal to LUT row 0 on single-device plans, and None on sharded
    plans (per-device chunks are shard_map operands);
  * write/sum DMA streaming and ca fused DMA bit-identity matrices;
  * host geometry of the overlap machinery: interior/boundary phase
    tables partition each device's owned steps, strip halo rounds never
    mix with full-row rounds for the same ghost row, and the trimmed
    byte count never exceeds the full-row baseline;
  * an impulse seeded against a slab boundary propagates identically
    under stages=2 overlap on forced 8-device meshes (subprocess).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=1200)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def _fake_mesh(D):
    """Host-geometry stand-in: ShardedPlan's partition/halo/phase math
    only reads ``mesh.shape[axis]``."""
    import jax
    if jax.device_count() >= D:
        from repro.launch.mesh import make_mesh
        return make_mesh((D,), ("data",))
    import types
    return types.SimpleNamespace(shape={"data": D})


def _state(n, binary=True):
    from repro.core import fractal as F
    import jax.numpy as jnp
    mask = F.membership_grid(n)
    rng = np.random.default_rng(0)
    vals = rng.integers(0, 2, (n, n)) if binary else \
        rng.normal(size=(n, n))
    return jnp.asarray(np.where(mask, vals, 0).astype(np.float32))


def _packed(n, block, a=None):
    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    import jax.numpy as jnp
    lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                            n // block))
    if a is None:
        a = jnp.zeros((n, n), jnp.float32)
    return lay.pack(a, block)


# ---------------------------------------------------------------------------
# capability plumbing
# ---------------------------------------------------------------------------

def test_backend_stage_capabilities_and_clamp():
    from repro.core import backend
    # the interpreter emulates the chip's DMA double buffer, so both
    # targets stage the same depth
    for t in backend.TARGETS.values():
        assert t.pipeline_stages == 2
        assert t.resolve_stages("auto") == 1
        assert t.resolve_stages(None) == 1      # "auto" -> synchronous
        assert t.resolve_stages(1) == 1
        assert t.resolve_stages(2) == 2
        assert t.resolve_stages(999) == t.pipeline_stages


def test_lut_row0_hoist_is_host_constant():
    from repro.core.domain import make_fractal_domain
    from repro.core.plan import GridPlan
    from repro.core.shard import ShardedPlan
    dom = make_fractal_domain("sierpinski-gasket", 8)
    for storage in ("embedded", "compact"):
        plan = GridPlan(dom, "prefetch_lut", storage=storage)
        row0 = plan._lut_row0()
        assert row0 is not None
        assert np.array_equal(np.asarray(row0),
                              np.asarray(plan.lut_host()[0]))
    sp = ShardedPlan(dom, "prefetch_lut", storage="compact",
                     mesh=_fake_mesh(2), axis="data", halo=True)
    assert sp._lut_row0() is None  # chunks are shard_map operands


# ---------------------------------------------------------------------------
# host geometry: phase tables + strip halo rounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("D", [2, 3, 4, 8])
def test_phase_tables_partition_owned_steps(D):
    from repro.core.domain import make_fractal_domain
    from repro.core.shard import ShardedPlan
    dom = make_fractal_domain("sierpinski-gasket", 8)  # n=64, block=8
    plan = ShardedPlan(dom, "prefetch_lut", storage="compact",
                       mesh=_fake_mesh(D), axis="data", halo=True)
    h = plan.halo
    mi, mb = plan.phase_widths()
    for d in range(D):
        own = set(range(int(plan._count[d])))
        i, b = set(map(int, h.int_steps[d])), set(map(int, h.bnd_steps[d]))
        assert i.isdisjoint(b)
        assert i | b == own  # every owned step in exactly one phase
    tabs = plan.phase_tables_host()
    if mi == 0 or mb == 0:
        assert tabs is None  # degenerate split: overlap has no benefit
        return
    for tbl, lists, m in zip(tabs, (h.int_steps, h.bnd_steps), (mi, mb)):
        assert tbl.shape == (D, 1 + m) and tbl.dtype == np.int32
        for d in range(D):
            c = int(tbl[d, 0])
            assert c == len(lists[d])
            assert list(tbl[d, 1:1 + c]) == list(lists[d])
            assert not tbl[d, 1 + c:].any()  # zero padding
    for which, width in (("interior", mi), ("boundary", mb)):
        pv = plan.phase_view(which)
        assert pv.steps_per_shard == width
        assert pv.num_scalar_prefetch == plan.num_scalar_prefetch + 1


def test_phase_view_rejects_unsupported_plans():
    from repro.core.domain import make_fractal_domain
    from repro.core.shard import ShardedPlan
    dom = make_fractal_domain("sierpinski-gasket", 8)
    bounding = ShardedPlan(dom, "bounding", storage="compact",
                           mesh=_fake_mesh(2), axis="data", halo=True)
    with pytest.raises(ValueError, match="bounding"):
        bounding.phase_view("interior")
    no_halo = ShardedPlan(dom, "closed_form", storage="compact",
                          mesh=_fake_mesh(2), axis="data", halo=False)
    with pytest.raises(ValueError, match="halo"):
        no_halo.phase_view("interior")
    ok = ShardedPlan(dom, "closed_form", storage="compact",
                     mesh=_fake_mesh(2), axis="data", halo=True)
    with pytest.raises(ValueError, match="unknown phase"):
        ok.phase_view("everything")


@pytest.mark.parametrize("D", [2, 4])
def test_halo_strips_trim_bytes_and_never_mix_with_full(D):
    from repro.core.domain import make_fractal_domain
    from repro.core.shard import ShardedPlan
    dom = make_fractal_domain("sierpinski-gasket", 8)
    plan = ShardedPlan(dom, "closed_form", storage="compact",
                       mesh=_fake_mesh(D), axis="data", halo=True)
    h = plan.halo
    assert plan.tile_map() is None  # embedded-ordered tiles -> strips
    for cls_map in h.row_class:
        for classes in cls_map.values():
            assert classes <= {"full", "top", "bot"}
            if "full" in classes:
                assert classes == {"full"}  # full absorbs the strips
    # trimming targets strip heights below the row unit (h = fuse <
    # block in every launch); there it always beats full rows, and
    # shallower fuse ships fewer bytes
    sizes = [h.bytes_exchanged(plan, 8, h=hh)["strips"]
             for hh in (1, 3)]
    full = h.bytes_exchanged(plan, 8, h=1)["full_rows"]
    assert 0 < sizes[0] <= sizes[1] <= full
    # column trimming stacks on top: the occupied window never ships
    # more than the full-width strip
    by = h.bytes_exchanged(plan, 8, h=1)
    assert 0 < by["trimmed"] <= by["strips"]
    # packed supertiles are not embedded-row-ordered: full rows only
    coarse = ShardedPlan(dom, "closed_form", storage="compact",
                         coarsen=2, mesh=_fake_mesh(D), axis="data",
                         halo=True)
    assert coarse.tile_map() is not None
    assert all(cls == "full" for _, cls, *_ in coarse.halo.rounds)
    byc = coarse.halo.bytes_exchanged(coarse, 8)
    assert byc["strips"] == byc["full_rows"]
    assert byc["trimmed"] <= byc["strips"]


# ---------------------------------------------------------------------------
# single-device bit-identity matrices (interpret structures)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("storage", ["embedded", "compact"])
def test_write_sum_dma_bit_identical(storage):
    from repro.kernels.sierpinski_write import (sierpinski_sum,
                                               sierpinski_write)
    n, block = 32, 8
    for gm in ("closed_form", "prefetch_lut", "bounding"):
        for coarsen in (1, 2):
            base = None
            for stages in (1, 2):
                m = _packed(n, block) if storage == "compact" else \
                    _state(n) * 0
                w = sierpinski_write(m, value=3.0, block=block,
                                     grid_mode=gm, storage=storage,
                                     n=n, coarsen=coarsen,
                                     num_stages=stages,
                                     backend="tpu-interpret")
                s = sierpinski_sum(w, block=block, grid_mode=gm,
                                   storage=storage, n=n,
                                   coarsen=coarsen, num_stages=stages,
                                   backend="tpu-interpret")
                out = (np.asarray(w), float(s))
                if base is None:
                    base = out
                else:
                    key = (gm, coarsen, stages)
                    assert np.array_equal(base[0], out[0]), key
                    assert base[1] == out[1], key
            # value lands on exactly the 3^log2(n) gasket cells
            assert base[1] == 3.0 * 3 ** 5


@pytest.mark.parametrize("storage", ["embedded", "compact"])
def test_ca_pipelined_bit_identical(storage):
    import jax.numpy as jnp
    from repro.kernels import ops
    n, block, steps = 32, 8, 5
    a0 = _state(n)
    for gm in ("closed_form", "prefetch_lut", "bounding"):
        for fuse in (1, 3):
            a = _packed(n, block, a0) if storage == "compact" else a0
            b = jnp.zeros_like(a)
            ref = None
            for stages in (1, 2, 4):
                out = np.asarray(ops.ca_run(
                    a, b, steps, fuse=fuse, rule="parity", block=block,
                    grid_mode=gm, storage=storage, n=n,
                    num_stages=stages, backend="tpu-interpret",
                    donate=False))
                if ref is None:
                    ref = out
                    assert ref.any()  # the matrix point is non-trivial
                else:
                    assert np.array_equal(ref, out), (gm, fuse, stages)


# ---------------------------------------------------------------------------
# sharded halo-compute overlap (subprocess, forced 8-device mesh)
# ---------------------------------------------------------------------------

def test_sharded_overlap_impulse_bit_identical():
    # An impulse seeded on the bottom row (dense in the gasket, and on
    # the last device's slab) reaches across every slab boundary within
    # steps x fuse; stages=2 routes boundary steps through the phase
    # tables + ghost strips concurrently with interior compute, and
    # must reproduce the single-device synchronous run exactly.  The
    # bounding lowering exercises the sync fallback under stages=2.
    out = run_sub("""
    import jax, numpy as np, jax.numpy as jnp
    from repro.core import fractal as F
    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    from repro.kernels import ops

    n, block, steps, fuse = 64, 8, 6, 3
    state = np.zeros((n, n), np.float32)
    state[n - 1, 0] = 1.0
    a0 = jnp.asarray(state * F.membership_grid(n))
    lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                            n // block))
    checked = 0
    for D in (2, 8):
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((D,), ("data",))
        for gm in ("closed_form", "prefetch_lut", "bounding"):
            for storage in ("compact", "embedded"):
                a = lay.pack(a0, block) if storage == "compact" else a0
                b = jnp.zeros_like(a)
                kw = dict(fuse=fuse, rule="parity", block=block,
                          grid_mode=gm, storage=storage, n=n,
                          donate=False)
                ref = np.asarray(ops.ca_run(a, b, steps, num_stages=1,
                                            **kw))
                assert ref.any()
                if (gm, storage) == ("bounding", "compact"):
                    # refused sharded: skipped steps would write back
                    # over owned slots
                    try:
                        ops.ca_run(a, b, steps, mesh=mesh, **kw)
                        raise SystemExit("expected ValueError")
                    except ValueError as e:
                        assert "cannot be sharded" in str(e)
                    continue
                for stages in (1, 2):
                    got = np.asarray(ops.ca_run(
                        a, b, steps, mesh=mesh, num_stages=stages,
                        **kw))
                    assert np.array_equal(got, ref), \\
                        (D, gm, storage, stages)
                    checked += 1
    print("OK", checked)
    """)
    assert "OK 20" in out
