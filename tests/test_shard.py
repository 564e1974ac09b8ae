"""Mesh-aware block-space execution (ShardedPlan) tests.

Multi-device behaviour runs in subprocesses with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag must be
set before jax initializes), following test_distributed.py.  Covered:

  * sharded ``ca_run`` is bit-identical to the single-device run per
    lowering x storage x fuse/coarsen x rule, on even and uneven
    domain/device splits (including devices that own nothing);
  * halo correctness: an impulse whose stencil footprint crosses slab
    boundaries propagates identically;
  * ``sierpinski_write``/``sum`` shard with psum combines; flash
    attention shards its query-block axis bit-identically;
  * per-device compact storage is O(n^H / D) + halo (host geometry);
  * TuneCache merge-on-save under concurrent writers + corrupt-file
    recovery; device-count-qualified cache keys;
  * BENCH artifact run metadata.
"""
import json
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


_PRELUDE = """
    import jax, numpy as np, jax.numpy as jnp
    from repro.core import fractal as F
    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    from repro.kernels import ops

    def fractal_state(n, binary):
        mask = F.membership_grid(n)
        rng = np.random.default_rng(0)
        vals = rng.integers(0, 2, (n, n)) if binary else \\
            rng.normal(size=(n, n))
        return jnp.asarray(np.where(mask, vals, 0).astype(np.float32))
"""


# ---------------------------------------------------------------------------
# sharded ca_run bit-identity (the acceptance criterion)
# ---------------------------------------------------------------------------

def test_sharded_ca_bit_identical_all_lowerings_and_storages():
    # n=32, block=8 -> 4x4 block grid, r=2, 3x3 orthotope: D=2 is an
    # uneven slot-row split (2+1 rows), D=3 exact, D=4 leaves device 3
    # with no rows at all.
    out = run_sub(_PRELUDE + """
    n, block, steps = 32, 8, 5
    lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                            n // block))
    checked = refused = 0
    for D in (2, 3, 4):
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((D,), ("data",))
        for gm in ("closed_form", "prefetch_lut", "bounding", "mma"):
            for storage in ("embedded", "compact"):
                for rule, fuse, coarsen in (("parity", 3, 1),
                                            ("parity", 1, 2),
                                            ("diffusion", 2, 1)):
                    a = fractal_state(n, rule == "parity")
                    b = jnp.zeros_like(a)
                    if storage == "compact":
                        a, b = lay.pack(a, block), lay.pack(b, block)
                    kw = dict(fuse=fuse, rule=rule, block=block,
                              grid_mode=gm, storage=storage, n=n,
                              coarsen=coarsen, donate=False)
                    want = ops.ca_run(a, b, steps, **kw)
                    if (gm, storage) == ("bounding", "compact"):
                        # skipped steps would write back over owned slots
                        try:
                            ops.ca_run(a, b, steps, mesh=mesh, **kw)
                            raise SystemExit("expected ValueError")
                        except ValueError as e:
                            assert "cannot be sharded" in str(e)
                        refused += 1
                        continue
                    got = ops.ca_run(a, b, steps, mesh=mesh, **kw)
                    assert np.array_equal(np.asarray(got),
                                          np.asarray(want)), \\
                        (D, gm, storage, rule, fuse, coarsen)
                    checked += 1
    print("OK", checked, refused)
    """)
    assert "OK 63 9" in out


def test_sharded_ca_larger_domain_uneven_rows():
    # n=64 -> r=3, 9x3 orthotope (9 slot rows): D=2 -> 5+4 rows, D=8
    # -> 8x1 rows with one device idle in the 2-row padding.
    out = run_sub(_PRELUDE + """
    n, block, steps = 64, 8, 6
    lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                            n // block))
    a = fractal_state(n, True); b = jnp.zeros_like(a)
    ap, bp = lay.pack(a, block), lay.pack(b, block)
    for D in (2, 8):
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((D,), ("data",))
        for fuse, coarsen in ((4, 1), (2, 2)):
            kw = dict(fuse=fuse, rule="parity", block=block,
                      grid_mode="closed_form", storage="compact", n=n,
                      coarsen=coarsen, donate=False)
            want = ops.ca_run(ap, bp, steps, **kw)
            got = ops.ca_run(ap, bp, steps, mesh=mesh, **kw)
            assert np.array_equal(np.asarray(got), np.asarray(want)), \\
                (D, fuse, coarsen)
    print("OK")
    """)
    assert "OK" in out


def test_sharded_ca_generalized_fractal():
    out = run_sub(_PRELUDE + """
    n, block, steps = 27, 3, 4
    lay = CompactLayout(make_fractal_domain("sierpinski-carpet",
                                            n // block))
    dom = make_fractal_domain("sierpinski-carpet", n)
    y, x = np.mgrid[0:n, 0:n]
    mask = np.asarray(dom.cell_member(x, y, n))
    rng = np.random.default_rng(1)
    a = jnp.asarray(np.where(mask, rng.integers(0, 2, (n, n)), 0)
                    .astype(np.float32))
    b = jnp.zeros_like(a)
    ap, bp = lay.pack(a, block), lay.pack(b, block)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((3,), ("data",))
    for gm in ("closed_form", "prefetch_lut"):
        for storage, (x0, y0) in (("embedded", (a, b)),
                                  ("compact", (ap, bp))):
            kw = dict(fuse=2, rule="parity", block=block, grid_mode=gm,
                      fractal="sierpinski-carpet", storage=storage,
                      n=n, donate=False)
            want = ops.ca_run(x0, y0, steps, **kw)
            got = ops.ca_run(x0, y0, steps, mesh=mesh, **kw)
            assert np.array_equal(np.asarray(got), np.asarray(want)), \\
                (gm, storage)
    print("OK")
    """)
    assert "OK" in out


def test_halo_impulse_crosses_shard_boundary():
    # a single live cell seeded at every slab-boundary block in turn
    # must spread identically to the single-device run: the fused
    # kernel's whole footprint comes through the ghost exchange.
    out = run_sub(_PRELUDE + """
    from repro.core.shard import ShardedPlan
    n, block, steps = 32, 8, 6
    dom = make_fractal_domain("sierpinski-gasket", n // block)
    lay = CompactLayout(dom)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,), ("data",))
    plan = ShardedPlan(dom, "closed_form", storage="compact",
                       mesh=mesh, axis="data", halo=True)
    coords = dom.coords_host()
    rows = lay.slots_host()[:, 1]
    # blocks whose slot row is the last row of slab 0 / first of slab 1
    edge = coords[(rows == plan.rpd - 1) | (rows == plan.rpd)]
    mask = F.membership_grid(n)
    for bx, by in edge:
        s = np.zeros((n, n), np.float32)
        s[by * block, bx * block] = 1.0
        a = jnp.asarray(s * mask); b = jnp.zeros_like(a)
        ap, bp = lay.pack(a, block), lay.pack(b, block)
        kw = dict(fuse=3, rule="parity", block=block,
                  grid_mode="closed_form", storage="compact", n=n,
                  donate=False)
        want = ops.ca_run(ap, bp, steps, **kw)
        got = ops.ca_run(ap, bp, steps, mesh=mesh, **kw)
        assert np.array_equal(np.asarray(got), np.asarray(want)), \\
            (int(bx), int(by))
    print("OK", len(edge))
    """)
    assert "OK" in out


# ---------------------------------------------------------------------------
# write / sum / flash
# ---------------------------------------------------------------------------

def test_sharded_write_and_sum():
    out = run_sub(_PRELUDE + """
    n, block = 32, 8
    lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                            n // block))
    m = fractal_state(n, False)
    mp = lay.pack(m, block)
    for D in (2, 3):
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((D,), ("data",))
        for gm in ("closed_form", "prefetch_lut", "bounding"):
            for storage, arr in (("embedded", m), ("compact", mp)):
                for coarsen in (1, 2):
                    kw = dict(block=block, grid_mode=gm,
                              storage=storage, n=n, coarsen=coarsen)
                    want = ops.sierpinski_write(arr, 7.0, **kw)
                    if (gm, storage) == ("bounding", "compact"):
                        try:
                            ops.sierpinski_write(arr, 7.0, mesh=mesh, **kw)
                            raise SystemExit("expected ValueError")
                        except ValueError as e:
                            assert "cannot be sharded" in str(e)
                    else:
                        got = ops.sierpinski_write(arr, 7.0, mesh=mesh,
                                                   **kw)
                        assert np.array_equal(np.asarray(got),
                                              np.asarray(want)), \\
                            ("write", D, gm, storage, coarsen)
                    sw = float(ops.sierpinski_sum(arr, **kw))
                    sg = float(ops.sierpinski_sum(arr, mesh=mesh, **kw))
                    np.testing.assert_allclose(sg, sw, rtol=1e-5)
    print("OK")
    """)
    assert "OK" in out


def test_sharded_flash_attention_query_axis():
    out = run_sub(_PRELUDE + """
    rng = np.random.default_rng(0)
    b, h, d = 1, 2, 16
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",))
    for kind, sq, sk, window in (("causal", 128, 128, 0),
                                 ("local", 128, 128, 32),
                                 ("local", 64, 128, 32),
                                 ("full", 128, 128, 0)):
        q = jnp.asarray(rng.normal(size=(b, h, sq, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, h, sk, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, h, sk, d)), jnp.float32)
        for gm in ("closed_form", "prefetch_lut", "bounding"):
            kw = dict(kind=kind, window=window, block_q=16, block_k=16,
                      grid_mode=gm)
            want = ops.flash_attention(q, k, v, **kw)
            got = ops.flash_attention(q, k, v, mesh=mesh, **kw)
            assert np.array_equal(np.asarray(got), np.asarray(want)), \\
                (kind, sq, sk, gm)
    # indivisible query-block grids are rejected with a clear error
    q = jnp.zeros((1, 1, 48, 8), jnp.float32)
    try:
        ops.flash_attention(q, q, q, kind="causal", block_q=16,
                            block_k=16, mesh=make_mesh((8,),
                                                       ("data",)))
        raise SystemExit("expected ValueError")
    except ValueError as e:
        assert "divisible" in str(e)
    print("OK")
    """, devices=8)
    assert "OK" in out


# ---------------------------------------------------------------------------
# host geometry: partition + halo plan invariants (no devices needed)
# ---------------------------------------------------------------------------

def _fake_mesh(D):
    """A mesh-shaped stand-in for host-geometry tests: ShardedPlan's
    partition/halo math only reads ``mesh.shape[axis]``, so geometry is
    testable without D real devices."""
    import jax
    if jax.device_count() >= D:
        from repro.launch.mesh import make_mesh
        return make_mesh((D,), ("data",))
    import types
    return types.SimpleNamespace(shape={"data": D})


@pytest.mark.parametrize("n,block,D", [(32, 8, 2), (64, 8, 3),
                                       (64, 8, 5)])
def test_storage_row_partition_covers_domain_once(n, block, D):
    from repro.core.domain import make_fractal_domain
    from repro.core.shard import ShardedPlan
    dom = make_fractal_domain("sierpinski-gasket", n // block)
    plan = ShardedPlan(dom, "closed_form", storage="compact",
                       mesh=_fake_mesh(D), axis="data", halo=True)
    # every slot row owned by exactly one device; counts sum to N
    assert plan.rpd * D >= plan.nrows
    assert int(plan._count.sum()) == dom.num_blocks
    # per-device compact memory is O(n^H / D) + halo: slab rows are the
    # ceil-split of the orthotope and ghosts never exceed the orthotope
    cells = plan.local_storage_shape(block)
    slab_cells = cells[0] * cells[1]
    per_dev = -(-dom.num_blocks // D) * block * block
    assert slab_cells <= per_dev + plan.ncols * block * block  # +pad row
    assert plan.halo.h_max <= plan.nrows
    # the closed-form slot-row decode enumerates exactly the member set
    got = set()
    for d in range(D):
        lo, c = d * plan.rpd, int(plan._count[d])
        for t in range(c):
            col, row = t % plan.ncols, lo + t // plan.ncols
            bx, by = plan._storage_coords(col, row)
            got.add((int(bx), int(by)))
    want = {(int(x), int(y)) for x, y in dom.coords_host()}
    assert got == want


def test_halo_plan_resolves_every_remote_neighbor():
    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    from repro.core.shard import ShardedPlan
    dom = make_fractal_domain("sierpinski-gasket", 8)
    lay = CompactLayout(dom)
    for D in (2, 3, 4):
        plan = ShardedPlan(dom, "closed_form", storage="compact",
                           mesh=_fake_mesh(D), axis="data", halo=True)
        halo = plan.halo
        rows = lay.slots_host()[:, 1]
        nbrs = lay.neighbor_slots_host()
        for d in range(D):
            lo, hi = d * plan.rpd, min((d + 1) * plan.rpd, plan.nrows)
            sel = (rows >= lo) & (rows < hi)
            need = np.unique(nbrs[sel][..., 1][nbrs[sel][..., 2] == 1])
            for g in need:
                # owned locally or mapped into the ghost region
                m = halo.ghost_map[d, g]
                if lo <= g < hi:
                    assert m == g - lo
                else:
                    assert plan.rpd <= m < plan.rpd + halo.h_max
        # every (ghost row, strip class) is delivered by exactly one
        # ppermute round, from its owner's matching send slot
        delivered = {d: set() for d in range(D)}
        for delta, cls, send, recv, scol, rcol, wc in halo.rounds:
            assert 0 < wc <= plan.ncols
            for d in range(D):
                src = (d - delta) % D
                needs = [g for g in halo.ghost_rows[d]
                         if g // plan.rpd == src
                         and cls in halo.row_class[d][g]]
                for i, g in enumerate(needs):
                    assert send[src][i] == g - src * plan.rpd
                    assert recv[d][i] == halo.ghost_rows[d].index(g)
                    # the shipped column window covers the readers'
                    # span and stays in range, gathered and scattered
                    # at the same clamped start
                    lo_c, hi_c = halo.col_span[d][(g, cls)]
                    c0 = int(rcol[d][i])
                    assert scol[src][i] == c0
                    assert 0 <= c0 <= lo_c and hi_c <= c0 + wc
                    assert c0 + wc <= plan.ncols
                    delivered[d].add((g, cls))
        for d in range(D):
            want = {(g, c) for g in halo.ghost_rows[d]
                    for c in halo.row_class[d][g]}
            assert delivered[d] == want
            # a full-row ship never coexists with a strip ship
            for g in halo.ghost_rows[d]:
                s = halo.row_class[d][g]
                assert s == {"full"} or "full" not in s


def test_sharded_plan_validation():
    from repro.core.domain import TriangularDomain, make_fractal_domain
    from repro.core.shard import ShardedPlan
    dom = make_fractal_domain("sierpinski-gasket", 4)
    mesh = _fake_mesh(2)
    with pytest.raises(ValueError, match="partition"):
        ShardedPlan(dom, mesh=mesh, axis="data", partition="bogus")
    with pytest.raises(ValueError, match="storage-rows"):
        ShardedPlan(dom, mesh=mesh, axis="data",
                    partition="storage-rows")
    with pytest.raises(ValueError, match="packed rows"):
        ShardedPlan(dom, storage="compact", mesh=mesh, axis="data",
                    partition="linear")
    # 'rows' needs a row-major enumeration: fractals are not
    with pytest.raises(ValueError, match="row-major"):
        ShardedPlan(dom, mesh=mesh, axis="data", partition="rows")
    ShardedPlan(TriangularDomain(8), mesh=mesh, axis="data",
                partition="rows")  # triangular is


# ---------------------------------------------------------------------------
# tune cache satellites: merge-on-save + device-qualified keys
# ---------------------------------------------------------------------------

def test_tune_cache_merge_on_save(tmp_path):
    from repro.core import tune
    path = str(tmp_path / "tune.json")
    a = tune.TuneCache(path)
    b = tune.TuneCache(path)
    # interleaved writers: the second save must not clobber the first
    a.put("ca", {"n": 1, "backend": "cpu"}, {"fuse": 2}, 1.0)
    b.put("ca", {"n": 2, "backend": "cpu"}, {"fuse": 4}, 2.0)
    fresh = tune.TuneCache(path)
    assert fresh.get("ca", {"n": 1, "backend": "cpu"}) == {"fuse": 2}
    assert fresh.get("ca", {"n": 2, "backend": "cpu"}) == {"fuse": 4}
    # in-memory entries win over disk on key conflict
    c = tune.TuneCache(path)
    c.put("ca", {"n": 1, "backend": "cpu"}, {"fuse": 8}, 0.5)
    assert tune.TuneCache(path).get(
        "ca", {"n": 1, "backend": "cpu"}) == {"fuse": 8}
    assert len(tune.TuneCache(path)) == 2


def test_tune_cache_recovers_from_corrupt_partial_write(tmp_path):
    from repro.core import tune
    path = tmp_path / "tune.json"
    good = tune.TuneCache(str(path))
    good.put("ca", {"n": 1, "backend": "cpu"}, {"fuse": 2}, 1.0)
    # simulate a torn write: truncate the file mid-JSON
    txt = path.read_text()
    path.write_text(txt[:len(txt) // 2])
    # a new writer must both read (as empty) and save over it cleanly
    c = tune.TuneCache(str(path))
    assert c.get("ca", {"n": 1, "backend": "cpu"}) is None
    c.put("ca", {"n": 3, "backend": "cpu"}, {"fuse": 1}, 3.0)
    fresh = tune.TuneCache(str(path))
    assert fresh.get("ca", {"n": 3, "backend": "cpu"}) == {"fuse": 1}
    assert json.loads(path.read_text())  # valid JSON again


def test_tune_keys_qualified_by_shard_count():
    # a sharded run consults the shard-count-qualified key (the mesh
    # axis size, NOT the process device count); unsharded runs keep the
    # unqualified key, so single-chip winners never answer for sharded
    # runs and different shard counts never collide.
    out = run_sub("""
    import os, tempfile
    os.environ["REPRO_TUNE_CACHE"] = os.path.join(
        tempfile.mkdtemp(), "tune.json")
    import jax
    from repro.core import tune
    from repro.kernels import sierpinski_ca as ca

    base = {"fractal": "sierpinski-gasket", "n": 32, "block": 8,
            "rule": "parity"}
    assert tune.shard_params(base, None, "data") == base
    from repro.launch.mesh import make_mesh
    mesh2 = make_mesh((2,), ("data",))
    assert tune.shard_params(base, mesh2, "data")["devices"] == 2
    # behavioral: auto resolves per key
    cache = tune.default_cache()
    cache.put("ca", tune._with_backend(dict(base)),
              {"lowering": "bounding", "fuse": 1, "coarsen": 1}, 1.0,
              save=False)
    cache.put("ca", tune._with_backend({**base, "devices": 2}),
              {"lowering": "prefetch_lut", "fuse": 4, "coarsen": 1},
              1.0, save=False)
    assert ca.auto_schedule(n=32, block=8)[0] == "bounding"
    assert ca.auto_schedule(n=32, block=8, mesh=mesh2) == \\
        ("prefetch_lut", 4, 1, 1)
    from repro.launch.mesh import make_mesh
    mesh4 = make_mesh((4,), ("data",))  # untuned D: defaults
    assert ca.auto_schedule(n=32, block=8, mesh=mesh4) == \\
        ("closed_form", 1, 1, 1)
    print("OK")
    """, devices=8)
    assert "OK" in out


# ---------------------------------------------------------------------------
# benchmark artifact metadata
# ---------------------------------------------------------------------------

def test_bench_artifact_carries_run_metadata(tmp_path):
    from benchmarks import common
    meta = common.run_metadata()
    for key in ("jax", "backend", "device_count", "platform", "python",
                "recorded_at"):
        assert key in meta, key
    old = list(common.RESULTS)
    try:
        common.RESULTS[:] = []
        common.row("x/y", 1.23, "a=1")
        path = tmp_path / "bench.json"
        common.dump_json(str(path))
        rec = json.loads(path.read_text())
        assert rec["meta"]["device_count"] >= 1
        assert rec["rows"] == [{"name": "x/y", "us_per_call": 1.23,
                                "derived": "a=1"}]
    finally:
        common.RESULTS[:] = old
