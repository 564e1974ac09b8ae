"""Paged block-space KV cache + continuous batching.

Covered:

  * PagedKVPool allocator invariants: reserved null page, lowest-first
    reuse, exhaustion, double-free, fragmentation accounting;
  * layout helpers round-trip (fuse/split, scatter -> gather oracle),
    inactive-slot writes routed to the null page;
  * the acceptance criterion: paged flash decode bit-identical to the
    contiguous seq_pos decode per backend structure x lowering x page
    size, incl. shuffled out-of-order page assignment and local
    windows; slot-sharded paged decode on a fake mesh;
  * per-row seq_pos vector on the contiguous decode path (regression);
  * zig-zag balanced causal sharding bit-identical to unsharded;
  * host page-table verification flags every mutation class;
  * page_size as a persisted autotune knob;
  * the continuous-batching scheduler: mixed-length batches match the
    single-request oracle, preemption is deterministic and leak-free,
    and the paged degradation ladder steps blockspace -> paged-xla.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import paged as P
from repro.models import attention as A

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(11)


def run_sub(code: str, devices: int = 4):
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={devices}")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=1200)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# allocator + layout helpers
# ---------------------------------------------------------------------------

def test_pool_allocator_invariants():
    pool = P.PagedKVPool(num_pages=6, page_size=8)
    assert pool.free_pages == 5            # page 0 is the null page
    a = pool.alloc(2)
    assert a == [1, 2]                     # lowest-first
    b = pool.alloc(3)
    assert b == [3, 4, 5]
    assert pool.alloc(1) is None           # exhausted, not an error
    pool.free(a)
    assert pool.alloc(1) == [1]            # freed pages are reused
    with pytest.raises(ValueError):
        pool.free([2, 2])                  # double free
    pool.free([P.NULL_PAGE])               # null page: silent no-op
    assert P.NULL_PAGE not in pool._free
    s = pool.stats([5])                    # 5 live tokens on 4 pages
    assert s["used_pages"] == 4
    assert 0.0 < s["fragmentation"] < 1.0


def test_pages_for_ceil_div():
    assert [P.pages_for(n, 8) for n in (0, 1, 8, 9, 16)] == [0, 1, 1, 2, 2]


def test_scatter_gather_roundtrip_and_fuse_split():
    hkv, s, d, ps = 2, 20, 8, 8
    k = jnp.asarray(RNG.normal(size=(hkv, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(hkv, s, d)), jnp.float32)
    kk, vv = P.split_kv(P.fuse_kv(k, v))
    assert np.array_equal(kk, k) and np.array_equal(vv, v)
    # scatter into out-of-order pages, gather back through the table
    pages = jnp.asarray([5, 2, 7], jnp.int32)
    pool = P.init_pool(9, hkv, ps, d)
    pool = P.write_prefill_pages(pool, pages, k, v)
    table = jnp.asarray([[5, 2, 7]], jnp.int32)
    gk, gv = P.gather_kv(pool, table)
    assert np.array_equal(gk[0, :, :s], k)
    assert np.array_equal(gv[0, :, :s], v)
    assert not np.asarray(gk[0, :, s:]).any()   # tail stays zero padding


def test_append_token_routes_inactive_to_null_page():
    hkv, d, ps = 2, 4, 8
    pool = P.init_pool(4, hkv, ps, d)
    table = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    pos = jnp.asarray([9, 3], jnp.int32)
    k_new = jnp.ones((2, hkv, 1, d), jnp.float32)
    v_new = 2 * jnp.ones((2, hkv, 1, d), jnp.float32)
    out = P.append_token(pool, table, pos, k_new, v_new,
                         active=jnp.asarray([True, False]))
    assert np.asarray(out[2, :hkv, 9 % ps]).all()      # slot 0 wrote page 2
    assert not np.asarray(out[3]).any()                # inactive: untouched
    assert np.asarray(out[P.NULL_PAGE]).any()          # routed to null page


# ---------------------------------------------------------------------------
# bit-identity: the acceptance criterion
# ---------------------------------------------------------------------------

def _paged_case(b, h, hkv, smax, d, ps, lens):
    """Contiguous q/k/v + the same KV scattered into a shuffled pool."""
    q = jnp.asarray(RNG.normal(size=(b, h, 1, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, smax, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, smax, d)), jnp.float32)
    npg = P.pages_for(smax, ps)
    perm = np.random.default_rng(3).permutation(b * npg) + 1
    pool = P.init_pool(b * npg + 1, hkv, ps, d)
    table = np.zeros((b, npg), np.int32)
    for i in range(b):
        pages = perm[i * npg:(i + 1) * npg]
        table[i] = pages
        pool = P.write_prefill_pages(pool, jnp.asarray(pages), k[i], v[i])
    pos = jnp.asarray(lens, jnp.int32)
    return q, k, v, pool, jnp.asarray(table), pos


@pytest.mark.parametrize("backend", ["tpu-interpret", "gpu-interpret"])
@pytest.mark.parametrize("gm", ["closed_form", "prefetch_lut",
                                "bounding", "mma"])
@pytest.mark.parametrize("ps", [8, 16])
def test_paged_decode_bit_identical_to_contiguous(backend, gm, ps):
    b, h, hkv, smax, d = 3, 4, 2, 64, 16
    q, k, v, pool, table, pos = _paged_case(
        b, h, hkv, smax, d, ps, lens=[37, 63, 9])
    # bitwise oracle: the contiguous flash decode at the same block
    # granularity (same online-softmax accumulation order)
    want = A.decode_attention_flash(q, k, v, pos, block_k=ps,
                                    backend=backend)
    got = A.decode_attention_paged(q, pool, table, pos, grid_mode=gm,
                                   backend=backend, verify=True)
    assert np.array_equal(np.asarray(got), np.asarray(want)), (backend, gm)
    # the XLA gather rung reproduces the plain softmax path bitwise
    xla = A.decode_attention_paged_xla(q, pool, table, pos)
    assert np.array_equal(np.asarray(xla),
                          np.asarray(A.decode_attention(q, k, v, pos)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("backend", ["tpu-interpret", "gpu-interpret"])
def test_paged_decode_local_window(backend):
    b, h, hkv, smax, d, ps = 2, 2, 2, 64, 16, 8
    q, k, v, pool, table, pos = _paged_case(
        b, h, hkv, smax, d, ps, lens=[50, 23])
    want = A.decode_attention_flash(q, k, v, pos, kind="local",
                                    window=16, block_k=ps,
                                    backend=backend)
    got = A.decode_attention_paged(q, pool, table, pos, window=16,
                                   backend=backend)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_paged_decode_slot_sharded_bit_identical():
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import paged as P
    from repro.models import attention as A
    rng = np.random.default_rng(5)
    b, h, hkv, smax, d, ps = 4, 4, 2, 32, 8, 8
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    npg = smax // ps
    pool = P.init_pool(b * npg + 1, hkv, ps, d)
    table = np.zeros((b, npg), np.int32)
    perm = rng.permutation(b * npg) + 1
    for i in range(b):
        k = jnp.asarray(rng.normal(size=(hkv, smax, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(hkv, smax, d)), jnp.float32)
        table[i] = perm[i * npg:(i + 1) * npg]
        pool = P.write_prefill_pages(pool, jnp.asarray(table[i]), k, v)
    table = jnp.asarray(table)
    pos = jnp.asarray([17, 31, 5, 24], jnp.int32)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",))
    want = A.decode_attention_paged(q, pool, table, pos)
    got = A.decode_attention_paged(q, pool, table, pos, mesh=mesh,
                                   shard_axis="data")
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # a batch that does not tile the mesh falls back to unsharded
    got3 = A.decode_attention_paged(q[:3], pool, table[:3], pos[:3],
                                    mesh=mesh, shard_axis="data")
    assert np.array_equal(np.asarray(got3), np.asarray(want)[:3])
    print("OK")
    """)


# ---------------------------------------------------------------------------
# per-row seq_pos on the contiguous decode path (regression)
# ---------------------------------------------------------------------------

def test_decode_flash_vector_seq_pos_matches_per_row():
    b, h, hkv, smax, d = 3, 4, 2, 64, 16
    q = jnp.asarray(RNG.normal(size=(b, h, 1, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, hkv, smax, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, hkv, smax, d)), jnp.float32)
    lens = [41, 63, 13]
    got = A.decode_attention_flash(q, k, v, jnp.asarray(lens, jnp.int32))
    for i, n in enumerate(lens):
        row = A.decode_attention_flash(q[i:i + 1], k[i:i + 1],
                                       v[i:i + 1], n)
        assert np.array_equal(np.asarray(got[i:i + 1]),
                              np.asarray(row)), i
    # a uniform vector is bitwise the scalar broadcast
    uni = A.decode_attention_flash(
        q, k, v, jnp.full((b,), 48, jnp.int32))
    assert np.array_equal(
        np.asarray(uni), np.asarray(A.decode_attention_flash(q, k, v, 48)))


# ---------------------------------------------------------------------------
# zig-zag balanced causal sharding
# ---------------------------------------------------------------------------

def test_zigzag_row_order_is_balanced_permutation():
    from repro.core.shard import zigzag_row_order
    for nby, D in ((8, 2), (16, 4), (24, 3)):
        perm = zigzag_row_order(nby, D)
        assert sorted(perm) == list(range(nby))
        # causal cost of device d = sum over owned rows j of (j+1);
        # the snake makes every device's total identical
        costs = [sum(j + 1 for j in perm[d * (nby // D):
                                         (d + 1) * (nby // D)])
                 for d in range(D)]
        assert len(set(costs)) == 1, (nby, D, costs)


def test_zigzag_flash_sharding_bit_identical():
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    b, h, d, s = 1, 2, 16, 256
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",))
    for gm in ("closed_form", "prefetch_lut", "bounding", "mma"):
        kw = dict(kind="causal", block_q=16, block_k=16, grid_mode=gm)
        want = ops.flash_attention(q, k, v, **kw)
        got = ops.flash_attention(q, k, v, mesh=mesh,
                                  shard_balance="zigzag", **kw)
        assert np.array_equal(np.asarray(got), np.asarray(want)), gm
    # zigzag requires causal and a row count divisible by 2D
    try:
        ops.flash_attention(q, k, v, kind="full", block_q=16,
                            block_k=16, mesh=mesh,
                            shard_balance="zigzag")
        raise SystemExit("expected ValueError (kind)")
    except ValueError as e:
        assert "causal" in str(e)
    try:
        ops.flash_attention(q[:, :, :64], k[:, :, :64], v[:, :, :64],
                            kind="causal", block_q=16, block_k=16,
                            mesh=mesh, shard_balance="zigzag")
        raise SystemExit("expected ValueError (rows)")
    except ValueError as e:
        assert "divisible" in str(e)
    print("OK")
    """)


# ---------------------------------------------------------------------------
# page-table verification
# ---------------------------------------------------------------------------

def _healthy_table():
    table = np.zeros((3, 8), np.int32)
    table[0, :3] = [1, 2, 3]
    table[1, :2] = [4, 5]
    return table, [20, 13, 0]


def test_verify_page_table_passes_healthy():
    from repro.analysis import verify_page_table
    table, lens = _healthy_table()
    rep = verify_page_table(table, lens, page_size=8, num_pages=16)
    assert not rep.findings


@pytest.mark.parametrize("name,mutate,kw", [
    ("bounds", lambda t: t.__setitem__((0, 1), 99), {}),
    ("bounds", lambda t: t.__setitem__((0, 1), -1), {}),
    ("null-in-extent", lambda t: t.__setitem__((1, 0), 0), {}),
    ("double-map", lambda t: t.__setitem__((1, 1), 2), {}),
    ("stale-free", lambda t: None, {"free_pages": [4]}),
    ("tail-null", lambda t: t.__setitem__((2, 0), 7), {}),
])
def test_verify_page_table_flags_mutations(name, mutate, kw):
    from repro.analysis import PlanVerificationError, verify_page_table
    table, lens = _healthy_table()
    mutate(table)
    with pytest.raises(PlanVerificationError, match=name):
        verify_page_table(table, lens, page_size=8, num_pages=16, **kw)


# ---------------------------------------------------------------------------
# page_size as an autotune knob
# ---------------------------------------------------------------------------

def test_autotune_paged_page_size_knob(tmp_path, monkeypatch):
    from repro.core import tune
    monkeypatch.setenv(tune.CACHE_ENV, str(tmp_path / "tune.json"))
    cfg, us, trials = tune.autotune_paged(
        batch=2, heads=2, seq=32, d=8, page_sizes=(8, 16))
    assert cfg["page_size"] in (8, 16) and "lowering" in cfg
    assert len(trials) >= 2
    # the winner persists and answers the lookup-only path
    params = {"batch": 2, "heads": 2, "kv_heads": 2, "seq": 32, "d": 8,
              "window": 0, "page_sizes": "16+8"}
    assert tune.best("paged", params) == cfg
    # a corrupt page_size marks the entry as a cache miss
    cache = tune.TuneCache(str(tmp_path / "tune.json"))
    cache.put("paged", tune._with_backend(params),
              {**cfg, "page_size": 0}, 1.0)
    assert tune.TuneCache(str(tmp_path / "tune.json")).get(
        "paged", tune._with_backend(params)) is None


# ---------------------------------------------------------------------------
# continuous-batching scheduler
# ---------------------------------------------------------------------------

def _paged_setup(decode_kernel="blockspace"):
    from repro.configs import get_config
    from repro.models import init
    cfg = get_config("quickstart", smoke=True).replace(
        attn_decode_kernel=decode_kernel)
    params = init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _mixed_prompts(cfg, lens=(7, 12, 5)):
    rng = np.random.default_rng(1)
    return [rng.integers(0, cfg.vocab_size, (n,)) for n in lens]


def test_paged_server_matches_single_request_oracle():
    from repro.launch.serve import (PagedServeConfig, PagedServer,
                                    ServeConfig, Server)
    cfg, params = _paged_setup()
    reqs = _mixed_prompts(cfg)
    scfg = PagedServeConfig(max_len=32, temperature=0.0, num_slots=2,
                            page_size=8, num_pages=16, guard=False)
    out = PagedServer(cfg, params, scfg).run(reqs, max_new=4)
    oracle = Server(cfg.replace(attn_decode_kernel="xla"), params,
                    ServeConfig(max_len=32, temperature=0.0,
                                guard=False))
    for rid, prompt in enumerate(reqs):
        want = oracle.generate(prompt[None], max_new=4)[0]
        assert np.array_equal(out[rid], want), rid


def test_paged_server_preemption_deterministic_and_leak_free():
    from repro.launch.serve import PagedServeConfig, PagedServer
    cfg, params = _paged_setup()
    reqs = _mixed_prompts(cfg, lens=(14, 18, 10))
    kw = dict(max_len=48, temperature=0.7, top_k=16, seed=5,
              num_slots=3, page_size=8, guard=False)
    starved = PagedServer(cfg, params,
                          PagedServeConfig(num_pages=8, **kw))
    out = starved.run(reqs, max_new=8)
    pre = [e for e in starved.events
           if isinstance(e, dict) and e.get("kind") == "preempt"]
    assert pre, "pool was not starved enough to preempt"
    roomy = PagedServer(cfg, params,
                        PagedServeConfig(num_pages=32, **kw))
    ref = roomy.run(reqs, max_new=8)
    for rid in ref:
        assert np.array_equal(out[rid], ref[rid]), rid
    for srv in (starved, roomy):            # every page returned
        assert srv.alloc.free_pages == srv.scfg.num_pages - 1


def test_paged_server_too_small_pool_raises():
    from repro.launch.serve import PagedServeConfig, PagedServer
    cfg, params = _paged_setup()
    scfg = PagedServeConfig(max_len=32, num_slots=1, page_size=4,
                            num_pages=3, guard=False)
    srv = PagedServer(cfg, params, scfg)
    with pytest.raises(RuntimeError, match="pool"):
        srv.run([np.arange(6) % cfg.vocab_size], max_new=16)


def test_paged_server_ladder_blockspace_to_xla():
    from repro.launch.serve import PagedServeConfig, PagedServer
    from repro.runtime.chaos import ChaosInjector, FaultPlan, FaultSpec
    from repro.runtime.guard import ServerState
    cfg, params = _paged_setup()
    reqs = _mixed_prompts(cfg)
    kw = dict(max_len=32, temperature=0.0, num_slots=2, page_size=8,
              num_pages=16, retries=2, backoff_base_s=0.0)
    want = PagedServer(cfg.replace(attn_decode_kernel="xla"), params,
                       PagedServeConfig(**kw)).run(reqs, max_new=4)
    plan = FaultPlan(0, [FaultSpec("transient_error", "serve.decode", i,
                                   rung=0) for i in range(3)])
    faulty = PagedServer(cfg, params, PagedServeConfig(**kw),
                         chaos=ChaosInjector(plan))
    assert faulty.ladder.rungs[0]["decode_kernel"] == "blockspace"
    out = faulty.run(reqs, max_new=4)
    assert faulty.state == ServerState.DEGRADED
    assert faulty.ladder.current()["decode_kernel"] == "xla"
    for rid in want:
        assert np.array_equal(out[rid], want[rid]), rid


def test_paged_throughput_report_fields():
    from repro.launch.serve import (PagedServeConfig, PagedServer,
                                    paged_throughput_report)
    cfg, params = _paged_setup(decode_kernel="xla")
    srv = PagedServer(cfg, params, PagedServeConfig(
        max_len=32, temperature=0.0, num_slots=2, page_size=8,
        num_pages=16, guard=False))
    rep = paged_throughput_report(srv, _mixed_prompts(cfg), max_new=3)
    assert rep["tokens"] == 9 and rep["requests"] == 3
    assert rep["tok_per_s"] > 0
    assert 0.0 <= rep["mean_fragmentation"] <= 1.0
    assert 0.0 < rep["peak_utilization"] <= 1.0
