"""Latent attention (MLA) on the paged serving path, the held-experts MoE
layer, and YaRN.

Covered:

  * the latent pool's append (inactive slots to the null page),
    prefill write and gather;
  * the paged latent decode kernel (interpret mode) against the gather
    oracle and against the contiguous absorbed ``mla_decode``, at
    several positions, page sizes and shuffled page layouts; many pages
    a grid step, the last chunk partial; NaN rows it must not read;
    its grid-step count against the grid it launches;
  * the held-experts layer: the 8 shares of a 64-expert layer, the
    shared expert counted once, add up to the uncut oracle; no token is
    dropped when every token routes to one expert; gates are not
    renormalised under ``norm_topk_prob=False``; an unimplemented
    routing method is refused;
  * YaRN's frequencies and attention factor against the published
    formula;
  * ``PagedServer`` over a latent stack: its ``xla`` rung serves through
    the gather fallback, token for token as the kernel, and its
    ``decode_grid_steps`` counts the latent kernel's grid; SSM and
    shared blocks are still refused, by name.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import paged as P
from repro.kernels.latent_decode import latent_decode_xla, paged_latent_decode
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models.config import ModelConfig
from repro.models.layers import yarn_get_mscale, yarn_inv_freq

RNG = np.random.default_rng(29)


def _rand(*shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


# ---------------------------------------------------------------------------
# latent pool
# ---------------------------------------------------------------------------

def test_latent_write_append_gather_roundtrip():
    ps, w, n = 4, 12, 10
    pool = P.init_latent_pool(n, ps, w)
    rows = _rand(7, w)
    pages = jnp.asarray([6, 2], jnp.int32)
    pool = P.write_latent_pages(pool, pages, rows)
    table = jnp.asarray([[6, 2, 0], [0, 0, 0]], jnp.int32)
    got = P.gather_latent(pool, table)
    # rows are stored in whole 128-lane tiles, zero past the row
    assert got.shape == (2, 12, P.latent_lanes(w)) == (2, 12, 128)
    np.testing.assert_array_equal(got[0, :7, :w], rows)
    np.testing.assert_array_equal(got[..., w:], 0)
    np.testing.assert_array_equal(got[0, 7:8], 0)     # the tail's padding
    new = _rand(2, w)
    pool = P.append_latent(pool, table, jnp.asarray([7, 3], jnp.int32), new,
                           jnp.asarray([True, False]))
    got = P.gather_latent(pool, table)
    np.testing.assert_array_equal(got[0, :7, :w], rows)
    np.testing.assert_array_equal(got[0, 7, :w], new[0])
    np.testing.assert_array_equal(pool[..., w:], 0)
    # the inactive slot wrote to the null page only
    np.testing.assert_array_equal(pool[0, 3, :w], new[1])
    assert float(jnp.abs(pool[1:]).sum() - jnp.abs(rows).sum()
                 - jnp.abs(new[0]).sum()) == pytest.approx(0, abs=1e-3)


# ---------------------------------------------------------------------------
# the paged latent kernel
# ---------------------------------------------------------------------------

def _paged_layout(b, m, ps, w, shuffle):
    n = b * m + 1
    ids = np.arange(1, n)
    if shuffle:
        ids = RNG.permutation(ids)
    table = jnp.asarray(ids.reshape(b, m), jnp.int32)
    pool = _rand(n, ps, w)
    return pool, table


@pytest.mark.parametrize("ps,shuffle", [(4, False), (8, True), (16, True)])
def test_latent_kernel_matches_gather_oracle(ps, shuffle):
    b, h, L, dr, m = 3, 4, 16, 8, 5
    pool, table = _paged_layout(b, m, ps, L + dr, shuffle)
    q = _rand(b, h, L + dr)
    for pos in ([0, ps, m * ps - 1], [ps - 1, 2 * ps + 1, 1]):
        pos = jnp.asarray(pos, jnp.int32)
        got = paged_latent_decode(q, pool, table, pos, scale=0.3, v_dim=L)
        want = latent_decode_xla(q, pool, table, pos, scale=0.3, v_dim=L)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ps,m,shuffle", [(4, 300, False), (8, 150, True),
                                          (16, 70, True)])
def test_latent_kernel_takes_chunks_of_pages(ps, m, shuffle):
    """Many pages a grid step, by the shape-derived rule (512 keys a
    chunk: 128, 64 and 32 pages here), the last chunk partial: positions
    at a chunk's last key, its next chunk's first, 0 (every chunk after
    the first dead) and the table's last key, against the gather
    oracle."""
    from repro.kernels.latent_decode import latent_decode_grid
    b, h, L, dr = 4, 4, 16, 8
    pool, table = _paged_layout(b, m, ps, L + dr, shuffle)
    _, domain, pages = latent_decode_grid(b, m, ps, L + dr, 4)
    assert pages * ps == 512 and pages < m and m % pages
    assert domain.num_blocks == -(-m // pages) == 3
    q = _rand(b, h, L + dr)
    for pos in ([pages * ps - 1, pages * ps, 0, m * ps - 1],
                [m * ps - 1, 2 * pages * ps, 5, pages * ps + 1]):
        pos = jnp.asarray(pos, jnp.int32)
        got = paged_latent_decode(q, pool, table, pos, scale=0.3, v_dim=L)
        want = latent_decode_xla(q, pool, table, pos, scale=0.3, v_dim=L)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_latent_kernel_never_shows_rows_past_a_slots_position():
    """Every pool page outside each slot's live pages, and each last
    live page's rows past the slot's position, hold NaN: the kernel
    neither copies nor weighs them, so its output is finite and equals
    the oracle's over the same pool with those rows zeroed."""
    b, h, L, dr, ps, m = 4, 4, 16, 8, 8, 150
    pool, table = _paged_layout(b, m, ps, L + dr, shuffle=True)
    q = _rand(b, h, L + dr)
    pos = np.array([64 * ps - 1, 64 * ps, 0, 2 * 64 * ps + 3])
    dead = np.ones((pool.shape[0], ps), bool)
    for s, p in enumerate(pos):
        pages = np.asarray(table[s, :p // ps + 1])
        dead[pages] = False
        dead[pages[-1], p % ps + 1:] = True
    clean = jnp.where(dead[..., None], 0.0, pool)
    poisoned = jnp.where(dead[..., None], jnp.nan, pool)
    pos = jnp.asarray(pos, jnp.int32)
    got = paged_latent_decode(q, poisoned, table, pos, scale=0.3, v_dim=L)
    assert np.isfinite(np.asarray(got)).all()
    want = latent_decode_xla(q, clean, table, pos, scale=0.3, v_dim=L)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_latent_kernel_reads_lane_padded_pools():
    """A pool from ``init_latent_pool`` stores 24-wide rows in 128
    lanes: the kernel and the oracle read the rows' own lanes."""
    b, h, L, dr, ps, m = 2, 4, 16, 8, 4, 6
    pool, table = _paged_layout(b, m, ps, L + dr, shuffle=True)
    wide = P.init_latent_pool(pool.shape[0], ps, L + dr)
    wide = wide.at[..., :L + dr].set(pool)
    q = _rand(b, h, L + dr)
    pos = jnp.asarray([5, m * ps - 1], jnp.int32)
    want = latent_decode_xla(q, pool, table, pos, scale=0.3, v_dim=L)
    for fn in (paged_latent_decode, latent_decode_xla):
        got = fn(q, wide, table, pos, scale=0.3, v_dim=L)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _latent_launches(fn):
    """Run ``fn`` with the latent kernel re-traced; the emit records."""
    from repro.core import backend as backend_lib
    from repro.kernels import latent_decode

    class Log:
        records = []

        def instrument(self, record, kernel, in_specs, out_specs):
            self.records.append(record)
            return kernel, in_specs, out_specs

        def wrap_call(self, record, fn):
            return fn

    latent_decode._latent_decode_impl.clear_cache()
    prev = backend_lib.set_emit_hook(Log())
    try:
        out = fn()
    finally:
        backend_lib.set_emit_hook(prev)
    return out, Log.records


@pytest.mark.parametrize("gm", ["closed_form", "prefetch_lut", "bounding",
                                "mma"])
def test_latent_decode_grid_steps_is_the_launched_grid(gm):
    from repro.kernels.latent_decode import latent_decode_grid_steps
    # the dsv2lite cell: 16 slots of 384 pages of 16 tokens, rows in 640
    # lanes of bf16, 27 latent layers: 12 chunks of 32 pages a slot
    cell = latent_decode_grid_steps(16, 384, 27, page_size=16, width=640,
                                    itemsize=2)
    assert cell == 16 * 12 * 27 == 5_184 <= 165_888 // 16
    b, h, L, dr, ps, m = 3, 4, 16, 8, 16, 70
    pool, table = _paged_layout(b, m, ps, L + dr, shuffle=True)
    q = _rand(b, h, L + dr)
    pos = jnp.asarray([0, 32 * ps, m * ps - 1], jnp.int32)
    got, (rec,) = _latent_launches(lambda: paged_latent_decode(
        q, pool, table, pos, scale=0.3, v_dim=L, grid_mode=gm))
    assert rec.plan.num_steps == latent_decode_grid_steps(
        b, m, page_size=ps, width=L + dr, itemsize=4) == b * 3
    want = latent_decode_xla(q, pool, table, pos, scale=0.3, v_dim=L)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def _mla_cfg(**kw):
    base = dict(d_model=64, n_heads=4, kv_lora_rank=16, qk_nope_dim=16,
                qk_rope_dim=8, v_head_dim=16, use_mla=True, dtype="float32",
                param_dtype="float32")
    return ModelConfig(**{**base, **kw})


@pytest.mark.parametrize("ps,shuffle", [(4, True), (8, False)])
@pytest.mark.parametrize("kernel", ["blockspace", "xla"])
def test_paged_mla_decode_matches_contiguous(ps, shuffle, kernel):
    """Each slot at its own position, its latents in shuffled pages: the
    paged absorbed decode gives the contiguous ``mla_decode`` output of
    that slot and writes the same cache row."""
    cfg = _mla_cfg(yarn_factor=40.0, yarn_mscale=0.707,
                   yarn_mscale_all_dim=0.707, attn_decode_kernel=kernel)
    p = mla_lib.mla_init(jax.random.PRNGKey(3), cfg)
    b, m, smax = 3, 4, 4 * ps
    w = cfg.latent_width
    c_cache = _rand(b, smax, cfg.kv_lora_rank)
    r_cache = _rand(b, smax, cfg.qk_rope_dim)
    table = np.arange(1, b * m + 1)
    if shuffle:
        table = RNG.permutation(table)
    table = jnp.asarray(table.reshape(b, m), jnp.int32)
    pool = P.init_latent_pool(b * m + 1, ps, w)
    for s in range(b):
        pool = P.write_latent_pages(pool, table[s], jnp.concatenate(
            [c_cache[s], r_cache[s]], -1))
    x = _rand(b, 1, cfg.d_model)
    pos = jnp.asarray([0, ps + 1, smax - 1], jnp.int32)
    out, pool = mla_lib.mla_decode_paged(p, x, cfg, pool, table, pos)
    rows = P.gather_latent(pool, table)
    for s in range(b):
        want, (c2, r2) = mla_lib.mla_decode(
            p, x[s:s + 1], cfg, (c_cache[s:s + 1], r_cache[s:s + 1]),
            int(pos[s]))
        np.testing.assert_allclose(out[s], want[0], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            rows[s, int(pos[s]), :w], jnp.concatenate(
                [c2[0, int(pos[s])], r2[0, int(pos[s])]]), atol=1e-6)


# ---------------------------------------------------------------------------
# held experts
# ---------------------------------------------------------------------------

def _moe_cfg(**kw):
    base = dict(d_model=32, d_ff_expert=16, n_experts=64, top_k=6,
                moe=True, n_shared_experts=2, dtype="float32",
                param_dtype="float32", norm_topk_prob=False)
    base.update(kw)
    return ModelConfig(**base)


def _share(p, first, held):
    out = dict(p)
    for k in ("wi", "wg", "wo"):
        out[k] = p[k][first:first + held]
    return out


def test_held_shares_add_up_to_the_uncut_layer():
    """Eight chips of eight experts each: the parts their held experts
    give, with the shared experts (which every chip computes alike)
    counted once, add up to the uncut layer's oracle."""
    cfg = _moe_cfg()
    p = moe_lib.moe_init(jax.random.PRNGKey(0), cfg.replace(experts_held=64))
    x = _rand(2, 24, 32)
    whole = moe_lib.moe_block_dense_ref(p, x, cfg.replace(experts_held=64))
    shared = moe_lib._shared(p, x.reshape(-1, 32)).reshape(x.shape)
    parts, loads = [], []
    for chip in range(8):
        share = cfg.replace(experts_first=8 * chip, experts_held=8)
        out, load = moe_lib.moe_block_held(_share(p, 8 * chip, 8), x, share)
        parts.append(out - shared)
        loads.append(load)
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-4,
                               atol=1e-5)
    assert int(sum(int(ld.sum()) for ld in loads)) == 2 * 24 * 6


def test_held_layer_matches_its_oracle_and_counts_routes():
    cfg = _moe_cfg(experts_first=16, experts_held=8)
    p = moe_lib.moe_init(jax.random.PRNGKey(1), cfg)
    assert p["wg"].shape[0] == 8 and p["router"].shape[1] == 64
    x = _rand(3, 10, 32)
    out, loads = moe_lib.moe_block_held(p, x, cfg)
    np.testing.assert_allclose(out, moe_lib.moe_block_dense_ref(p, x, cfg),
                               rtol=1e-4, atol=1e-5)
    _, _, idx = moe_lib.route(x.reshape(-1, 32), p["router"], cfg)
    want = [(np.asarray(idx) == 16 + e).sum() for e in range(8)]
    np.testing.assert_array_equal(loads, want)


def test_no_token_dropped_when_all_route_to_one_expert():
    """Every token's top route lands on held expert 3: each is computed,
    where the capacity-dispatched layer drops all but its capacity."""
    cfg = _moe_cfg(top_k=1, experts_held=8, n_shared_experts=0)
    p = moe_lib.moe_init(jax.random.PRNGKey(2), cfg)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 3].set(1.0)
    x = jnp.abs(_rand(4, 32, 32)) + 0.1        # router logit x.sum() > 0
    out, loads = moe_lib.moe_block_held(p, x, cfg)
    assert int(loads[3]) == 4 * 32 == int(loads.sum())
    np.testing.assert_allclose(out, moe_lib.moe_block_dense_ref(p, x, cfg),
                               rtol=1e-4, atol=1e-5)
    dropped, _ = moe_lib.moe_block(
        dict(p, router=p["router"][:, :8]),
        x, cfg.replace(experts_held=0, n_experts=8, capacity_factor=1.0))
    lost = np.all(np.asarray(dropped) == 0, axis=-1)
    assert lost.sum() > 0 and not np.any(np.all(np.asarray(out) == 0, -1))


@pytest.mark.parametrize("norm", [False, True])
def test_norm_topk_prob_renormalises_only_when_set(norm):
    cfg = _moe_cfg(norm_topk_prob=norm)
    x = _rand(16, 32)
    router = _rand(32, 64)
    probs, gates, idx = moe_lib.route(x, router, cfg)
    np.testing.assert_allclose(
        gates if not norm else gates * jnp.take_along_axis(
            probs, idx, -1).sum(-1, keepdims=True),
        jnp.take_along_axis(probs, idx, -1), rtol=1e-6)
    sums = np.asarray(gates.sum(-1))
    if norm:
        np.testing.assert_allclose(sums, 1.0, rtol=1e-6)
    else:
        assert np.all(sums < 1.0)


def test_unimplemented_routing_is_refused():
    cfg = _moe_cfg(topk_method="group_limited_greedy")
    with pytest.raises(NotImplementedError, match="group_limited_greedy"):
        moe_lib.route(_rand(4, 32), _rand(32, 64), cfg)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def test_yarn_against_the_published_formula():
    """DeepSeek-V2-Lite's rope_scaling: factor 40 over 4096 positions,
    beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707, on the
    64 rope dims at theta 10000."""
    d, theta = 64, 10000.0
    cfg = _mla_cfg(qk_nope_dim=128, qk_rope_dim=64, yarn_factor=40.0,
                   yarn_original_max_pos=4096, yarn_mscale=0.707,
                   yarn_mscale_all_dim=0.707)
    got = yarn_inv_freq(d, theta, cfg.yarn)

    def corr(rot):
        return d * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    for i in range(d // 2):
        plain = 1.0 / theta ** (2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = plain / 40 * ramp + plain * (1 - ramp)
        assert got[i] == pytest.approx(want, rel=1e-5), i
    assert yarn_get_mscale(40.0, 0.707) == pytest.approx(
        0.1 * 0.707 * math.log(40) + 1)
    assert yarn_get_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert cfg.mla_softmax_scale == pytest.approx(
        192 ** -0.5 * yarn_get_mscale(40.0, 0.707) ** 2)
    assert _mla_cfg().mla_softmax_scale == pytest.approx(24 ** -0.5)


# ---------------------------------------------------------------------------
# the server over latent pools
# ---------------------------------------------------------------------------

def _latent_server_setup():
    from repro.configs import get_config
    from repro.models import init
    cfg = get_config("deepseek-v2-lite-16b", smoke=True).replace(
        experts_first=4, experts_held=8)
    return cfg, init(jax.random.PRNGKey(0), cfg)


def test_latent_server_counts_the_latent_kernels_grid_steps():
    """``decode_grid_steps`` counts the latent kernel's grid over the
    latent layers under ``blockspace``, and is 0 on the ``xla`` rung."""
    from repro.kernels.latent_decode import latent_decode_grid_steps
    from repro.launch.serve import PagedServeConfig, PagedServer
    cfg, params = _latent_server_setup()
    srv = PagedServer(cfg.replace(attn_decode_kernel="blockspace"), params,
                      PagedServeConfig(max_len=32, num_slots=2, page_size=4,
                                       num_pages=20))
    want = latent_decode_grid_steps(
        2, 8, cfg.n_layers, page_size=4, width=P.latent_lanes(
            cfg.latent_width), itemsize=jnp.dtype(cfg.jdtype()).itemsize)
    assert srv.decode_grid_steps == want == 2 * cfg.n_layers > 0
    srv._apply_rung({"decode_kernel": "xla"})
    assert srv.decode_grid_steps == 0


def test_latent_server_xla_rung_serves_as_the_kernel():
    from repro.launch.serve import PagedServeConfig, PagedServer
    cfg, params = _latent_server_setup()
    rng = np.random.default_rng(4)
    reqs = [rng.integers(0, cfg.vocab_size, (n,)) for n in (7, 12, 5)]
    kw = dict(max_len=32, temperature=0.0, num_slots=2, page_size=4,
              num_pages=20)
    srv = PagedServer(cfg.replace(attn_decode_kernel="blockspace"), params,
                      PagedServeConfig(**kw))
    assert [r["decode_kernel"] for r in srv.ladder.rungs] == [
        "blockspace", "xla"]
    out = srv.run(reqs, max_new=5)
    srv.ladder.step_down(reason="test")
    srv._apply_rung(srv.ladder.current())
    xla = PagedServer(cfg.replace(attn_decode_kernel="xla"), params,
                      PagedServeConfig(**kw)).run(reqs, max_new=5)
    for rid in out:
        assert np.array_equal(out[rid], xla[rid]), rid
    # the held routes of every decode step were kept
    assert len(srv.moe_routes_held) == srv.steps_served > 0
    assert all(0 <= r <= 2 * cfg.top_k * (cfg.n_layers - cfg.first_dense)
               for r in srv.moe_routes_held)


@pytest.mark.parametrize("arch,what", [("falcon-mamba-7b", "'mamba1'"),
                                       ("zamba2-2.7b", "shared")])
def test_paged_serving_refuses_ssm_and_shared_blocks(arch, what):
    from repro.configs import get_config
    from repro.models import model as model_lib
    cfg = get_config(arch, smoke=True)
    with pytest.raises(ValueError, match=what) as e:
        model_lib.init_paged_cache(cfg, 8, 4)
    assert "SSM state and shared blocks are not paged" in str(e.value)
