"""Emission-target tests: every kernel x storage x lowering on the
interpreted target against the ``kernels/ref.py`` oracles, plus the
descriptor invariants, target resolution rules, and the host-table
memoization the emission layer rides on.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as B
from repro.core import memo
from repro.core.compact import CompactLayout, pack_kv
from repro.core.domain import (make_attention_domain, make_fractal_domain)
from repro.core.plan import LOWERINGS, GridPlan
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.sierpinski_ca import ca_run
from repro.kernels.sierpinski_write import sierpinski_sum, sierpinski_write

RNG = np.random.default_rng(7)
TARGET = "tpu-interpret"


# ---------------------------------------------------------------------------
# capability descriptor + resolution invariants
# ---------------------------------------------------------------------------

def test_capability_descriptor_invariants():
    assert set(B.TARGETS) == {"tpu", "tpu-interpret"}
    for t in B.TARGETS.values():
        assert t.emulated() is B.TPU_INTERPRET
        assert t.emulated().emulated() is t.emulated()  # idempotent
        assert t.native() is B.TPU and not t.native().interpret
        assert B.resolve(t) is t
        assert B.TARGETS[t.name] is t
        assert t.pipeline_stages == 2
        # the chip bounds the SMEM tables; the interpreter does not
        assert (t.smem_table_bytes is None) == t.interpret


def test_resolution_rules():
    # platform default on CPU is the interpreted target
    assert jax.default_backend() == "cpu"
    assert B.resolve(None) is B.TPU_INTERPRET
    assert B.platform_default() is B.TPU_INTERPRET
    # a native target stays native off its platform: it fails to lower
    # on the CPU instead of being emulated unseen
    assert B.resolve("tpu") is B.TPU
    assert B.resolve(B.TPU) is B.TPU
    assert B.resolve("tpu", interpret=False) is B.TPU
    # interpret=True forces emulation; aliases resolve
    assert B.resolve("mosaic") is B.TPU
    assert B.resolve("tpu", interpret=True) is B.TPU_INTERPRET
    assert B.resolve("interpret") is B.TPU_INTERPRET
    assert B.resolve(B.TPU_INTERPRET, interpret=False) is B.TPU
    for gone in ("cuda", "gpu", "gpu-interpret", "triton"):
        with pytest.raises(ValueError, match="unknown backend"):
            B.resolve(gone)
    # process override (the serve/train --backend flag)
    B.set_default("tpu")
    try:
        assert B.resolve(None) is B.TPU
        assert B.platform_default() is B.TPU_INTERPRET
    finally:
        B.set_default(None)
    assert B.resolve(None) is B.TPU_INTERPRET
    with pytest.raises(ValueError):
        B.set_default("gpu-interpret")


def test_env_override(monkeypatch):
    # no environment variable steers the target: a leftover
    # REPRO_BACKEND (once "gpu-interpret") is ignored, not an error
    monkeypatch.setenv("REPRO_BACKEND", "gpu-interpret")
    assert B.resolve(None) is B.TPU_INTERPRET
    monkeypatch.setenv("REPRO_BACKEND", "tpu")
    assert B.resolve(None) is B.TPU_INTERPRET


# ---------------------------------------------------------------------------
# oracle matrix: write / sum / CA x storage x lowering
# ---------------------------------------------------------------------------

def _fractal_operands(n, block, fractal="sierpinski-gasket"):
    dom = make_fractal_domain(fractal, n // block)
    y, x = np.mgrid[0:n, 0:n]
    mask = np.asarray(dom.cell_member(jnp.asarray(x), jnp.asarray(y), n))
    state = (RNG.integers(0, 2, (n, n)) * mask).astype(np.float32)
    lay = CompactLayout(dom)
    return dom, lay, jnp.asarray(state)


@pytest.mark.parametrize("storage", ("embedded", "compact"))
@pytest.mark.parametrize("lowering", LOWERINGS)
def test_write_and_sum_backend_parity(storage, lowering):
    n, block = 32, 8
    dom, lay, state = _fractal_operands(n, block)
    m = lay.pack(state, block) if storage == "compact" else state
    kw = dict(block=block, grid_mode=lowering, storage=storage, n=n,
              backend=TARGET)
    out = np.asarray(sierpinski_write(m, 7.0, **kw))
    total = np.asarray(sierpinski_sum(m, **kw))
    emb = lay.unpack(jnp.asarray(out), block) \
        if storage == "compact" else out
    np.testing.assert_array_equal(
        np.asarray(emb), np.asarray(ref.sierpinski_write_ref(state, 7.0)))
    np.testing.assert_allclose(total, float(jnp.sum(state)), rtol=1e-6)


@pytest.mark.parametrize("storage", ("embedded", "compact"))
@pytest.mark.parametrize("lowering", LOWERINGS)
def test_ca_backend_parity(storage, lowering):
    n, block, steps = 32, 8, 5
    dom, lay, state = _fractal_operands(n, block)
    zero = jnp.zeros((n, n), jnp.float32)
    if storage == "compact":
        a, b = lay.pack(state, block), lay.pack(zero, block)
    else:
        a, b = state, zero
    kw = dict(rule="parity", block=block, grid_mode=lowering,
              storage=storage, n=n, fuse=2, donate=False, backend=TARGET)
    out = np.asarray(ca_run(a, b, steps, **kw))
    # reference: unfused sequential oracle
    want = state
    for _ in range(steps):
        want = ref.ca_step_ref(want, rule="parity")
    emb = lay.unpack(jnp.asarray(out), block) \
        if storage == "compact" else out
    np.testing.assert_array_equal(np.asarray(emb), np.asarray(want))


@pytest.mark.parametrize("lowering", LOWERINGS)
def test_ca_coarsen_backend_parity(lowering):
    n, block = 32, 4
    dom, lay, state = _fractal_operands(n, block)
    a, b = lay.pack(state, block), lay.pack(
        jnp.zeros((n, n), jnp.float32), block)
    kw = dict(rule="diffusion", block=block, grid_mode=lowering,
              storage="compact", n=n, fuse=2, coarsen=2, donate=False,
              backend=TARGET)
    out = ca_run(a, b, 4, **kw)
    want = state
    for _ in range(4):
        want = ref.ca_step_ref(want, rule="diffusion")
    np.testing.assert_allclose(np.asarray(lay.unpack(out, block)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# oracle matrix: flash attention x kind x lowering (+ compact KV)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,window", (("causal", 0), ("local", 32),
                                         ("full", 0)))
@pytest.mark.parametrize("lowering", LOWERINGS)
def test_flash_backend_parity(kind, window, lowering):
    b, h, s, d = 2, 4, 128, 16
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, 2, s, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, 2, s, d)), jnp.float32)
    kw = dict(kind=kind, window=window, block_q=32, block_k=32,
              grid_mode=lowering, backend=TARGET)
    out = np.asarray(flash_attention(q, k, v, **kw))
    want = ref.attention_ref(q, k, v, kind=kind, window=window)
    np.testing.assert_allclose(out, np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("lowering", LOWERINGS)
def test_flash_compact_kv_backend_parity(lowering):
    sq, sk, w, bq = 64, 128, 32, 16
    q = jnp.asarray(RNG.normal(size=(1, 2, sq, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, sk, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, sk, 16)), jnp.float32)
    dom = make_attention_domain("local", sq // bq, sk // bq, w // bq + 1)
    kp, vp = pack_kv(k, dom, bq), pack_kv(v, dom, bq)
    kw = dict(kind="local", window=w, block_q=bq, block_k=bq,
              grid_mode=lowering, backend=TARGET)
    packed = np.asarray(flash_attention(q, kp, vp, storage="compact",
                                        kv_seq_len=sk, **kw))
    # packing only drops key blocks no query reaches: bit-identical to
    # the embedded KV, and both to the oracle
    embedded = np.asarray(flash_attention(q, k, v, **kw))
    np.testing.assert_array_equal(packed, embedded)
    want = ref.attention_ref(q, k, v, kind="local", window=w)
    np.testing.assert_allclose(packed, np.asarray(want), atol=2e-5)


def test_flash_decode_seq_pos_parity():
    from repro.models.attention import decode_attention
    S = 64
    q = jnp.asarray(RNG.normal(size=(2, 4, 1, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 2, S, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 2, S, 16)), jnp.float32)
    for pos in (0, 21, S - 1):
        out = np.asarray(flash_attention(
            q, k, v, kind="full", block_q=1, block_k=16,
            seq_pos=jnp.asarray(pos), backend=TARGET))
        want = decode_attention(q, k, v, jnp.asarray(pos))
        np.testing.assert_allclose(out, np.asarray(want), atol=2e-6)


def test_seq_pos_requires_kind_full():
    # a band row wholly beyond seq_pos has an empty k-extent: no step
    # initializes its output, so the combination is rejected (decode
    # rides kind="full" + window=)
    q = jnp.zeros((1, 1, 64, 8), jnp.float32)
    for kind in ("causal", "local"):
        with pytest.raises(ValueError, match="seq_pos"):
            flash_attention(q, q, q, kind=kind, window=16, block_q=16,
                            block_k=16, seq_pos=jnp.asarray(3),
                            backend="tpu-interpret")


def test_decode_attention_flash_windowed():
    from repro.models.attention import (decode_attention,
                                        decode_attention_flash)
    S = 64
    q = jnp.asarray(RNG.normal(size=(2, 4, 1, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 2, S, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 2, S, 16)), jnp.float32)
    for kind, w in (("causal", 0), ("local", 24)):
        for pos in (5, 40, S - 1):
            want = decode_attention(q, k, v, jnp.asarray(pos), kind=kind,
                                    window=w)
            got = decode_attention_flash(
                q, k, v, jnp.asarray(pos), kind=kind, window=w,
                block_k=16, backend=TARGET)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(want), atol=2e-6)


# ---------------------------------------------------------------------------
# explicit-plan parity: GridPlan(backend=...) drives the same emitters
# ---------------------------------------------------------------------------

def test_gridplan_carries_target():
    dom = make_fractal_domain("sierpinski-gasket", 4)
    assert GridPlan(dom).target is B.resolve(None)
    p_tpu = GridPlan(dom, "prefetch_lut", backend="tpu")
    assert p_tpu.target is B.TPU
    assert GridPlan(dom, backend=B.TPU_INTERPRET).target is B.TPU_INTERPRET
    # the native target's emitter checks its SMEM budget when traced;
    # the interpreted one has none
    assert p_tpu.target.smem_table_bytes == B.SMEM_TABLE_BYTES
    # every table-backed lowering binds its table, on every target
    for lowering in ("prefetch_lut", "mma"):
        for backend in B.TARGETS:
            plan = GridPlan(dom, lowering, backend=backend)
            assert plan.num_scalar_prefetch == 1
            assert len(plan.bound_prefetch()) == 1


# ---------------------------------------------------------------------------
# host-table memoization (the multi-host startup satellite)
# ---------------------------------------------------------------------------

def test_lut_host_memoized_per_domain_axes():
    dom = make_fractal_domain("sierpinski-gasket", 8)
    a = GridPlan(dom, "prefetch_lut", storage="compact").lut_host()
    b = GridPlan(dom, "prefetch_lut", storage="compact").lut_host()
    assert a is b  # same table object across plan instances
    c = GridPlan(make_fractal_domain("sierpinski-gasket", 8),
                 "prefetch_lut", storage="compact").lut_host()
    assert a is c  # and across equal domain instances (cache_key)
    d = GridPlan(dom, "prefetch_lut", storage="embedded").lut_host()
    assert d is not a  # storage changes the table

    layA = GridPlan(dom).layout
    layB = GridPlan(make_fractal_domain("sierpinski-gasket", 8)).layout
    assert layA is layB  # CompactLayout shared per domain


def test_shard_tables_memoized():
    from repro.core.shard import ShardedPlan

    class _Dev:
        def __init__(self, i):
            self.id = i

    from jax.sharding import Mesh
    if jax.device_count() >= 2:
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    else:
        pytest.skip("needs 2 devices")
    dom = make_fractal_domain("sierpinski-gasket", 8)
    p1 = ShardedPlan(dom, "prefetch_lut", storage="compact", mesh=mesh,
                     axis="data", halo=True)
    p2 = ShardedPlan(dom, "prefetch_lut", storage="compact", mesh=mesh,
                     axis="data", halo=True)
    assert p1.halo is p2.halo
    assert p1.shard_table_host() is p2.shard_table_host()
    assert p1.lut_sharded_host() is p2.lut_sharded_host()


def _mesh_or_skip(D=2):
    from jax.sharding import Mesh
    if jax.device_count() < D:
        pytest.skip(f"needs {D} devices "
                    f"(XLA_FLAGS=--xla_force_host_platform_device_count)")
    return Mesh(np.array(jax.devices()[:D]), ("data",))


@pytest.mark.parametrize("storage", ("embedded", "compact"))
def test_sharded_ca_backend_parity(storage):
    """A run on a mesh (slab halo exchange / psum combine) must stay
    bit-identical to the unsharded run."""
    mesh = _mesh_or_skip(2)
    n, block = 32, 8
    dom, lay, state = _fractal_operands(n, block)
    zero = jnp.zeros((n, n), jnp.float32)
    if storage == "compact":
        a, b = lay.pack(state, block), lay.pack(zero, block)
    else:
        a, b = state, zero
    base = np.asarray(ca_run(state, zero, 4, rule="parity", block=block,
                             grid_mode="closed_form", fuse=2,
                             donate=False, backend="tpu-interpret"))
    got = ca_run(a, b, 4, rule="parity", block=block,
                 grid_mode="closed_form", storage=storage, n=n, fuse=2,
                 donate=False, backend=TARGET, mesh=mesh)
    emb = lay.unpack(got, block) if storage == "compact" else got
    np.testing.assert_array_equal(np.asarray(emb), base)


def test_sharded_flash_backend_parity():
    mesh = _mesh_or_skip(2)
    s = 128
    q = jnp.asarray(RNG.normal(size=(1, 2, s, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, s, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, s, 16)), jnp.float32)
    base = np.asarray(flash_attention(q, k, v, kind="causal",
                                      block_q=32, block_k=32,
                                      backend="tpu-interpret"))
    for lowering in LOWERINGS:
        got = flash_attention(q, k, v, kind="causal", block_q=32,
                              block_k=32, grid_mode=lowering,
                              backend=TARGET, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(got), base)


def test_memo_stats_count_hits():
    memo.clear()
    dom = make_fractal_domain("sierpinski-gasket", 8)
    GridPlan(dom, storage="compact").lut_host()
    misses = memo.STATS["misses"]
    GridPlan(dom, storage="compact").lut_host()
    assert memo.STATS["hits"] >= 1
    assert memo.STATS["misses"] == misses  # no rebuild


def test_uncacheable_domain_still_works():
    from repro.core.domain import BoundingBoxDomain
    dom = BoundingBoxDomain(4, 4, member=lambda x, y: (x + y) % 2 == 0)
    assert dom.cache_key is None
    a = GridPlan(dom, "prefetch_lut").lut_host()
    b = GridPlan(dom, "prefetch_lut").lut_host()
    np.testing.assert_array_equal(a, b)  # rebuilt, but correct


def test_prefetch_table_over_smem_is_refused_at_plan_time():
    """A decode table that would not fit the chip's SMEM is refused
    while the launch is traced for the native target, with the limit in
    the message -- not handed to the compiler (or quietly swapped for
    another lowering).  The compact n=2^16 gasket at block 128 has
    19,683 blocks x 28 i32 columns: 2.2 MB of table against 1 MiB of
    SMEM.  The interpreter, which holds tables in host memory, has no
    such budget."""
    n, block = 1 << 16, 128
    lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                            n // block))
    m = jax.ShapeDtypeStruct(lay.array_shape(block), jnp.float32)
    for lowering in ("prefetch_lut", "mma"):
        with pytest.raises(ValueError, match=str(B.SMEM_TABLE_BYTES)):
            jax.jit(lambda a, lowering=lowering: sierpinski_write(
                a, 1.0, block=block, grid_mode=lowering,
                storage="compact", n=n, coarsen=1, num_stages=1,
                backend="tpu")).trace(m)
    assert B.TPU.smem_table_bytes == B.SMEM_TABLE_BYTES
    assert B.TPU_INTERPRET.smem_table_bytes is None
    # the embedded table of the same domain (2 columns) rides flat
    B.check_smem_tables([(19683, 2)], limit=B.TPU.smem_table_bytes)
