"""The benchmark's plain references against the program, at tiny sizes on
the CPU (the program's kernels under the Pallas interpreter)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve_closed
from bench.reference import gasket as ref
from bench.reference import phi3, prng_key

N, BLOCK = 64, 16
R = 2                                   # log2(N / BLOCK)


def _pack(emb, block=BLOCK, r=R):
    """Embedded -> packed with the benchmark's own lambda map."""
    bx, by = ref.slot_blocks(r)
    rows, cols = ref.orthotope(r)
    t = np.asarray(emb).reshape(1 << r, block, 1 << r, block)
    out = t[by, :, bx, :]                       # (rows, cols, B, B)
    return jnp.asarray(out.transpose(0, 2, 1, 3).reshape(rows * block,
                                                         cols * block))


def _unpack(packed, block=BLOCK, r=R):
    bx, by = ref.slot_blocks(r)
    rows, cols = ref.orthotope(r)
    t = np.asarray(packed).reshape(rows, block, cols, block)
    out = np.zeros((1 << r, block, 1 << r, block), t.dtype)
    out[by, :, bx, :] = t.transpose(0, 2, 1, 3)
    return out.reshape((1 << r) * block, (1 << r) * block)


#: the kernel and the reference may round a multiply-add differently
#: (the CPU fuses it, the chip does not): one f32 rounding of values < 1
ROUNDING = 2e-7


def _state(n=N, seed=0):
    u = jax.random.uniform(jax.random.PRNGKey(seed), (n, n))
    return jnp.where(jnp.asarray(ref.membership_grid(n)), u, 0)


def test_layout_matches_the_programs_compact_storage():
    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    for r in (1, 2, 3, 4):
        lay = CompactLayout(make_fractal_domain("sierpinski-gasket", 1 << r))
        bx, by = ref.slot_blocks(r)
        coords, slots = lay.domain.coords_host(), lay.slots_host()
        assert ref.orthotope(r) == (lay.grid_shape[1], lay.grid_shape[0])
        assert np.array_equal(bx[slots[:, 1], slots[:, 0]], coords[:, 0])
        assert np.array_equal(by[slots[:, 1], slots[:, 0]], coords[:, 1])
        sy, sx = ref.block_slots(r)
        assert np.array_equal(sy[by, bx], np.mgrid[0:bx.shape[0],
                                                   0:bx.shape[1]][0])


def test_membership_counts_the_gasket():
    for n in (1, 2, 8, 64):
        assert int(ref.membership_grid(n).sum()) == 3 ** (n.bit_length() - 1)


def test_diffusion_matches_the_programs_embedded_ca_run():
    from repro.kernels import ops
    s0 = _state()
    want = s0
    for _ in range(10):
        want = ref.ca_step_ref(want)
    got = ops.ca_run(s0, jnp.zeros_like(s0), 10, fuse=4, rule="diffusion",
                     block=BLOCK, grid_mode="closed_form", coarsen=1,
                     num_stages=1)
    assert float(jnp.max(jnp.abs(got - want))) <= ROUNDING


def test_diffusion_matches_the_programs_compact_ca_run():
    from repro.kernels import ops
    s0 = _state()
    want = s0
    for _ in range(8):
        want = ref.ca_step_ref(want)
    got = ops.ca_run(_pack(s0), jnp.zeros(_pack(s0).shape), 8, fuse=8,
                     rule="diffusion", block=BLOCK, grid_mode="closed_form",
                     storage="compact", n=N, coarsen=1, num_stages=1)
    assert np.max(np.abs(_unpack(got) - np.asarray(want))) <= ROUNDING


def test_write_matches_the_programs_compact_write():
    from repro.kernels import ops
    m = jax.random.uniform(jax.random.PRNGKey(1), (N, N))
    got = ops.sierpinski_write(_pack(m), 3.0, block=BLOCK,
                               grid_mode="closed_form", storage="compact",
                               n=N, coarsen=1, num_stages=1)
    want = ref.write_ref(m, 3.0)
    mask = np.repeat(np.repeat(ref.membership_grid(1 << R), BLOCK, 0),
                     BLOCK, 1)              # cells of stored blocks
    assert np.array_equal(_unpack(got)[mask], np.asarray(want)[mask])


@pytest.mark.parametrize("bx,by", [(0, 0), (0, 3), (3, 3), (1, 2)])
def test_window_reference_is_exact_inside_its_ring(bx, by):
    s0 = _state()
    want = s0
    steps = BLOCK
    for _ in range(steps):
        want = ref.ca_step_ref(want)
    pad = jnp.pad(s0, BLOCK)
    y0, x0 = (by - 1) * BLOCK, (bx - 1) * BLOCK
    win = pad[y0 + BLOCK:y0 + 4 * BLOCK, x0 + BLOCK:x0 + 4 * BLOCK]
    got = ref.diffusion_window(win, x0, y0, n=N, steps=steps, alpha=0.25)
    cell = np.s_[by * BLOCK:(by + 1) * BLOCK, bx * BLOCK:(bx + 1) * BLOCK]
    err = jnp.abs(got[BLOCK:2 * BLOCK, BLOCK:2 * BLOCK] - want[cell])
    assert float(jnp.max(err)) <= ROUNDING


TINY_PHI3 = {"program_arch": "phi3-mini-3.8b", "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "intermediate_size": 96, "num_hidden_layers": 2,
             "vocab_size": 200, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
             "torch_dtype": "float32"}


def test_phi3_forward_matches_the_programs_forward():
    from repro.models import model as model_lib
    cfg = serve_closed.program_config(TINY_PHI3,
                                      {"decode_kernel": "blockspace"})
    key = prng_key(2 ** 31 + 3)
    params = serve_closed.program_params(key, phi3.dims(TINY_PHI3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0, 200)
    with jax.default_matmul_precision("highest"):
        got, _ = model_lib.logits_fn(params, toks, cfg=cfg)
    want = phi3.logits(key, toks, TINY_PHI3)
    assert got.shape == want.shape
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-4, err


def test_program_weights_are_the_references_layer_by_layer():
    dm = phi3.dims(TINY_PHI3)
    key = prng_key(7)
    params = serve_closed.program_params(key, dm)
    blocks = params["blocks"]["slot_0"]
    for i in range(dm["layers"]):
        w = phi3.layer_weights(key, jnp.int32(i), dm)
        assert w["wq"].dtype == jnp.bfloat16
        assert jnp.array_equal(blocks["mixer"]["wq"][i], w["wq"])
        assert jnp.array_equal(blocks["ffn"]["wo"][i], w["wo_mlp"])
        assert jnp.array_equal(blocks["norm2"]["scale"][i], w["norm2"])


def test_fp8_rounds_coarser_than_bf16():
    w = jax.random.normal(jax.random.PRNGKey(0), (256, 64)) * 0.02

    def rel(v):
        return float(jnp.linalg.norm(v - w) / jnp.linalg.norm(w))
    assert phi3.fp8(w).dtype == jnp.float32
    assert rel(w.astype(jnp.bfloat16).astype(jnp.float32)) < 0.005 \
        < rel(phi3.fp8(w)) < 0.1
