"""Full-model tests: every family builds, trains one step, and decode
matches the full forward token-by-token."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (ModelConfig, decode_step, init, init_cache,
                          logits_fn, loss_fn, prefill)
from repro.models.model import group_layout

RNG = np.random.default_rng(17)

MINI = {
    "dense-localglobal": ModelConfig(
        name="dense-localglobal", n_layers=6, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=256,
        attn_pattern=("local", "local", "global"), local_window=8,
        qkv_bias=True, dtype="float32", param_dtype="float32", remat=False),
    "mla-moe": ModelConfig(
        name="mla-moe", family="moe", n_layers=5, d_model=64, n_heads=4,
        use_mla=True, q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
        qk_rope_dim=8, v_head_dim=16, d_ff=128, d_ff_expert=32, moe=True,
        n_experts=8, top_k=2, n_shared_experts=1, first_dense=1,
        capacity_factor=8.0, vocab_size=128, dtype="float32",
        param_dtype="float32", remat=False),
    "mamba1": ModelConfig(
        name="mamba1", family="ssm", n_layers=4, d_model=64,
        ssm_kind="mamba1", d_state=8, expand=2, conv_kernel=4, ssd_chunk=8,
        d_ff=0, vocab_size=128, dtype="float32", param_dtype="float32",
        remat=False),
    "zamba-hybrid": ModelConfig(
        name="zamba-hybrid", family="hybrid", n_layers=4, d_model=64,
        n_heads=4, n_kv_heads=4, ssm_kind="mamba2", d_state=16,
        ssd_head_dim=16, ssd_chunk=8, expand=2, conv_kernel=4,
        hybrid_attn_period=2, d_ff=128, vocab_size=128, dtype="float32",
        param_dtype="float32", remat=False),
    "moe-interleaved": ModelConfig(
        name="moe-interleaved", family="moe", n_layers=4, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, d_ff_expert=64, moe=True,
        n_experts=4, top_k=1, n_shared_experts=1, moe_period=2,
        moe_offset=1, capacity_factor=8.0, vocab_size=128, dtype="float32",
        param_dtype="float32", remat=False),
    "embeddings-input": ModelConfig(
        name="embeddings-input", family="audio", n_layers=3, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=64,
        input_mode="embeddings", dtype="float32", param_dtype="float32",
        remat=False),
}


def _batch(cfg, b=2, s=16):
    if cfg.input_mode == "embeddings":
        inputs = jnp.asarray(RNG.normal(size=(b, s, cfg.d_model)),
                             jnp.float32)
    else:
        inputs = jnp.asarray(RNG.integers(0, cfg.vocab_size, (b, s)))
    labels = jnp.asarray(RNG.integers(0, cfg.vocab_size, (b, s)))
    return {"inputs": inputs, "labels": labels}


@pytest.mark.parametrize("name", list(MINI))
def test_loss_and_grads(name):
    cfg = MINI[name]
    p = init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    loss, metrics = loss_fn(p, batch, cfg)
    assert np.isfinite(float(loss))
    g = jax.grad(lambda p: loss_fn(p, batch, cfg)[0])(p)
    assert all(np.isfinite(np.asarray(v)).all() for v in jax.tree.leaves(g))


@pytest.mark.parametrize("name", list(MINI))
def test_decode_matches_forward(name):
    cfg = MINI[name]
    p = init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    inputs = batch["inputs"]
    b, s = inputs.shape[0], inputs.shape[1]
    logits_all, _ = logits_fn(p, inputs, cfg)
    cache = init_cache(cfg, b, s)
    lg = []
    for t in range(s):
        inp = inputs[:, t:t + 1] if cfg.input_mode == "tokens" \
            else inputs[:, t:t + 1, :]
        l, cache = decode_step(p, inp, cache, t, cfg)
        lg.append(l)
    np.testing.assert_allclose(jnp.concatenate(lg, 1), logits_all,
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("name", ["dense-localglobal", "mamba1"])
def test_prefill_last_logits(name):
    cfg = MINI[name]
    p = init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    logits_all, _ = logits_fn(p, batch["inputs"], cfg)
    pl, caches = prefill(p, batch["inputs"], cfg)
    np.testing.assert_allclose(pl, logits_all[:, -1:], rtol=2e-4, atol=2e-4)


def test_remat_matches_no_remat():
    cfg = MINI["dense-localglobal"]
    p = init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    l1, _ = loss_fn(p, batch, cfg)
    l2, _ = loss_fn(p, batch, cfg.replace(remat=True))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_chunked_ce_matches_full():
    cfg = MINI["dense-localglobal"]
    p = init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    l1, _ = loss_fn(p, batch, cfg)
    l2, _ = loss_fn(p, batch, cfg.replace(logit_chunk=4))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_attn_schedule_equivalence_end_to_end():
    # compact (triangular) vs bounding-box (dense) schedule: same loss
    cfg = MINI["dense-localglobal"].replace(flash_threshold=8,
                                            attn_chunk=8)
    p = init(jax.random.PRNGKey(0), cfg)
    batch = _batch(cfg)
    l1, _ = loss_fn(p, batch, cfg.replace(attn_schedule="dense"))
    l2, _ = loss_fn(p, batch, cfg.replace(attn_schedule="triangular"))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_group_layout_covers_all_layers():
    for cfg in MINI.values():
        prefix, period, n_groups = group_layout(cfg)
        assert prefix + period * n_groups == cfg.n_layers


def test_param_count_analytic_close_to_actual():
    cfg = MINI["dense-localglobal"]
    p = init(jax.random.PRNGKey(0), cfg)
    actual = sum(np.prod(l.shape) for l in jax.tree.leaves(p))
    analytic = cfg.param_count()
    assert abs(actual - analytic) / actual < 0.05


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "deepseek-v2-236b",
                                  "falcon-mamba-7b", "zamba2-2.7b"])
def test_param_leaves_honour_param_dtype(arch):
    """bf16 weights stay bf16: a numpy f64 init scale must not promote
    them (phi3-mini at published widths would then need 15 GB of f32
    weights and not fit a 16 GB chip).  MoE routers alone are f32, by
    design: routing scores are taken in f32."""
    from repro.configs import get_config
    from repro.models.model import abstract_init
    cfg = get_config(arch, smoke=True).replace(param_dtype="bfloat16")
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            abstract_init(cfg)):
        name = jax.tree_util.keystr(path)
        want = jnp.float32 if name.endswith("['router']") else jnp.bfloat16
        assert leaf.dtype == want, (name, leaf.dtype)
