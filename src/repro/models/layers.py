"""Shared building blocks: norms, RoPE, SwiGLU MLP, GQA attention block,
embeddings, and initialization helpers.

Parameters are plain pytrees (nested dicts of jnp arrays).  Every init
function takes an explicit PRNG key and returns (params, None); shapes
are kept in one place so the sharding rules in repro.distributed can be
name-pattern based.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain
from . import attention as attn_lib


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    # the numpy f64 scale would promote a bf16 draw to f32
    return (jax.random.normal(key, (d_in, d_out), dtype) * scale
            ).astype(dtype)


def split(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d, dtype):
    return {"scale": jnp.ones((d,), dtype)}

def rmsnorm(params, x, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (rotate-half convention), optionally YaRN-scaled
# ---------------------------------------------------------------------------

def yarn_get_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention factor 0.1 * mscale * ln(factor) + 1 (1 at
    factor <= 1)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, yarn) -> np.ndarray:
    """(d/2,) float32 inverse frequencies of YaRN (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``): the plain frequencies above the
    correction range, those divided by ``factor`` below it, a linear
    ramp between.  ``yarn`` is ``ModelConfig.yarn``."""
    factor, orig, beta_fast, beta_slow = yarn[:4]

    def corr_dim(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    expo = np.arange(0, d, 2, dtype=np.float32) / d
    extra = 1.0 / theta ** expo
    inter = 1.0 / (factor * theta ** expo)
    ramp = np.clip((np.arange(d // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def _rope_tables(d, theta, yarn):
    """(inverse frequencies (d/2,), the cos/sin scale)."""
    if yarn is None:
        return (theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32)
                          / (d // 2)), None)
    factor, _, _, _, mscale, mscale_all = yarn
    att = yarn_get_mscale(factor, mscale) / yarn_get_mscale(factor,
                                                            mscale_all)
    return jnp.asarray(yarn_inv_freq(d, theta, yarn)), (
        None if att == 1.0 else att)


def _rotate(x, cos, sin):
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def rope(x, positions, theta=10000.0, yarn=None):
    """x: (B,H,S,D) with even D; positions: (S,) int; ``yarn``: the
    config's YaRN tuple, or None for plain rope."""
    freqs, att = _rope_tables(x.shape[-1], theta, yarn)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, None]        # (1,1,S,D/2)
    sin = jnp.sin(angles)[None, None]
    if att is not None:
        cos, sin = cos * att, sin * att
    return _rotate(x, cos, sin)


def rope_rows(x, positions, theta=10000.0, yarn=None):
    """Per-batch-row RoPE for single-token decode: x (B,H,1,D) with even
    D; positions (B,) int, one decode position per slot.  Equals
    :func:`rope` broadcast when every row sits at the same position
    (same elementwise ops, so bitwise equal)."""
    freqs, att = _rope_tables(x.shape[-1], theta, yarn)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[:, None, None, :]      # (B,1,1,D/2)
    sin = jnp.sin(angles)[:, None, None, :]
    if att is not None:
        cos, sin = cos * att, sin * att
    return _rotate(x, cos, sin)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d, f, dtype):
    k1, k2, k3 = split(key, 3)
    return {"wi": dense_init(k1, d, f, dtype),
            "wg": dense_init(k2, d, f, dtype),
            "wo": dense_init(k3, f, d, dtype, scale=1.0 / np.sqrt(f))}

def mlp(params, x, megatron_sp=False):
    h = jax.nn.silu(x @ params["wg"].astype(x.dtype)) * (
        x @ params["wi"].astype(x.dtype))
    if megatron_sp:
        # pin the hidden to TP-sharded: XLA must gather activations
        # (small) instead of the F-sharded weights (big)
        h = constrain(h, "mlp_hidden")
    return h @ params["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def attn_init(key, cfg, dtype=None):
    dtype = dtype or cfg.jparam_dtype()
    d, hd = cfg.d_model, cfg.hd
    hq, hkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    k1, k2, k3, k4 = split(key, 4)
    p = {"wq": dense_init(k1, d, hq, dtype),
         "wk": dense_init(k2, d, hkv, dtype),
         "wv": dense_init(k3, d, hkv, dtype),
         "wo": dense_init(k4, hq, d, dtype, scale=1.0 / np.sqrt(hq))}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq,), dtype)
        p["bk"] = jnp.zeros((hkv,), dtype)
        p["bv"] = jnp.zeros((hkv,), dtype)
    return p


def _qkv(params, x, cfg):
    b, s, _ = x.shape
    hd = cfg.hd
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if cfg.megatron_sp:
        q = constrain(q, "attn_heads")
        k = constrain(k, "attn_heads")
        v = constrain(v, "attn_heads")
    return q, k, v


def attn_block(params, x, cfg, kind, positions):
    """Self-attention over the full sequence (train / prefill, no cache)."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    o = attn_lib.attention(
        q, k, v, kind=("local" if kind == "local" else "causal"),
        window=cfg.local_window, chunk=cfg.attn_chunk,
        schedule=cfg.attn_schedule_resolved, flash_threshold=cfg.flash_threshold)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    return o @ params["wo"].astype(x.dtype)


def attn_block_prefill(params, x, cfg, kind, positions):
    """Like attn_block but also returns the (k, v) cache."""
    b, s, _ = x.shape
    q, k, v = _qkv(params, x, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    o = attn_lib.attention(
        q, k, v, kind=("local" if kind == "local" else "causal"),
        window=cfg.local_window, chunk=cfg.attn_chunk,
        schedule=cfg.attn_schedule_resolved, flash_threshold=cfg.flash_threshold)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    return o @ params["wo"].astype(x.dtype), (k, v)


def attn_block_decode(params, x, cfg, kind, cache, pos):
    """One-token step.  cache: (k, v) each (B,Hkv,Smax,hd); pos: ()."""
    b, s, _ = x.shape  # s == 1
    q, k_new, v_new = _qkv(params, x, cfg)
    posv = jnp.full((1,), pos, jnp.int32)
    q = rope(q, posv, cfg.rope_theta)
    k_new = rope(k_new, posv, cfg.rope_theta)
    k_cache, v_cache = cache
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), pos, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), pos, axis=2)
    decode = (attn_lib.decode_attention_flash
              if cfg.attn_decode_kernel == "blockspace"
              else attn_lib.decode_attention)
    o = decode(
        q, k_cache, v_cache, pos,
        kind=("local" if kind == "local" else "causal"),
        window=cfg.local_window)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * cfg.hd)
    return o @ params["wo"].astype(x.dtype), (k_cache, v_cache)


def attn_block_decode_paged(params, x, cfg, kind, pool, page_table, pos,
                            active=None):
    """One-token step against a paged fused-KV pool (continuous
    batching: every slot at its own position).

    pool: (P, 2*Hkv, page_size, hd) head-interleaved pages
    (:mod:`repro.core.paged`); page_table: (B, max_pages) i32; pos: (B,)
    per-slot decode positions; active: optional (B,) bool -- inactive
    slots write their new KV to the null page and their outputs are
    garbage the scheduler must ignore.  Returns (out, updated pool)."""
    from repro.core import paged as paged_lib

    b, s, _ = x.shape  # s == 1
    q, k_new, v_new = _qkv(params, x, cfg)
    q = rope_rows(q, pos, cfg.rope_theta)
    k_new = rope_rows(k_new, pos, cfg.rope_theta)
    pool = paged_lib.append_token(pool, page_table, pos, k_new, v_new,
                                  active)
    decode = (attn_lib.decode_attention_paged
              if cfg.attn_decode_kernel == "blockspace"
              else attn_lib.decode_attention_paged_xla)
    o = decode(q, pool, page_table, pos,
               window=(cfg.local_window if kind == "local" else 0))
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * cfg.hd)
    return o @ params["wo"].astype(x.dtype), pool


# ---------------------------------------------------------------------------
# embedding / lm head
# ---------------------------------------------------------------------------

def embed_init(key, vocab, d, dtype):
    return {"table": jax.random.normal(key, (vocab, d), dtype) * 0.01}

def embed(params, tokens, dtype):
    return params["table"].astype(dtype)[tokens]

def lm_head_init(key, d, vocab, dtype):
    return {"w": dense_init(key, d, vocab, dtype)}

def lm_head(params, x):
    return x @ params["w"].astype(x.dtype)
