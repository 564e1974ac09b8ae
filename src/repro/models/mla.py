"""Multi-head Latent Attention (DeepSeek-V2): low-rank compressed KV with
decoupled RoPE key, plus the absorbed-matmul decode path that attends
directly over the compressed cache (the reason MLA caches are ~512+64
floats per token instead of 2 * H * hd).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as attn_lib
from .layers import dense_init, rmsnorm, rope, rope_rows, split


def mla_init(key, cfg, dtype=None):
    dtype = dtype or cfg.jparam_dtype()
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = split(key, 6)
    p = {}
    if cfg.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], d, cfg.q_lora_rank, dtype)
        p["q_norm"] = jnp.ones((cfg.q_lora_rank,), dtype)
        p["wq_b"] = dense_init(ks[1], cfg.q_lora_rank, h * (dn + dr), dtype)
    else:
        p["wq"] = dense_init(ks[0], d, h * (dn + dr), dtype)
    p["wkv_a"] = dense_init(ks[2], d, cfg.kv_lora_rank + dr, dtype)
    p["kv_norm"] = jnp.ones((cfg.kv_lora_rank,), dtype)
    p["wkv_b"] = dense_init(ks[3], cfg.kv_lora_rank, h * (dn + dv), dtype)
    p["wo"] = dense_init(ks[4], h * dv, d, dtype,
                         scale=1.0 / np.sqrt(h * dv))
    return p


def _queries(p, x, cfg, positions, rows=False):
    """(q_nope, q_rope), each (B,H,S,*); ``rows``: ``positions`` is a
    (B,) vector, one decode position per row (S == 1)."""
    b, s, _ = x.shape
    h, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    if cfg.q_lora_rank:
        cq = rmsnorm({"scale": p["q_norm"]},
                     x @ p["wq_a"].astype(x.dtype), cfg.norm_eps)
        q = cq @ p["wq_b"].astype(x.dtype)
    else:
        q = x @ p["wq"].astype(x.dtype)
    q = q.reshape(b, s, h, dn + dr).transpose(0, 2, 1, 3)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = (rope_rows if rows else rope)(q_rope, positions,
                                           cfg.rope_theta, cfg.yarn)
    return q_nope, q_rope


def _latents(p, x, cfg, positions, rows=False):
    """Compressed kv latent + roped shared key.  c_kv: (B,S,L); k_rope
    (B,1,S,dr)."""
    kv_a = x @ p["wkv_a"].astype(x.dtype)
    c_kv, k_rope = kv_a[..., :cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank:]
    c_kv = rmsnorm({"scale": p["kv_norm"]}, c_kv, cfg.norm_eps)
    k_rope = (rope_rows if rows else rope)(
        k_rope[:, None], positions, cfg.rope_theta, cfg.yarn)  # (B,1,S,dr)
    return c_kv, k_rope


def mla_block(p, x, cfg, positions, *, return_cache=False):
    """Train/prefill: materialize per-head K/V from the latent."""
    b, s, _ = x.shape
    h, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    q_nope, q_rope = _queries(p, x, cfg, positions)
    c_kv, k_rope = _latents(p, x, cfg, positions)

    kv = c_kv @ p["wkv_b"].astype(x.dtype)
    kv = kv.reshape(b, s, h, dn + dv).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, h, s, dr))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    o = attn_lib.attention(
        q, k, v, kind="causal", scale=cfg.mla_softmax_scale,
        chunk=cfg.attn_chunk, schedule=cfg.attn_schedule_resolved,
        flash_threshold=cfg.flash_threshold)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
    out = o @ p["wo"].astype(x.dtype)
    if return_cache:
        return out, (c_kv, k_rope[:, 0])
    return out


def mla_decode(p, x, cfg, cache, pos):
    """Absorbed decode: scores = (q_nope W_uk) c_kv^T + q_rope k_rope^T.
    cache: (c_kv (B,Smax,L), k_rope (B,Smax,dr))."""
    b = x.shape[0]
    h, dn, dr, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    L = cfg.kv_lora_rank
    posv = jnp.full((1,), pos, jnp.int32)
    q_nope, q_rope = _queries(p, x, cfg, posv)       # (B,H,1,dn/dr)
    c_new, kr_new = _latents(p, x, cfg, posv)        # (B,1,L), (B,1,1,dr)

    c_cache, r_cache = cache
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        c_cache, c_new.astype(c_cache.dtype), pos, axis=1)
    r_cache = jax.lax.dynamic_update_slice_in_dim(
        r_cache, kr_new[:, 0].astype(r_cache.dtype), pos, axis=1)

    wkv_b = p["wkv_b"].astype(x.dtype).reshape(L, h, dn + dv)
    w_uk, w_uv = wkv_b[..., :dn], wkv_b[..., dn:]
    # absorb W_uk into q:  (B,H,1,dn) x (L,H,dn) -> (B,H,1,L)
    q_abs = jnp.einsum("bhqd,lhd->bhql", q_nope, w_uk)
    s = jnp.einsum("bhql,bsl->bhqs", q_abs.astype(jnp.float32),
                   c_cache.astype(jnp.float32))
    s += jnp.einsum("bhqd,bsd->bhqs", q_rope.astype(jnp.float32),
                    r_cache.astype(jnp.float32))
    s *= cfg.mla_softmax_scale
    kpos = jnp.arange(c_cache.shape[1])[None, None, None, :]
    s = jnp.where(kpos <= pos, s, attn_lib.NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqs,bsl->bhql", pr.astype(c_cache.dtype), c_cache)
    o = jnp.einsum("bhql,lhd->bhqd", ctx, w_uv)      # (B,H,1,dv)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * dv)
    return o @ p["wo"].astype(x.dtype), (c_cache, r_cache)


def _absorbed_query(p, x, cfg, pos):
    """(B,H,L+dr) decode queries at per-slot positions ``pos`` (B,):
    ``q_nope W_uk`` beside the roped part, one row per head."""
    h, dn = cfg.n_heads, cfg.qk_nope_dim
    q_nope, q_rope = _queries(p, x, cfg, pos, rows=True)  # (B,H,1,dn/dr)
    w_uk = p["wkv_b"].astype(x.dtype).reshape(
        cfg.kv_lora_rank, h, dn + cfg.v_head_dim)[..., :dn]
    q_abs = jnp.einsum("bhqd,lhd->bhql", q_nope, w_uk)
    return jnp.concatenate([q_abs, q_rope.astype(q_abs.dtype)],
                           axis=-1)[:, :, 0]


def mla_decode_paged(p, x, cfg, pool, page_table, pos, active=None):
    """Absorbed decode of one token per slot against a paged latent pool
    (:func:`repro.core.paged.init_latent_pool`), every slot at its own
    position.  x: (B,1,D); pool: (P, page_size, lanes), rows L+dr wide
    in the first lanes; page_table: (B,
    max_pages); pos: (B,); inactive slots write to the null page.  The
    attention runs in the ``paged_latent_decode`` kernel, or over the
    gathered latents in XLA when ``cfg.attn_decode_kernel == "xla"``.
    Returns (out (B,1,D), updated pool)."""
    from repro.core import paged as paged_lib
    from repro.kernels import latent_decode

    b = x.shape[0]
    h, dv, L = cfg.n_heads, cfg.v_head_dim, cfg.kv_lora_rank
    q = _absorbed_query(p, x, cfg, pos)
    c_new, kr_new = _latents(p, x, cfg, pos, rows=True)
    row = jnp.concatenate([c_new[:, 0], kr_new[:, 0, 0].astype(
        c_new.dtype)], axis=-1)                           # (B, L+dr)
    pool = paged_lib.append_latent(pool, page_table, pos, row, active)
    decode = (latent_decode.paged_latent_decode
              if cfg.attn_decode_kernel == "blockspace"
              else latent_decode.latent_decode_xla)
    ctx = decode(q, pool, page_table, pos, scale=cfg.mla_softmax_scale,
                 v_dim=L)                                 # (B,H,L)
    w_uv = p["wkv_b"].astype(x.dtype).reshape(
        L, h, cfg.qk_nope_dim + dv)[..., cfg.qk_nope_dim:]
    o = jnp.einsum("bhl,lhd->bhd", ctx.astype(x.dtype), w_uv)
    return o.reshape(b, 1, h * dv) @ p["wo"].astype(x.dtype), pool
