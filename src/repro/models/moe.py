"""Mixture-of-Experts FFN with top-k routing, shared experts, and
capacity-bounded sort-based dispatch (gather/scatter, NOT the GShard
one-hot-einsum dispatch whose FLOPs would dwarf the expert matmuls).

Dispatch: every (token, slot) pair is ranked within its expert queue via
an argsort of the flat expert assignment; ranks >= capacity are dropped
(their gate mass is simply lost, standard "token dropping").  Tokens are
scattered into an (E*C, D) buffer, experts run as one batched SwiGLU
matmul (E, C, D) x (E, D, F), and results are gathered back weighted by
the (renormalized) top-k gates.

Expert parallelism: the (E, ...) expert weights shard over the "model"
(and optionally "data") mesh axes; XLA turns the scatter/gather into the
dispatch collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain
from .layers import dense_init, split


def moe_init(key, cfg, dtype=None):
    dtype = dtype or cfg.jparam_dtype()
    d = cfg.d_model
    fe = cfg.d_ff_expert or cfg.d_ff
    e = cfg.n_experts
    ks = split(key, 5)
    scale_in = 1.0 / np.sqrt(d)
    scale_out = 1.0 / np.sqrt(fe)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "wi": (jax.random.normal(ks[1], (e, d, fe), dtype)
               * scale_in).astype(dtype),
        "wg": (jax.random.normal(ks[2], (e, d, fe), dtype)
               * scale_in).astype(dtype),
        "wo": (jax.random.normal(ks[3], (e, fe, d), dtype)
               * scale_out).astype(dtype),
    }
    if cfg.n_shared_experts:
        fs = fe * cfg.n_shared_experts
        kk = split(ks[4], 3)
        p["shared"] = {"wi": dense_init(kk[0], d, fs, dtype),
                       "wg": dense_init(kk[1], d, fs, dtype),
                       "wo": dense_init(kk[2], fs, d, dtype,
                                        scale=1.0 / np.sqrt(fs))}
    return p


def _capacity(n_tokens: int, cfg) -> int:
    c = int(np.ceil(cfg.capacity_factor * n_tokens * cfg.top_k
                    / cfg.n_experts))
    return max(8, -(-c // 8) * 8)  # pad to multiple of 8


def moe_block(p, x, cfg):
    """x: (B,S,D) -> (out (B,S,D), aux_loss ())."""
    b, s, d = x.shape
    n = b * s
    k = cfg.top_k
    e = cfg.n_experts
    cap = _capacity(n, cfg)
    xf = x.reshape(n, d)
    xf = constrain(xf, "moe_tokens")

    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (N,E)
    gates, idx = jax.lax.top_k(probs, k)                        # (N,k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)

    # load-balance aux loss (Switch): E * mean(frac_tokens * frac_probs)
    me = jnp.mean(probs, axis=0)
    one_hot_top1 = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    ce = jnp.mean(one_hot_top1, axis=0)
    aux = e * jnp.sum(me * ce)

    # --- sort-based within-expert ranking --------------------------------
    flat_e = idx.reshape(-1)                                    # (N*k,)
    sort_i = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_i]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank_sorted = jnp.arange(n * k) - starts[sorted_e]
    rank = jnp.zeros((n * k,), jnp.int32).at[sort_i].set(
        rank_sorted.astype(jnp.int32))
    keep = rank < cap
    slot = jnp.where(keep, flat_e * cap + rank, e * cap)        # drop slot

    # --- dispatch ---------------------------------------------------------
    token_id = jnp.repeat(jnp.arange(n), k)
    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[slot].add(xf[token_id], mode="drop",
                           unique_indices=False)
    he = buf[:e * cap].reshape(e, cap, d)
    he = constrain(he, "moe_experts")  # expert-major over 'model' (EP)

    # --- expert SwiGLU ----------------------------------------------------
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", he,
                                  p["wg"].astype(x.dtype)))
    up = jnp.einsum("ecd,edf->ecf", he, p["wi"].astype(x.dtype))
    y = jnp.einsum("ecf,efd->ecd", gate * up, p["wo"].astype(x.dtype))
    y = constrain(y, "moe_experts")
    y = y.reshape(e * cap, d)
    y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)], axis=0)

    # --- combine ----------------------------------------------------------
    ys = y[slot] * (gates.reshape(-1)[:, None].astype(y.dtype)
                    * keep[:, None])
    ys = constrain(ys, "moe_tokens")
    out = jnp.sum(ys.reshape(n, k, d), axis=1)
    out = constrain(out, "moe_tokens")

    if cfg.n_shared_experts:
        sp = p["shared"]
        hsh = jax.nn.silu(xf @ sp["wg"].astype(x.dtype)) * (
            xf @ sp["wi"].astype(x.dtype))
        out = out + hsh @ sp["wo"].astype(x.dtype)
    return out.reshape(b, s, d), aux * cfg.router_aux_weight


def moe_block_dense_ref(p, x, cfg):
    """Oracle: compute ALL experts for every token, combine with the same
    top-k renormalized gates, no capacity dropping.  O(E) FLOPs -- tests
    only."""
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    gate_full = jnp.zeros_like(probs)
    gate_full = jnp.take_along_axis(
        gate_full, idx, axis=1) * 0  # noop to keep shapes clear
    gfull = jnp.zeros((n, cfg.n_experts), jnp.float32)
    gfull = gfull.at[jnp.arange(n)[:, None], idx].set(gates)
    hg = jax.nn.silu(jnp.einsum("nd,edf->nef", xf, p["wg"].astype(x.dtype)))
    hu = jnp.einsum("nd,edf->nef", xf, p["wi"].astype(x.dtype))
    ye = jnp.einsum("nef,efd->ned", hg * hu, p["wo"].astype(x.dtype))
    out = jnp.einsum("ned,ne->nd", ye.astype(jnp.float32), gfull)
    out = out.astype(x.dtype)
    if cfg.n_shared_experts:
        sp = p["shared"]
        hsh = jax.nn.silu(xf @ sp["wg"].astype(x.dtype)) * (
            xf @ sp["wi"].astype(x.dtype))
        out = out + hsh @ sp["wo"].astype(x.dtype)
    return out.reshape(b, s, d)
