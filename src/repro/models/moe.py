"""Mixture-of-Experts FFN with top-k routing, shared experts, and
capacity-bounded sort-based dispatch (gather/scatter, NOT the GShard
one-hot-einsum dispatch whose FLOPs would dwarf the expert matmuls).

Dispatch: every (token, slot) pair is ranked within its expert queue via
an argsort of the flat expert assignment; ranks >= capacity are dropped
(their gate mass is simply lost, standard "token dropping").  Tokens are
scattered into an (E*C, D) buffer, experts run as one batched SwiGLU
matmul (E, C, D) x (E, D, F), and results are gathered back weighted by
the top-k gates (renormalized under ``norm_topk_prob``).

Expert parallelism: the (E, ...) expert weights shard over the "model"
(and optionally "data") mesh axes; XLA turns the scatter/gather into the
dispatch collectives.

The held-experts layer (:func:`moe_block_held`, chosen by
``cfg.experts_held``) is the one-chip share of an expert-parallel
deployment: the router keeps its published width and top-k, the chip
holds the routed experts ``[experts_first, experts_first +
experts_held)`` and computes, with no token dropped, only the routes
that land on them (``jax.lax.ragged_dot`` over the routes sorted by
expert), plus the shared experts.  What the other chips' experts would
add is not there.
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
    e = cfg.n_experts_local
    ks = split(key, 5)
    scale_in = 1.0 / np.sqrt(d)
    scale_out = 1.0 / np.sqrt(fe)
    p = {
        "router": dense_init(ks[0], d, cfg.n_experts, jnp.float32),
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


def route(xf, router, cfg):
    """Softmax scores over all ``n_experts`` and their greedy top-k:
    (probs (N,E) f32, gates (N,k) f32, expert ids (N,k)).  Gates are
    renormalised to sum 1 only under ``cfg.norm_topk_prob``, then scaled
    by ``routed_scaling_factor``."""
    if cfg.topk_method != "greedy":
        raise NotImplementedError(
            f"MoE routing {cfg.topk_method!r} is not implemented (only "
            f"'greedy' top-k): refusing to route with another method")
    logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    if cfg.routed_scaling_factor != 1.0:
        gates = gates * cfg.routed_scaling_factor
    return probs, gates, idx


def _shared(p, xf):
    sp = p["shared"]
    hsh = jax.nn.silu(xf @ sp["wg"].astype(xf.dtype)) * (
        xf @ sp["wi"].astype(xf.dtype))
    return hsh @ sp["wo"].astype(xf.dtype)


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

    probs, gates, idx = route(xf, p["router"], cfg)             # (N,E)

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
        out = out + _shared(p, xf)
    return out.reshape(b, s, d), aux * cfg.router_aux_weight


def held_routes(idx, cfg):
    """Where each (token, slot) route lands among the held experts: the
    local expert id (N,k) and whether it is held (N,k) bool."""
    local = idx - cfg.experts_first
    held = (local >= 0) & (local < cfg.n_experts_local)
    return local, held


def moe_block_held(p, x, cfg):
    """The held-experts layer: x (B,S,D) -> (out (B,S,D), loads (E_held,)
    int32, the routes each held expert computed).

    Every token is routed over all ``n_experts``; the routes to held
    experts are sorted by expert and computed exactly, none dropped
    (``ragged_dot`` over the sorted rows, the other routes sorted last
    and left out); the shared experts are added for every token."""
    b, s, d = x.shape
    n, k, e = b * s, cfg.top_k, cfg.n_experts_local
    xf = x.reshape(n, d)
    _, gates, idx = route(xf, p["router"], cfg)
    local, held = held_routes(idx, cfg)
    flat = jnp.where(held, local, e).reshape(-1)                # (N*k,)
    order = jnp.argsort(flat, stable=True)
    loads = jnp.bincount(flat, length=e + 1)[:e].astype(jnp.int32)
    rows = xf[order // k]                                       # (N*k,D)

    def ffn(w, a):
        return jax.lax.ragged_dot(a, p[w].astype(x.dtype), loads)

    hid = jax.nn.silu(ffn("wg", rows)) * ffn("wi", rows)
    y = ffn("wo", hid)                                          # (N*k,D)
    weight = gates.reshape(-1)[order]
    y = jnp.where((flat[order] < e)[:, None],
                  y.astype(jnp.float32) * weight[:, None], 0)
    out = jnp.zeros((n, d), jnp.float32).at[order // k].add(y)
    out = out.astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + _shared(p, xf)
    return out.reshape(b, s, d), loads


def moe_block_dense_ref(p, x, cfg):
    """Oracle: compute every held expert for every token, combine with
    the same top-k gates (renormalised under ``norm_topk_prob``), no
    capacity dropping; routes to experts not held add nothing.  O(E)
    FLOPs -- tests only."""
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    _, gates, idx = route(xf, p["router"], cfg)
    local, held = held_routes(idx, cfg)
    gfull = jnp.zeros((n, cfg.n_experts_local + 1), jnp.float32)
    gfull = gfull.at[jnp.arange(n)[:, None],
                     jnp.where(held, local, cfg.n_experts_local)].add(
                         gates)[:, :-1]
    hg = jax.nn.silu(jnp.einsum("nd,edf->nef", xf, p["wg"].astype(x.dtype)))
    hu = jnp.einsum("nd,edf->nef", xf, p["wi"].astype(x.dtype))
    ye = jnp.einsum("ned,ne->nd", jnp.einsum(
        "nef,efd->ned", hg * hu, p["wo"].astype(x.dtype)).astype(
            jnp.float32), gfull)
    out = ye.astype(x.dtype)
    if cfg.n_shared_experts:
        out = out + _shared(p, xf)
    return out.reshape(b, s, d)
