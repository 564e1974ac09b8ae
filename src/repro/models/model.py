"""Model assembly: embeddings -> scanned layer groups -> head.

Layers are grouped by their repeating signature (attention pattern,
MoE period, hybrid shared-attention period) and executed with
``lax.scan`` over stacked parameters -- one traced body per
architecture regardless of depth (compile-time matters: 40 dry-run
cells x 2 meshes).  A non-scanned prefix covers e.g. DeepSeek's
first-dense layer.

Three entry points per architecture:
  * ``loss_fn``     -- train forward + chunked cross-entropy
  * ``prefill``     -- forward returning per-layer caches + last logits
  * ``decode_step`` -- one token through all layers with cache update

Cache pytrees mirror the parameter layout ({prefix_i, blocks.slot_s})
so the same scan drives both.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from . import mla as mla_lib
from . import moe as moe_lib
from . import ssm as ssm_lib
from .config import ModelConfig
from repro.distributed.sharding import constrain


# ---------------------------------------------------------------------------
# layer signatures and grouping
# ---------------------------------------------------------------------------

def layer_sig(cfg: ModelConfig, i: int) -> Tuple[str, str, str, bool]:
    mixer = cfg.layer_mixer(i)
    akind = cfg.attn_kind(i) if mixer in ("attn", "mla") else ""
    ffn = cfg.layer_ffn(i) if cfg.d_ff or cfg.moe else "none"
    if cfg.family == "hybrid":
        ffn = "none"  # zamba-style: MLP lives in the shared block
    return (mixer, akind, ffn, cfg.has_shared_attn(i))


def _lcm(*xs):
    out = 1
    for x in xs:
        out = math.lcm(out, max(1, x))
    return out


def group_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """Returns (prefix_len, period, n_groups); prefix layers are unscanned."""
    period = _lcm(len(cfg.attn_pattern) if cfg.ssm_kind is None else 1,
                  cfg.moe_period if cfg.moe else 1,
                  cfg.hybrid_attn_period or 1)
    prefix = cfg.first_dense
    rest = cfg.n_layers - prefix
    if rest % period:
        prefix += rest % period
        rest = cfg.n_layers - prefix
    # slot signatures must not depend on the group index
    for s in range(period):
        sigs = {layer_sig(cfg, prefix + g * period + s)
                for g in range(rest // period)}
        assert len(sigs) <= 1, f"slot {s} not scan-invariant: {sigs}"
    return prefix, period, rest // period


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------

def layer_init(key, cfg: ModelConfig, i: int):
    mixer, akind, ffn, shared = layer_sig(cfg, i)
    ks = L.split(key, 4)
    p: Dict[str, Any] = {"norm1": L.rmsnorm_init(cfg.d_model,
                                                 cfg.jparam_dtype())}
    if mixer == "attn":
        p["mixer"] = L.attn_init(ks[0], cfg)
    elif mixer == "mla":
        p["mixer"] = mla_lib.mla_init(ks[0], cfg)
    elif mixer == "mamba1":
        p["mixer"] = ssm_lib.mamba1_init(ks[0], cfg)
    elif mixer == "mamba2":
        p["mixer"] = ssm_lib.mamba2_init(ks[0], cfg)
    if ffn != "none":
        p["norm2"] = L.rmsnorm_init(cfg.d_model, cfg.jparam_dtype())
        if ffn == "dense":
            p["ffn"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff,
                                  cfg.jparam_dtype())
        else:
            p["ffn"] = moe_lib.moe_init(ks[1], cfg)
    return p


def shared_attn_init(key, cfg: ModelConfig):
    """Zamba-style weight-shared attention+MLP block (simplified: single
    shared block, concat with the initial embedding, no LoRA adapters)."""
    ks = L.split(key, 4)
    return {
        "in_proj": L.dense_init(ks[0], 2 * cfg.d_model, cfg.d_model,
                                cfg.jparam_dtype()),
        "norm1": L.rmsnorm_init(cfg.d_model, cfg.jparam_dtype()),
        "attn": L.attn_init(ks[1], cfg),
        "norm2": L.rmsnorm_init(cfg.d_model, cfg.jparam_dtype()),
        "mlp": L.mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.jparam_dtype()),
    }


def _shared_block(sp, h, h0, cfg, positions, mode, cache=None, pos=None):
    u = jnp.concatenate([h, h0], axis=-1) @ sp["in_proj"].astype(h.dtype)
    un = L.rmsnorm(sp["norm1"], u, cfg.norm_eps)
    new_cache = None
    if mode == "train":
        a = L.attn_block(sp["attn"], un, cfg, "global", positions)
    elif mode == "prefill":
        a, new_cache = L.attn_block_prefill(sp["attn"], un, cfg, "global",
                                            positions)
    else:
        a, new_cache = L.attn_block_decode(sp["attn"], un, cfg, "global",
                                           cache, pos)
    u = u + a
    u = u + L.mlp(sp["mlp"], L.rmsnorm(sp["norm2"], u, cfg.norm_eps),
                  megatron_sp=cfg.megatron_sp)
    return h + u, new_cache


def _pad_seq(x, axis, max_len):
    if max_len is None or x.shape[axis] >= max_len:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, max_len - x.shape[axis])
    return jnp.pad(x, pad)


def apply_layer(p, h, sig, cfg, positions, *, mode="train", cache=None,
                pos=None, h0=None, shared_params=None, max_len=None):
    """Returns (h, aux, new_cache)."""
    mixer, akind, ffn, shared = sig
    aux = jnp.zeros((), jnp.float32)
    hn = L.rmsnorm(p["norm1"], h, cfg.norm_eps)
    new_cache: Dict[str, Any] = {}
    cache = cache or {}

    if mixer == "attn":
        if mode == "train":
            out = L.attn_block(p["mixer"], hn, cfg, akind, positions)
        elif mode == "prefill":
            out, c = L.attn_block_prefill(p["mixer"], hn, cfg, akind,
                                          positions)
            new_cache["mixer"] = tuple(_pad_seq(t, 2, max_len) for t in c)
        else:
            out, c = L.attn_block_decode(p["mixer"], hn, cfg, akind,
                                         cache["mixer"], pos)
            new_cache["mixer"] = c
    elif mixer == "mla":
        if mode == "train":
            out = mla_lib.mla_block(p["mixer"], hn, cfg, positions)
        elif mode == "prefill":
            out, c = mla_lib.mla_block(p["mixer"], hn, cfg, positions,
                                       return_cache=True)
            new_cache["mixer"] = tuple(_pad_seq(t, 1, max_len) for t in c)
        else:
            out, c = mla_lib.mla_decode(p["mixer"], hn, cfg,
                                        cache["mixer"], pos)
            new_cache["mixer"] = c
    elif mixer in ("mamba1", "mamba2"):
        blk = (ssm_lib.mamba1_block if mixer == "mamba1"
               else ssm_lib.mamba2_block)
        dec = (ssm_lib.mamba1_decode if mixer == "mamba1"
               else ssm_lib.mamba2_decode)
        if mode == "train":
            out = blk(p["mixer"], hn, cfg)
        elif mode == "prefill":
            out, c = blk(p["mixer"], hn, cfg, return_cache=True)
            new_cache["mixer"] = c
        else:
            out, c = dec(p["mixer"], hn, cfg, cache["mixer"])
            new_cache["mixer"] = c
    else:
        raise ValueError(mixer)
    h = h + out
    h = constrain(h, "residual")

    if ffn != "none":
        hn = L.rmsnorm(p["norm2"], h, cfg.norm_eps)
        if ffn == "dense":
            h = h + L.mlp(p["ffn"], hn, megatron_sp=cfg.megatron_sp)
        elif cfg.experts_held:
            h = h + moe_lib.moe_block_held(p["ffn"], hn, cfg)[0]
        else:
            out, a = moe_lib.moe_block(p["ffn"], hn, cfg)
            h = h + out
            aux = aux + a
        h = constrain(h, "residual")

    if shared:
        h, c = _shared_block(shared_params, h, h0, cfg, positions, mode,
                             cache=cache.get("shared"), pos=pos)
        if mode == "prefill":
            new_cache["shared"] = tuple(_pad_seq(t, 2, max_len) for t in c)
        elif mode == "decode":
            new_cache["shared"] = c
        h = constrain(h, "residual")
    return h, aux, (new_cache if mode != "train" else None)


# ---------------------------------------------------------------------------
# full model: init
# ---------------------------------------------------------------------------

def init(key, cfg: ModelConfig):
    prefix, period, n_groups = group_layout(cfg)
    keys = L.split(key, 6)
    params: Dict[str, Any] = {}
    if cfg.input_mode == "tokens":
        params["embed"] = L.embed_init(keys[0], cfg.padded_vocab,
                                       cfg.d_model, cfg.jparam_dtype())
    for i in range(prefix):
        params[f"prefix_{i}"] = layer_init(
            jax.random.fold_in(keys[1], i), cfg, i)
    if n_groups:
        blocks = {}
        for s in range(period):
            gkeys = jnp.stack([jax.random.fold_in(keys[2], g * period + s)
                               for g in range(n_groups)])
            blocks[f"slot_{s}"] = jax.vmap(
                lambda kk, s=s: layer_init(kk, cfg, prefix + s))(gkeys)
        params["blocks"] = blocks
    if cfg.hybrid_attn_period:
        params["shared_attn"] = shared_attn_init(keys[3], cfg)
    params["final_norm"] = L.rmsnorm_init(cfg.d_model, cfg.jparam_dtype())
    params["lm_head"] = L.lm_head_init(keys[4], cfg.d_model,
                                       cfg.padded_vocab,
                                       cfg.jparam_dtype())
    return params


def abstract_init(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocating (dry-run path)."""
    return jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _embed_inputs(params, inputs, cfg):
    if cfg.input_mode == "tokens":
        return L.embed(params["embed"], inputs, cfg.jdtype())
    return inputs.astype(cfg.jdtype())


def forward(params, inputs, cfg: ModelConfig):
    """Full-sequence forward -> (hidden (B,S,D), aux_loss)."""
    prefix, period, n_groups = group_layout(cfg)
    h = _embed_inputs(params, inputs, cfg)
    h = constrain(h, "residual")
    s = h.shape[1]
    positions = jnp.arange(s)
    h0 = h
    aux = jnp.zeros((), jnp.float32)
    shared = params.get("shared_attn")

    for i in range(prefix):
        h, a, _ = apply_layer(params[f"prefix_{i}"], h, layer_sig(cfg, i),
                              cfg, positions, h0=h0, shared_params=shared)
        aux = aux + a

    if n_groups:
        sigs = [layer_sig(cfg, prefix + s_) for s_ in range(period)]

        def body(carry, xs):
            h, aux = carry
            for s_ in range(period):
                h, a, _ = apply_layer(xs[f"slot_{s_}"], h, sigs[s_], cfg,
                                      positions, h0=h0,
                                      shared_params=shared)
                aux = aux + a
            return (h, aux), None

        if cfg.remat:
            body = jax.checkpoint(body)
        (h, aux), _ = jax.lax.scan(body, (h, aux), params["blocks"])

    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return h, aux


def logits_fn(params, inputs, cfg):
    h, aux = forward(params, inputs, cfg)
    return L.lm_head(params["lm_head"], h), aux


def _xent(logits, labels):
    """f32 cross entropy; logits (..., V), labels (...) int."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def loss_fn(params, batch, cfg: ModelConfig):
    """batch: {"inputs": (B,S) tokens | (B,S,D) embeds, "labels": (B,S)}"""
    h, aux = forward(params, batch["inputs"], cfg)
    labels = batch["labels"]
    w = params["lm_head"]["w"]
    if cfg.logit_chunk and h.shape[1] % cfg.logit_chunk == 0:
        nc = h.shape[1] // cfg.logit_chunk
        hc = h.reshape(h.shape[0], nc, cfg.logit_chunk, h.shape[2])
        lc = labels.reshape(labels.shape[0], nc, cfg.logit_chunk)

        def chunk_ce(args):
            hh, ll = args
            return _xent(hh @ w.astype(hh.dtype), ll)

        ce = jax.lax.map(chunk_ce, (hc.transpose(1, 0, 2, 3),
                                    lc.transpose(1, 0, 2)))
        loss = jnp.mean(ce)
    else:
        logits = L.lm_head(params["lm_head"], h)
        loss = jnp.mean(_xent(logits, labels))
    total = loss + aux
    return total, {"loss": loss, "aux_loss": aux,
                   "tokens": jnp.asarray(labels.size, jnp.float32)}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Zero caches for decode-from-scratch (or shapes for the dry run)."""
    prefix, period, n_groups = group_layout(cfg)
    dt = cfg.jdtype()

    def one(i):
        mixer, akind, ffn, shared = layer_sig(cfg, i)
        c: Dict[str, Any] = {}
        if mixer == "attn":
            kv = (jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.hd), dt),
                  jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.hd), dt))
            c["mixer"] = kv
        elif mixer == "mla":
            c["mixer"] = (
                jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
                jnp.zeros((batch, max_len, cfg.qk_rope_dim), dt))
        elif mixer == "mamba1":
            c["mixer"] = (
                jnp.zeros((batch, cfg.d_inner, cfg.d_state), jnp.float32),
                jnp.zeros((batch, cfg.conv_kernel - 1, cfg.d_inner), dt))
        elif mixer == "mamba2":
            c["mixer"] = (
                jnp.zeros((batch, cfg.ssd_heads, cfg.d_state,
                           cfg.ssd_head_dim), jnp.float32),
                jnp.zeros((batch, cfg.conv_kernel - 1,
                           cfg.d_inner + 2 * cfg.d_state), dt))
        if shared:
            c["shared"] = (
                jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.hd), dt),
                jnp.zeros((batch, cfg.n_kv_heads, max_len, cfg.hd), dt))
        return c

    cache: Dict[str, Any] = {}
    for i in range(prefix):
        cache[f"prefix_{i}"] = one(i)
    if n_groups:
        blocks = {}
        for s in range(period):
            blocks[f"slot_{s}"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape),
                one(prefix + s))
        cache["blocks"] = blocks
    return cache


def decode_step(params, inputs, cache, pos, cfg: ModelConfig):
    """One token for the whole batch.  inputs: (B,1) tokens or (B,1,D).
    pos: () int32 current position.  Returns (logits (B,1,V), cache)."""
    prefix, period, n_groups = group_layout(cfg)
    h = _embed_inputs(params, inputs, cfg)
    h0 = h
    shared = params.get("shared_attn")
    new_cache: Dict[str, Any] = {}

    for i in range(prefix):
        h, _, c = apply_layer(params[f"prefix_{i}"], h, layer_sig(cfg, i),
                              cfg, None, mode="decode",
                              cache=cache[f"prefix_{i}"], pos=pos, h0=h0,
                              shared_params=shared)
        new_cache[f"prefix_{i}"] = c

    if n_groups:
        sigs = [layer_sig(cfg, prefix + s_) for s_ in range(period)]

        def body(h, xs):
            pslots, cslots = xs
            out_c = {}
            for s_ in range(period):
                h, _, c = apply_layer(pslots[f"slot_{s_}"], h, sigs[s_],
                                      cfg, None, mode="decode",
                                      cache=cslots[f"slot_{s_}"], pos=pos,
                                      h0=h0, shared_params=shared)
                out_c[f"slot_{s_}"] = c
            return h, out_c

        h, blocks_cache = jax.lax.scan(
            body, h, (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = blocks_cache

    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return L.lm_head(params["lm_head"], h), new_cache


def prefill(params, inputs, cfg: ModelConfig, max_len: int | None = None):
    """Full-sequence forward returning last-position logits + caches.
    ``max_len`` pre-pads the KV caches so decode can continue in place."""
    prefix, period, n_groups = group_layout(cfg)
    h = _embed_inputs(params, inputs, cfg)
    s = h.shape[1]
    positions = jnp.arange(s)
    h0 = h
    shared = params.get("shared_attn")
    caches: Dict[str, Any] = {}

    for i in range(prefix):
        h, _, c = apply_layer(params[f"prefix_{i}"], h, layer_sig(cfg, i),
                              cfg, positions, mode="prefill", h0=h0,
                              shared_params=shared, max_len=max_len)
        caches[f"prefix_{i}"] = c

    if n_groups:
        sigs = [layer_sig(cfg, prefix + s_) for s_ in range(period)]

        def body(h, pslots):
            out_c = {}
            for s_ in range(period):
                h, _, c = apply_layer(pslots[f"slot_{s_}"], h, sigs[s_],
                                      cfg, positions, mode="prefill",
                                      h0=h0, shared_params=shared,
                                      max_len=max_len)
                out_c[f"slot_{s_}"] = c
            return h, out_c

        h, blocks_cache = jax.lax.scan(body, h, params["blocks"])
        caches["blocks"] = blocks_cache

    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = L.lm_head(params["lm_head"], h[:, -1:])
    return logits, caches


# ---------------------------------------------------------------------------
# serving: paged KV (continuous batching)
# ---------------------------------------------------------------------------

def _check_paged(cfg: ModelConfig) -> None:
    """Paged serving covers stacks whose every mixer is plain attention
    (fused (K, V) pages) or latent attention (one latent row per
    token), with no weight-shared block: SSM state is not paged."""
    for i in range(cfg.n_layers):
        mixer, _, _, shared = layer_sig(cfg, i)
        if mixer not in ("attn", "mla") or shared:
            what = f"{mixer!r}" + (" + the weight-shared attention block"
                                   if shared else "")
            raise ValueError(
                f"paged serving needs attention or latent-attention "
                f"layers without a shared block; layer {i} is {what} "
                f"(SSM state and shared blocks are not paged)")


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int):
    """Per-layer page pools, the paged analogue of :func:`init_cache`:
    fused-KV pools for attention layers, latent pools (one ``kv_lora +
    qk_rope`` row per token) for MLA layers.  One *shared* (B,
    max_pages) page table (built by the scheduler) addresses every
    layer's pool: the layers hold different values at identical page
    indices."""
    from repro.core import paged as paged_lib

    _check_paged(cfg)
    prefix, period, n_groups = group_layout(cfg)
    dt = cfg.jdtype()

    def one(i):
        if layer_sig(cfg, i)[0] == "mla":
            return {"mixer": paged_lib.init_latent_pool(
                num_pages, page_size, cfg.latent_width, dt)}
        return {"mixer": paged_lib.init_pool(
            num_pages, cfg.n_kv_heads, page_size, cfg.hd, dt)}

    cache: Dict[str, Any] = {}
    for i in range(prefix):
        cache[f"prefix_{i}"] = one(i)
    if n_groups:
        cache["blocks"] = {
            f"slot_{s}": jax.tree.map(
                lambda x: jnp.broadcast_to(
                    x[None], (n_groups,) + x.shape), one(prefix + s))
            for s in range(period)}
    return cache


def _write_pages(pool, pages, cache, mixer):
    """One layer: a batch-1 prefill cache into its pages."""
    from repro.core import paged as paged_lib
    if mixer == "mla":
        c_kv, k_rope = cache
        return paged_lib.write_latent_pages(
            pool, pages, jnp.concatenate(
                [c_kv[0], k_rope[0].astype(c_kv.dtype)], axis=-1))
    k, v = cache
    return paged_lib.write_prefill_pages(pool, pages, k[0], v[0])


def scatter_prefill_pages(pools, caches, pages, cfg: ModelConfig):
    """Admission: scatter one request's prefill cache (a batch-1
    :func:`prefill` cache pytree, S tokens) into its allocated pages
    across every layer pool.  ``pages``: (n,) i32 physical page ids,
    ``n * page_size >= S`` (tail pages zero-padded, masked by seq_pos
    at read time).  Returns the updated pools pytree."""
    prefix, period, n_groups = group_layout(cfg)
    out: Dict[str, Any] = {}
    for i in range(prefix):
        out[f"prefix_{i}"] = {"mixer": _write_pages(
            pools[f"prefix_{i}"]["mixer"], pages,
            caches[f"prefix_{i}"]["mixer"], layer_sig(cfg, i)[0])}
    if n_groups:
        blocks: Dict[str, Any] = {}
        for s in range(period):
            mixer = layer_sig(cfg, prefix + s)[0]
            blocks[f"slot_{s}"] = {"mixer": jax.vmap(
                lambda p, c, m=mixer: _write_pages(p, pages, c, m))(
                pools["blocks"][f"slot_{s}"]["mixer"],
                caches["blocks"][f"slot_{s}"]["mixer"])}
        out["blocks"] = blocks
    return out


def _paged_layer(p, h, sig, cfg, pool, page_table, pos, active):
    """One layer of the paged decode step: (h, pool, the held experts'
    route counts (E_held,) i32 of an MoE layer under a held share, else
    None)."""
    mixer, akind, ffn, shared = sig
    hn = L.rmsnorm(p["norm1"], h, cfg.norm_eps)
    if mixer == "mla":
        out, pool = mla_lib.mla_decode_paged(
            p["mixer"], hn, cfg, pool, page_table, pos, active)
    else:
        out, pool = L.attn_block_decode_paged(
            p["mixer"], hn, cfg, akind, pool, page_table, pos, active)
    h = h + out
    h = constrain(h, "residual")
    loads = None
    if ffn != "none":
        hn = L.rmsnorm(p["norm2"], h, cfg.norm_eps)
        if ffn == "dense":
            h = h + L.mlp(p["ffn"], hn, megatron_sp=cfg.megatron_sp)
        elif cfg.experts_held:
            out, loads = moe_lib.moe_block_held(p["ffn"], hn, cfg)
            h = h + out
        else:
            out, _ = moe_lib.moe_block(p["ffn"], hn, cfg)
            h = h + out
        h = constrain(h, "residual")
    return h, pool, loads


def decode_step_paged(params, inputs, pools, page_table, pos, active,
                      cfg: ModelConfig):
    """One token for every serving slot against the paged pools.

    inputs: (B,1) tokens; page_table: (B, max_pages) i32; pos: (B,)
    per-slot positions; active: (B,) bool (inactive slots write to the
    null page and their logits are garbage the scheduler ignores).
    Returns (logits (B,1,V), updated pools); under a held-experts share
    (``cfg.experts_held``) also the routes each held expert computed,
    (MoE layers, E_held) i32, in layer order."""
    _check_paged(cfg)
    prefix, period, n_groups = group_layout(cfg)
    h = _embed_inputs(params, inputs, cfg)
    new_pools: Dict[str, Any] = {}
    loads = []

    for i in range(prefix):
        h, pool, ld = _paged_layer(
            params[f"prefix_{i}"], h, layer_sig(cfg, i), cfg,
            pools[f"prefix_{i}"]["mixer"], page_table, pos, active)
        new_pools[f"prefix_{i}"] = {"mixer": pool}
        if ld is not None:
            loads.append(ld[None])

    if n_groups:
        sigs = [layer_sig(cfg, prefix + s_) for s_ in range(period)]

        def body(h, xs):
            pslots, cslots = xs
            out_c, out_l = {}, []
            for s_ in range(period):
                h, pool, ld = _paged_layer(
                    pslots[f"slot_{s_}"], h, sigs[s_], cfg,
                    cslots[f"slot_{s_}"]["mixer"], page_table, pos,
                    active)
                out_c[f"slot_{s_}"] = {"mixer": pool}
                if ld is not None:
                    out_l.append(ld)
            return h, (out_c, out_l)

        h, (blocks_cache, block_loads) = jax.lax.scan(
            body, h, (params["blocks"], pools["blocks"]))
        new_pools["blocks"] = blocks_cache
        if block_loads:
            # (groups, E) per MoE slot -> layer order g * period + slot
            loads.append(jnp.stack(block_loads, axis=1).reshape(
                -1, block_loads[0].shape[-1]))

    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = L.lm_head(params["lm_head"], h)
    if cfg.experts_held:
        return logits, new_pools, jnp.concatenate(loads, axis=0)
    return logits, new_pools
