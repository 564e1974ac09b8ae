"""DeepSeek-V2 (arXiv:2405.04434), as DeepSeek-V2-Lite's config.json
states it: its weights made from the seed, and a plain float32 forward.

The architecture: a decoder-only stack of ``num_hidden_layers`` pre-norm
blocks.  Each block: RMSNorm (``rms_norm_eps``, a learned scale), then
multi-head latent attention without a query LoRA (``q_lora_rank`` null):
queries ``x Wq`` split per head into a ``qk_nope_head_dim`` part and a
``qk_rope_head_dim`` part; ``x Wkv_a`` gives the ``kv_lora_rank`` latent,
RMS-normed, and one shared ``qk_rope_head_dim`` key; ``Wkv_b`` expands the
latent into each head's nope key and ``v_head_dim`` value.  Rotary
embeddings with YaRN scaling (``rope_scaling``) on the rope parts; a
causal softmax scaled by ``(nope + rope)^-1/2 * mscale(factor,
mscale_all_dim)^2``; an output projection; a residual add.  Then
RMSNorm and, in the first ``first_k_dense_replace`` layers, a SwiGLU MLP
of ``intermediate_size``; after them a mixture of experts: softmax
scores over ``n_routed_experts_published`` experts, the greedy top
``num_experts_per_tok`` with their scores as gates (not renormalised:
``norm_topk_prob`` false; times ``routed_scaling_factor``), each routed
expert a SwiGLU MLP of ``moe_intermediate_size``, plus
``n_shared_experts`` shared experts (one SwiGLU MLP of that many times
the width); a residual add.  A final RMSNorm and an untied vocabulary
projection.

Here the keys and values are materialised per head from ``Wkv_b`` (the
paper's training form), attention runs in blocks of queries, and the
experts are computed densely for every token, each weighted by its gate
(zero where it was not chosen).  Only the held experts
``[experts_held_first, + n_routed_experts)`` are computed: what the
chips holding the others would add is not here, as in the program.

Departures (the configuration's ``assumed``): rope pairs the halves of
the rope dims (rotate-half) where the checkpoint interleaves them, a
fixed permutation of the weights' columns, immaterial with random
weights.

Weights: each tensor from its own key, ``fold_in`` of the seed's key
with the tensor's number and its layer (and a routed expert's global
id), in bfloat16; dense weights normal / sqrt(fan-in), the embedding
normal * 0.01, norm scales 1 + normal * 0.1.  The forward casts them to
float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: tensor numbers in the weight keys
_TENSORS = ("wq", "wkv_a", "wkv_b", "wo", "norm1", "norm2", "kv_norm",
            "wg", "wi", "wo_mlp", "router", "ex_wg", "ex_wi", "ex_wo",
            "sh_wg", "sh_wi", "sh_wo")
_EMBED, _HEAD, _FINAL = 100, 101, 102
#: queries per block of the attention
Q_BLOCK = 512


def dims(config: dict) -> dict:
    """The sizes and constants of a configuration file."""
    if config.get("q_lora_rank"):
        raise ValueError("only the form without a query LoRA is written")
    rs = config["rope_scaling"]
    vocab = int(config["vocab_size"])
    return {
        "layers": int(config["num_hidden_layers"]),
        "d_model": int(config["hidden_size"]),
        "heads": int(config["num_attention_heads"]),
        "kv_lora": int(config["kv_lora_rank"]),
        "nope": int(config["qk_nope_head_dim"]),
        "rope": int(config["qk_rope_head_dim"]),
        "v_dim": int(config["v_head_dim"]),
        "d_ff": int(config["intermediate_size"]),
        "expert_ff": int(config["moe_intermediate_size"]),
        "dense_layers": int(config["first_k_dense_replace"]),
        "experts": int(config["n_routed_experts_published"]),
        "held_first": int(config["experts_held_first"]),
        "held": int(config["n_routed_experts"]),
        "top_k": int(config["num_experts_per_tok"]),
        "shared": int(config["n_shared_experts"]),
        "norm_topk": bool(config["norm_topk_prob"]),
        "routed_scale": float(config["routed_scaling_factor"]),
        "vocab": -(-vocab // 16) * 16,
        "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]),
        "yarn": (float(rs["factor"]),
                 int(rs["original_max_position_embeddings"]),
                 float(rs["beta_fast"]), float(rs["beta_slow"]),
                 float(rs["mscale"]), float(rs["mscale_all_dim"])),
    }


def _key(key, tensor, layer=0):
    """The key of a tensor, named (a layer's) or numbered (the outer
    ones), in a layer."""
    if isinstance(tensor, str):
        tensor = _TENSORS.index(tensor)
    return jax.random.fold_in(jax.random.fold_in(key, tensor), layer)


# each weight is computed in float32 and rounded to bfloat16 once


def _dense(key, fan_in, fan_out):
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
    return (w * (1.0 / math.sqrt(fan_in))).astype(jnp.bfloat16)


def _scale(key, d):
    w = jax.random.normal(key, (d,), jnp.float32)
    return (1 + 0.1 * w).astype(jnp.bfloat16)


def attn_weights(key, layer, dm: dict) -> dict:
    """One block's attention and norm weights, bfloat16."""
    d, h = dm["d_model"], dm["heads"]
    dn, dr, dv, L = dm["nope"], dm["rope"], dm["v_dim"], dm["kv_lora"]

    def k(name):
        return _key(key, name, layer)
    return {"norm1": _scale(k("norm1"), d), "norm2": _scale(k("norm2"), d),
            "wq": _dense(k("wq"), d, h * (dn + dr)),
            "wkv_a": _dense(k("wkv_a"), d, L + dr),
            "kv_norm": _scale(k("kv_norm"), L),
            "wkv_b": _dense(k("wkv_b"), L, h * (dn + dv)),
            "wo": _dense(k("wo"), h * dv, d)}


def dense_layer_weights(key, layer, dm: dict) -> dict:
    """A leading dense block's weights, bfloat16."""
    d, f = dm["d_model"], dm["d_ff"]
    w = attn_weights(key, layer, dm)
    w.update(wg=_dense(_key(key, "wg", layer), d, f),
             wi=_dense(_key(key, "wi", layer), d, f),
             wo_mlp=_dense(_key(key, "wo_mlp", layer), f, d))
    return w


def moe_layer_weights(key, layer, dm: dict) -> dict:
    """An expert block's weights, bfloat16: the router over every
    published expert, the held experts (each from its global id), the
    shared experts."""
    d, fe = dm["d_model"], dm["expert_ff"]
    fs = fe * dm["shared"]
    ids = jnp.arange(dm["held_first"], dm["held_first"] + dm["held"])

    def experts(name, fan_in, fan_out):
        k = _key(key, name, layer)
        return jax.vmap(lambda e: _dense(jax.random.fold_in(k, e),
                                         fan_in, fan_out))(ids)
    w = attn_weights(key, layer, dm)
    w.update(router=_dense(_key(key, "router", layer), d, dm["experts"]),
             ex_wg=experts("ex_wg", d, fe), ex_wi=experts("ex_wi", d, fe),
             ex_wo=experts("ex_wo", fe, d),
             sh_wg=_dense(_key(key, "sh_wg", layer), d, fs),
             sh_wi=_dense(_key(key, "sh_wi", layer), d, fs),
             sh_wo=_dense(_key(key, "sh_wo", layer), fs, d))
    return w


def outer_weights(key, dm: dict) -> dict:
    """Embedding table, final norm scale and head, bfloat16."""
    d, v = dm["d_model"], dm["vocab"]
    table = jax.random.normal(_key(key, _EMBED), (v, d), jnp.float32)
    return {"embed": (table * 0.01).astype(jnp.bfloat16),
            "final_norm": _scale(_key(key, _FINAL), d),
            "head": _dense(_key(key, _HEAD), d, v)}


# ---------------------------------------------------------------------------
# the plain forward, float32
# ---------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(d: int, theta: float, yarn) -> jnp.ndarray:
    """YaRN's inverse frequencies (arXiv:2309.00071 as DeepSeek-V2 uses
    it): the plain frequencies where a dimension turns more than
    ``beta_fast`` times over the original context, those divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, a linear
    ramp between."""
    factor, orig, beta_fast, beta_slow = yarn[:4]

    def dim_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    plain = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rope(x, yarn, theta):
    """x: (B, H, S, D) at positions 0..S-1, rotate-half pairing, YaRN
    frequencies, cos and sin times mscale / mscale_all_dim."""
    s, d = x.shape[2], x.shape[3]
    inv = yarn_inv_freq(d, theta, yarn)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    att = _mscale(yarn[0], yarn[4]) / _mscale(yarn[0], yarn[5])
    cos, sin = jnp.cos(ang) * att, jnp.sin(ang) * att
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(dm: dict) -> float:
    m = _mscale(dm["yarn"][0], dm["yarn"][5])
    return (dm["nope"] + dm["rope"]) ** -0.5 * m * m


def attention(h, w, dm: dict):
    """The attention half of a block on (B, S, D): h + attn(norm(h))."""
    b, s, _ = h.shape
    nh, dn, dr, dv = dm["heads"], dm["nope"], dm["rope"], dm["v_dim"]
    x = _rmsnorm(h, w["norm1"], dm["eps"])
    q = (x @ w["wq"]).reshape(b, s, nh, dn + dr).transpose(0, 2, 1, 3)
    kv_a = x @ w["wkv_a"]
    c = _rmsnorm(kv_a[..., :dm["kv_lora"]], w["kv_norm"], dm["eps"])
    k_pe = _rope(kv_a[:, None, :, dm["kv_lora"]:], dm["yarn"], dm["theta"])
    kv = (c @ w["wkv_b"]).reshape(b, s, nh, dn + dv).transpose(0, 2, 1, 3)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (b, nh, s, dr))], -1)
    v = kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], dm["yarn"],
                                            dm["theta"])], -1)
    qb = min(Q_BLOCK, s)
    assert s % qb == 0, (s, qb)
    scale = softmax_scale(dm)

    def one_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=2)
        att = jnp.einsum("bhqd,bhkd->bhqk", qi, k) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)[None]
        att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", att, v)

    o = jax.lax.map(one_block, jnp.arange(s // qb))     # (n, B, H, qb, dv)
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, s, nh * dv)
    return h + o @ w["wo"]


def _swiglu(x, wg, wi, wo):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def dense_block(h, w, dm: dict):
    h = attention(h, w, dm)
    x = _rmsnorm(h, w["norm2"], dm["eps"])
    return h + _swiglu(x, w["wg"], w["wi"], w["wo_mlp"])


def gates(x, router, dm: dict):
    """(N, held) gates of the held experts: the greedy top-k softmax
    scores, zero for a held expert not chosen."""
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, idx = jax.lax.top_k(probs, dm["top_k"])
    if dm["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * dm["routed_scale"]
    held = jnp.arange(dm["held_first"], dm["held_first"] + dm["held"])
    return jnp.sum(jnp.where(idx[..., None] == held, top[..., None], 0.0),
                   axis=-2)


def moe_block(h, w, dm: dict):
    h = attention(h, w, dm)
    x = _rmsnorm(h, w["norm2"], dm["eps"])
    g = gates(x, w["router"], dm)
    out = _swiglu(x, w["sh_wg"], w["sh_wi"], w["sh_wo"])
    for e in range(dm["held"]):
        out = out + g[..., e:e + 1] * _swiglu(
            x, w["ex_wg"][e], w["ex_wi"][e], w["ex_wo"][e])
    return h + out


def fp8(w):
    """The control's weights: each matrix rounded to float8 (e4m3) with
    one scale per output column, back in float32."""
    if w.ndim < 2:
        return w
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def hidden(key, tokens, config: dict, *, control: bool = False):
    """The final RMS-normed hidden states (B, S, D) float32 of int tokens
    (B, S), S a multiple of ``Q_BLOCK`` (or below it), the weights
    remade from the seed's ``key`` one layer at a time in bfloat16 and
    cast to float32 (rounded to float8 under ``control``), matmuls at
    full precision.  Logits are ``hidden @ head(key, config)``."""
    dm = dims(config)
    cast = fp8 if control else (lambda a: a)

    def f32(tree):
        return jax.tree.map(lambda a: cast(a.astype(jnp.float32)), tree)

    gen_dense = jax.jit(lambda key, i: dense_layer_weights(key, i, dm))
    gen_moe = jax.jit(lambda key, i: moe_layer_weights(key, i, dm))
    run_dense = jax.jit(lambda h, w: dense_block(h, f32(w), dm))
    run_moe = jax.jit(lambda h, w: moe_block(h, f32(w), dm))
    gen_outer = jax.jit(lambda key: outer_weights(key, dm))

    @jax.jit
    def first(o, tokens):
        return o["embed"].astype(jnp.float32)[tokens]

    @jax.jit
    def last(h, o):
        return _rmsnorm(h, o["final_norm"].astype(jnp.float32), dm["eps"])

    with jax.default_matmul_precision("highest"):
        o = gen_outer(key)
        h = first(o, jnp.asarray(tokens))
        for i in range(dm["layers"]):
            if i < dm["dense_layers"]:
                h = run_dense(h, gen_dense(key, jnp.int32(i)))
            else:
                h = run_moe(h, gen_moe(key, jnp.int32(i)))
        return last(h, o)


def head(key, config: dict, *, control: bool = False):
    """The vocabulary projection (D, V) float32 (float8-rounded under
    ``control``)."""
    dm = dims(config)
    w = jax.jit(lambda key: outer_weights(key, dm)["head"])(key)
    w = w.astype(jnp.float32)
    return fp8(w) if control else w
