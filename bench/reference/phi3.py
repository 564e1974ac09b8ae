"""Phi-3-mini (arXiv:2404.14219): its weights made from the seed, and a
plain float32 forward pass.

The architecture, as the model card's config.json states it: a
decoder-only stack of ``num_hidden_layers`` pre-norm blocks.  Each block:
RMSNorm (``rms_norm_eps``, a learned scale), multi-head attention with
rotary position embeddings (rotate-half, ``rope_theta``, over the whole
head), causal softmax scaled by 1/sqrt(head size), an output projection,
a residual add; RMSNorm, a SwiGLU MLP (silu(x Wg) * (x Wi)) Wo, a
residual add.  A final RMSNorm and an untied vocabulary projection.
The published fused qkv and gate/up projections are the same products
as the separate ones used here.  The published sliding window (2047)
never binds below 2047 positions, where every cell of this benchmark
stays.

Weights: each tensor is drawn from its own key, ``fold_in`` of the
seed's key (``bench.reference.prng_key``) with the tensor's number and
its layer, in bfloat16 (the type the model
is served in); dense weights are normal / sqrt(fan-in), the embedding
normal * 0.01, norm scales 1 + normal * 0.1.  The forward casts them
to float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: tensor numbers in the weight keys
_TENSORS = ("wq", "wk", "wv", "wo", "wg", "wi", "wo_mlp", "norm1", "norm2")
_EMBED, _HEAD, _FINAL = 100, 101, 102


def dims(config: dict) -> dict:
    d = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    vocab = int(config["vocab_size"])
    return {"layers": int(config["num_hidden_layers"]), "d_model": d,
            "heads": heads, "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": d // heads, "d_ff": int(config["intermediate_size"]),
            "vocab": -(-vocab // 16) * 16}


def _key(key, tensor: int, layer=0):
    return jax.random.fold_in(jax.random.fold_in(key, tensor), layer)


# each weight is computed in float32 and rounded to bfloat16 once, so no
# compiler's excess precision can change it


def _dense(key, fan_in, fan_out):
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
    return (w * (1.0 / math.sqrt(fan_in))).astype(jnp.bfloat16)


def _scale(key, d):
    w = jax.random.normal(key, (d,), jnp.float32)
    return (1 + 0.1 * w).astype(jnp.bfloat16)


def layer_weights(key, layer, dm: dict) -> dict:
    """One block's weights, bfloat16."""
    d, hd = dm["d_model"], dm["head_dim"]
    hq, hkv, f = dm["heads"] * hd, dm["kv_heads"] * hd, dm["d_ff"]
    k = {name: _key(key, i, layer) for i, name in enumerate(_TENSORS)}
    return {"norm1": _scale(k["norm1"], d), "norm2": _scale(k["norm2"], d),
            "wq": _dense(k["wq"], d, hq), "wk": _dense(k["wk"], d, hkv),
            "wv": _dense(k["wv"], d, hkv), "wo": _dense(k["wo"], hq, d),
            "wg": _dense(k["wg"], d, f), "wi": _dense(k["wi"], d, f),
            "wo_mlp": _dense(k["wo_mlp"], f, d)}


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


def _rope(x, theta):
    """x: (B, H, S, D) at positions 0..S-1, rotate-half convention."""
    s, d = x.shape[2], x.shape[3]
    inv = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(h, w, *, dm, eps, theta):
    """One pre-norm block on (B, S, D) float32 activations."""
    b, s, d = h.shape
    hd, nh, nkv = dm["head_dim"], dm["heads"], dm["kv_heads"]
    x = _rmsnorm(h, w["norm1"], eps)
    q = (x @ w["wq"]).reshape(b, s, nh, hd).transpose(0, 2, 1, 3)
    k = (x @ w["wk"]).reshape(b, s, nkv, hd).transpose(0, 2, 1, 3)
    v = (x @ w["wv"]).reshape(b, s, nkv, hd).transpose(0, 2, 1, 3)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", att, v)
    h = h + o.transpose(0, 2, 1, 3).reshape(b, s, nh * hd) @ w["wo"]
    x = _rmsnorm(h, w["norm2"], eps)
    return h + (jax.nn.silu(x @ w["wg"]) * (x @ w["wi"])) @ w["wo_mlp"]


def fp8(w):
    """The control's weights: each matrix rounded to float8 (e4m3) with
    one scale per output column, back in float32."""
    if w.ndim < 2:
        return w
    amax = jnp.max(jnp.abs(w), axis=0, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def logits(key, tokens, config: dict, *, control: bool = False):
    """Reference logits (B, S, V) float32 for int tokens (B, S), the
    weights remade from the seed's ``key`` one layer at a time in
    bfloat16 (their own compiled call, so they are the served values),
    cast to float32 (rounded to float8 under ``control``), matmuls at
    full precision."""
    dm = dims(config)
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    cast = fp8 if control else (lambda w: w)

    def f32(tree):
        return jax.tree.map(lambda a: cast(a.astype(jnp.float32)), tree)

    gen_outer = jax.jit(lambda key: outer_weights(key, dm))
    gen_layer = jax.jit(lambda key, i: layer_weights(key, i, dm))
    layer = jax.jit(lambda h, w: block(h, f32(w), dm=dm, eps=eps,
                                       theta=theta))

    @jax.jit
    def last(h, o):
        o = f32(o)
        return _rmsnorm(h, o["final_norm"], eps) @ o["head"]

    with jax.default_matmul_precision("highest"):
        o = gen_outer(key)
        h = o["embed"].astype(jnp.float32)[jnp.asarray(tokens)]
        for i in range(dm["layers"]):
            h = layer(h, gen_layer(key, jnp.int32(i)))
        return last(h, o)
