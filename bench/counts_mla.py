"""Operations and bytes of the latent-attention (MLA) serving step, from
shapes: what the algorithm must do, as in :mod:`bench.counts`.

``dm`` is :func:`bench.reference.deepseek_v2.dims` of the configuration.
"""
from __future__ import annotations


def paged_latent_decode(*, contexts, dm: dict, kv_bytes: int = 2) -> dict:
    """One decode step's absorbed latent attention over every layer.
    ``contexts`` lists each slot's live keys (its position + 1).  Bytes:
    the live latent rows (``kv_lora + rope`` wide, unpadded) read once
    per slot; operations: each head's score against the whole row and
    its sum of the rows' ``kv_lora`` lanes, two per multiply-add."""
    keys = sum(int(c) for c in contexts)
    width = dm["kv_lora"] + dm["rope"]
    return {"ops": dm["layers"] * keys * 2 * dm["heads"]
            * (width + dm["kv_lora"]),
            "bytes": dm["layers"] * keys * width * kv_bytes}


def _attn_weights(dm: dict) -> int:
    """Multiply-adds of one token's attention projections."""
    d, h = dm["d_model"], dm["heads"]
    dn, dr, dv, L = dm["nope"], dm["rope"], dm["v_dim"], dm["kv_lora"]
    return d * h * (dn + dr) + d * (L + dr) + h * dv * d


def _ffn(dm: dict, layer: int) -> int:
    """Multiply-adds of one token's dense MLP, or of its shared experts
    and router in an expert layer (the routed experts are counted by
    route)."""
    d = dm["d_model"]
    if layer < dm["dense_layers"]:
        return 3 * d * dm["d_ff"]
    return 3 * d * dm["expert_ff"] * dm["shared"] + d * dm["experts"]


def decode_flops_per_token(*, context: int, dm: dict) -> int:
    """Forward FLOPs of one decoded token in the absorbed form, attending
    over ``context`` keys, with its logits; without the routed experts."""
    h, L = dm["heads"], dm["kv_lora"]
    absorb = h * dm["nope"] * L + h * L * dm["v_dim"]
    per_layer = [_attn_weights(dm) + absorb + _ffn(dm, i)
                 for i in range(dm["layers"])]
    attn = dm["layers"] * h * (2 * L + dm["rope"]) * context
    return 2 * (sum(per_layer) + attn + dm["d_model"] * dm["vocab"])


def routed_flops(routes: int, dm: dict) -> int:
    """FLOPs of ``routes`` (token, expert) routes through a held expert's
    SwiGLU MLP."""
    return 2 * 3 * dm["d_model"] * dm["expert_ff"] * int(routes)


def prefill_flops(*, tokens: int, dm: dict) -> int:
    """Forward FLOPs of a causal prefill of ``tokens`` tokens in the
    materialised form (per-head keys and values from the latent), logits
    for the last token only; without the routed experts, whose routes a
    prefill does not report."""
    h, L = dm["heads"], dm["kv_lora"]
    dn, dr, dv = dm["nope"], dm["rope"], dm["v_dim"]
    body = sum(_attn_weights(dm) + L * h * (dn + dv) + _ffn(dm, i)
               for i in range(dm["layers"]))
    attn = dm["layers"] * h * (dn + dr + dv) * tokens * (tokens + 1) // 2
    return 2 * (tokens * body + attn + dm["d_model"] * dm["vocab"])
