"""Operations and bytes each measured kernel and step needs, from shapes.

These are what the algorithm must do, not what an implementation
happens to do: a roofline share is the least time these counts need on
the chip's peaks over the time the trace measured, so a kernel that
moves or computes more than this reads below 100%.
"""
from __future__ import annotations

#: f32 operations per member cell per diffusion step: three adds for the
#: neighbour sum, deg * s, the difference, the product with alpha and
#: the final add
DIFFUSION_OPS_PER_CELL = 7


def gasket_members(n: int) -> int:
    """Cells of the n x n gasket: 3**log2(n)."""
    r = n.bit_length() - 1
    assert 1 << r == n, n
    return 3 ** r


def ca_fused(*, n: int, steps: int, stored_bytes: int) -> dict:
    """One fused CA launch of ``steps`` steps: the rule's operations on
    every member cell each step; the stored state read once and the new
    state written once."""
    return {"ops": DIFFUSION_OPS_PER_CELL * gasket_members(n) * steps,
            "bytes": 2 * stored_bytes}


def write(*, stored_bytes: int) -> dict:
    """One write call: the stored array written once; no arithmetic."""
    return {"ops": 0, "bytes": stored_bytes}


def paged_decode(*, contexts, layers: int, heads: int, kv_heads: int,
                 head_dim: int, kv_bytes: int = 2) -> dict:
    """One decode step's paged attention over every layer.  ``contexts``
    lists each active slot's live keys (its position + 1).  Bytes: the
    live K and V rows at the unpadded head size, read once per KV head;
    operations: q.k and p.v, two per multiply-add, per query head."""
    keys = sum(int(c) for c in contexts)
    return {"ops": layers * heads * keys * head_dim * 4,
            "bytes": layers * kv_heads * keys * head_dim * 2 * kv_bytes}


def model_flops_per_token(*, layers: int, d_model: int, heads: int,
                          kv_heads: int, head_dim: int, d_ff: int,
                          vocab: int, context: int, head: bool = True) -> int:
    """Forward FLOPs of one token of a dense GQA transformer with a SwiGLU
    MLP and an untied head, attending over ``context`` keys; ``head``
    counts the vocabulary projection (only tokens whose logits are
    used need it)."""
    attn_w = d_model * head_dim * (2 * heads + 2 * kv_heads)
    mlp_w = 3 * d_model * d_ff
    attn = 4 * heads * head_dim * context
    return 2 * layers * (attn_w + mlp_w) + layers * attn \
        + (2 * d_model * vocab if head else 0)


def prefill_flops(*, tokens: int, **dims) -> int:
    """Forward FLOPs of a causal prefill of ``tokens`` tokens, token i
    attending over i + 1 keys, with logits for the last token only."""
    body = model_flops_per_token(context=0, head=False, **dims)
    head = 2 * dims["d_model"] * dims["vocab"]
    attn_per_key = 4 * dims["heads"] * dims["head_dim"] * dims["layers"]
    return tokens * body + head + attn_per_key * tokens * (tokens + 1) // 2


def roofline_share(*, ops: float, nbytes: float, seconds: float,
                   peaks: dict):
    """Percent of the chip's roofline a kernel reached: the least time
    its operations (bf16 peak) or its bytes (HBM bandwidth) need, the
    larger of the two, over the time it took.  None without a time."""
    if seconds <= 0:
        return None
    least = max(ops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
