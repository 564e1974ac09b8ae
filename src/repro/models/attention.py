"""Attention for the model stack, pure JAX (the lowering path for
dry-run/roofline; the Pallas kernels in repro.kernels are the TPU fast
path validated against the same oracles).

Three execution strategies, selected by sequence length and config:

* ``simple``    -- full masked attention (small seqs, autodiff handles bwd)
* ``flash``     -- chunked online-softmax with a custom VJP that
                   recomputes per-chunk scores in the backward pass
                   (memory O(S * chunk) instead of O(S^2))
* ``decode``    -- one-token query against a long KV cache

The flash path has two *schedules*, the XLA-level mirror of the
GridPlan lowerings (``repro.core.plan``):

* ``dense``      -- every (q, k-chunk) pair is computed and masked: the
                    bounding-box analogue (2x wasted FLOPs for causal).
* ``triangular`` -- a static python loop over q chunks whose per-row
                    k-extents come from the block domain via
                    ``GridPlan.row_extents()``: the compact block-space
                    analogue (exactly the paper's Theorem-2 work saving
                    applied to the 2-simplex domain of causal attention).

``schedule`` also accepts GridPlan lowering names ("closed_form",
"prefetch_lut", "bounding", "compact"), mapped through
``plan.xla_schedule`` -- the launch configs plumb one lowering knob to
both the Pallas kernels and this XLA path.

GQA is handled by grouping q heads as (Hkv, G) so K/V are never
materialized per-q-head.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.domain import make_attention_domain
from repro.core.plan import GridPlan, xla_schedule

NEG_INF = float(-1e30)


def _schedule_name(schedule: str) -> str:
    """Normalize: accept schedules and GridPlan lowering names."""
    if schedule in ("dense", "triangular"):
        return schedule
    return xla_schedule(schedule)


def _mask(qpos, kpos, kind: str, window: int):
    if kind == "full":
        return None
    m = kpos <= qpos
    if kind == "local":
        m &= kpos > qpos - window
    return m


def _apply_mask(s, mask):
    return s if mask is None else jnp.where(mask, s, NEG_INF)


# ---------------------------------------------------------------------------
# simple (full materialization)
# ---------------------------------------------------------------------------

def simple_attention(q, k, v, *, kind="causal", window=0,
                     scale: Optional[float] = None):
    """q: (B,H,Sq,D); k,v: (B,Hkv,Sk,D).  f32 softmax, returns q.dtype."""
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, g, sq, d)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(sq)[:, None] + (sk - sq)
    kpos = jnp.arange(sk)[None, :]
    s = _apply_mask(s, _mask(qpos, kpos, kind, window))
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return o.reshape(b, h, sq, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# flash: chunked online softmax with custom VJP
# ---------------------------------------------------------------------------

def _chunk_fwd_scan(qg, k, v, kind, window, scale, chunk, q_offset):
    """Online-softmax over k chunks.  qg: (B,Hkv,G,Sq,D); k,v: (B,Hkv,Sk,D).
    Returns o (f32) and lse, both (B,Hkv,G,Sq,*)."""
    b, hkv, g, sq, d = qg.shape
    sk = k.shape[2]
    dv = v.shape[-1]
    nc = sk // chunk
    kc = k.reshape(b, hkv, nc, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, nc, chunk, dv).transpose(2, 0, 1, 3, 4)
    qpos = jnp.arange(sq)[:, None] + q_offset

    def step(carry, inp):
        ci, kci, vci = inp
        acc, m, l = carry
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kci,
                       preferred_element_type=jnp.float32) * scale
        kpos = ci * chunk + jnp.arange(chunk)[None, :]
        s = _apply_mask(s, _mask(qpos, kpos, kind, window))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vci.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, hkv, g, sq, dv), jnp.float32)
    m0 = jnp.full((b, hkv, g, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq, 1), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (jnp.arange(nc), kc, vc))
    l = jnp.where(l == 0, 1.0, l)
    return acc / l, m + jnp.log(l)


def _chunk_bwd_scan(qg, k, v, o, lse, dog, kind, window, scale, chunk,
                    q_offset):
    """Backward for the dense schedule.  Shapes as in _chunk_fwd_scan;
    o/do/lse in the grouped layout.  Returns dqg, dk, dv."""
    b, hkv, g, sq, d = qg.shape
    sk = k.shape[2]
    dv = v.shape[-1]
    nc = sk // chunk
    kc = k.reshape(b, hkv, nc, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, nc, chunk, dv).transpose(2, 0, 1, 3, 4)
    qpos = jnp.arange(sq)[:, None] + q_offset
    delta = jnp.sum(dog * o, axis=-1, keepdims=True)  # (B,Hkv,G,Sq,1)

    def step(dq, inp):
        ci, kci, vci = inp
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kci,
                       preferred_element_type=jnp.float32) * scale
        kpos = ci * chunk + jnp.arange(chunk)[None, :]
        s = _apply_mask(s, _mask(qpos, kpos, kind, window))
        p = jnp.exp(s - lse)
        dv_c = jnp.einsum("bhgqk,bhgqd->bhkd", p, dog,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhgqd,bhkd->bhgqk", dog, vci.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq = dq + jnp.einsum("bhgqk,bhkd->bhgqd", ds,
                             kci.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        dk_c = jnp.einsum("bhgqk,bhgqd->bhkd", ds, qg.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        return dq, (dk_c, dv_c)

    dq0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    dq, (dkc, dvc) = jax.lax.scan(step, dq0, (jnp.arange(nc), kc, vc))
    dk_out = dkc.transpose(1, 2, 0, 3, 4).reshape(b, hkv, sk, d)
    dv_out = dvc.transpose(1, 2, 0, 3, 4).reshape(b, hkv, sk, dv)
    return dq, dk_out, dv_out


def _tri_klen(i: int, chunk: int, sk: int, sq: int, kind: str,
              window: int) -> tuple[int, int]:
    """Static (k_start, k_len) for q chunk i under the compact schedule
    with a q/k offset (cross-attention-style sk > sq)."""
    hi = min(sk, (i + 1) * chunk + (sk - sq))
    if kind == "local":
        lo = max(0, (i * chunk + (sk - sq) - window) // chunk * chunk)
    else:
        lo = 0
    return lo, hi - lo


@functools.lru_cache(maxsize=256)
def _compact_extents(kind: str, window: int, chunk: int, sq: int,
                     sk: int) -> tuple:
    """Static per-q-chunk (k_start, k_len) for the compact schedule.

    For the square self-attention case the extents come from the block
    domain itself (``GridPlan.row_extents``), so any domain the engine
    registers schedules correctly; the offset case (sk > sq) keeps the
    token-level closed form.  Cached: re-entered on every fwd AND bwd
    trace of the custom-vjp flash."""
    m_q = sq // chunk
    if sq != sk:
        return tuple(_tri_klen(i, chunk, sk, sq, kind, window)
                     for i in range(m_q))
    wb = (-(-window // chunk) + 1) if kind == "local" else 0
    domain = make_attention_domain(kind, m_q, m_q, wb)
    ext = GridPlan(domain).row_extents()
    return tuple((int(lo) * chunk, (int(hi) + 1 - int(lo)) * chunk)
                 for lo, hi in ext)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, kind, window, scale, chunk, schedule):
    o, _ = _flash_fwd_impl(q, k, v, kind, window, scale, chunk, schedule)
    return o


def _flash_fwd_impl(q, k, v, kind, window, scale, chunk, schedule):
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = h // hkv
    qg = q.reshape(b, hkv, g, sq, d)
    q_offset = sk - sq
    if schedule == "dense" or kind == "full":
        o, lse = _chunk_fwd_scan(qg, k, v, kind, window, scale, chunk,
                                 q_offset)
    else:  # triangular / band compact schedule: static loop over q chunks
        nq = sq // chunk
        extents = _compact_extents(kind, window, chunk, sq, sk)
        os_, lses = [], []
        for i in range(nq):
            lo, ln = extents[i]
            qi = qg[:, :, :, i * chunk:(i + 1) * chunk]
            oi, lsei = _chunk_fwd_scan(
                qi, k[:, :, lo:lo + ln], v[:, :, lo:lo + ln], kind, window,
                scale, min(chunk, ln), q_offset + i * chunk - lo)
            os_.append(oi)
            lses.append(lsei)
        o = jnp.concatenate(os_, axis=3)
        lse = jnp.concatenate(lses, axis=3)
    return o.reshape(b, h, sq, v.shape[-1]).astype(q.dtype), lse


def _flash_vjp_fwd(q, k, v, kind, window, scale, chunk, schedule):
    o, lse = _flash_fwd_impl(q, k, v, kind, window, scale, chunk, schedule)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(kind, window, scale, chunk, schedule, res, do):
    q, k, v, o, lse = res
    b, h, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    dvd = v.shape[-1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, sq, d)
    og = o.reshape(b, hkv, g, sq, dvd).astype(jnp.float32)
    dog = do.reshape(b, hkv, g, sq, dvd).astype(jnp.float32)
    q_offset = sk - sq
    if schedule == "dense" or kind == "full":
        dq, dk, dv = _chunk_bwd_scan(qg, k, v, og, lse, dog, kind, window,
                                     scale, chunk, q_offset)
    else:
        nq = sq // chunk
        extents = _compact_extents(kind, window, chunk, sq, sk)
        dq = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
        dk = jnp.zeros((b, hkv, sk, d), jnp.float32)
        dv = jnp.zeros((b, hkv, sk, dvd), jnp.float32)
        for i in range(nq):
            lo, ln = extents[i]
            sl = slice(i * chunk, (i + 1) * chunk)
            dqi, dki, dvi = _chunk_bwd_scan(
                qg[:, :, :, sl], k[:, :, lo:lo + ln], v[:, :, lo:lo + ln],
                og[:, :, :, sl], lse[:, :, :, sl], dog[:, :, :, sl],
                kind, window, scale, min(chunk, ln),
                q_offset + i * chunk - lo)
            dq = dq.at[:, :, :, sl].set(dqi)
            dk = dk.at[:, :, lo:lo + ln].add(dki)
            dv = dv.at[:, :, lo:lo + ln].add(dvi)
    return (dq.reshape(b, h, sq, d).astype(q.dtype),
            dk.astype(k.dtype), dv.astype(v.dtype))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_xla(q, k, v, *, kind="causal", window=0,
                        scale: Optional[float] = None, chunk=1024,
                        schedule="dense"):
    schedule = _schedule_name(schedule)
    if scale is None:
        scale = float(1.0 / np.sqrt(q.shape[-1]))
    chunk = min(chunk, k.shape[2])
    if k.shape[2] % chunk:
        raise ValueError("Sk must be divisible by chunk")
    if schedule == "triangular" and q.shape[2] % chunk:
        raise ValueError("Sq must be divisible by chunk for triangular")
    return _flash(q, k, v, kind, window, float(scale), chunk, schedule)


# ---------------------------------------------------------------------------
# decode: one new token against a KV cache
# ---------------------------------------------------------------------------

#: the mesh the block-space decode path shards its continuous-batching
#: slot groups over; set by the serving layer (``set_decode_mesh``) so
#: the model stack stays mesh-agnostic.
_DECODE_MESH = None
_DECODE_AXIS = "data"


def set_decode_mesh(mesh, axis: str = "data") -> None:
    """Register the serving mesh for :func:`decode_attention_flash`
    (``None`` disables sharding).  Called by ``launch/serve.py`` when a
    mesh is in play; the next traced decode step picks it up."""
    global _DECODE_MESH, _DECODE_AXIS
    _DECODE_MESH = mesh
    _DECODE_AXIS = axis


def decode_attention_flash(q, k, v, pos, *, kind="causal", window=0,
                           scale: Optional[float] = None,
                           block_k: int = 128, backend=None, mesh=None,
                           shard_axis: Optional[str] = None):
    """Single-token decode through the block-space Pallas kernel.

    q: (B,H,1,D); k,v: (B,Hkv,Smax,D) caches; pos: () current position,
    or a (B,) int32 vector of *per-row* positions (continuous batching:
    every slot decodes at its own depth; a scalar broadcasts).
    The kernel receives ``pos`` as a run-time operand (SMEM on
    TPU, a regular operand on GPU): keys beyond ``pos`` are masked and
    key *blocks* beyond ``pos // block_k`` are predicated off -- the
    run-time analogue of the paper's block-space work saving.  On the
    gpu structure the in-kernel loop bound truncates outright, so a
    short sequence in a long cache *reads* O(pos / block_k) tiles; on
    the TPU structure the static grid still pipelines every cache tile
    through VMEM and only the dead blocks' compute is skipped
    (``pl.when``), so the tile-traffic saving is gpu-only.
    ``kind='local'`` anchors the sliding window at ``pos`` inside the
    kernel.

    ``mesh`` (default: the registered serving mesh) shards the *batch*
    axis -- continuous-batching slot groups: each device decodes its
    contiguous group of slots with its cache shard, embarrassingly
    parallel (no collectives).  A batch that does not tile the mesh
    axis runs the kernel unsharded instead; a cache length that does
    not tile ``block_k`` falls back to the XLA
    :func:`decode_attention`."""
    b, h, _, d = q.shape
    sk = k.shape[2]
    block_k = min(block_k, sk)
    if sk % block_k:
        return decode_attention(q, k, v, pos, kind=kind, window=window,
                                scale=scale)
    w = window if kind == "local" else 0
    kw = dict(kind="full", window=w, scale=scale, block_q=1,
              block_k=block_k, backend=backend)
    if mesh is None:
        mesh = _DECODE_MESH
    axis = shard_axis or _DECODE_AXIS
    if mesh is None or b % int(mesh.shape[axis]):
        return flash_attention_kernel(q, k, v, seq_pos=pos, **kw)

    from jax.sharding import PartitionSpec as P

    def device_fn(qd, kd, vd, posd):
        return flash_attention_kernel(qd, kd, vd, seq_pos=posd, **kw)

    posv = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    batched = P(axis, None, None, None)
    return jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(batched, batched, batched, P(axis)),
        out_specs=batched, check_vma=False)(q, k, v, posv)


def flash_attention_kernel(*args, **kwargs):
    """The Pallas kernel entry point (import indirection keeps the XLA
    model stack importable without the kernels package in play)."""
    from repro.kernels.flash_attention import flash_attention
    return flash_attention(*args, **kwargs)


def decode_attention(q, k, v, pos, *, kind="causal", window=0,
                     scale: Optional[float] = None):
    """q: (B,H,1,D); k,v: (B,Hkv,S,D) cache; pos: () current position
    or (B,) per-row positions.  Keys at kpos > pos (unfilled cache
    tail) are masked out."""
    b, h, _, d = q.shape
    _, hkv, sk, _ = k.shape
    g = h // hkv
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    qg = q.reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(sk)[None, None, None, :]
    pos = jnp.asarray(pos)
    if pos.ndim:  # (B,) per-row decode positions
        pos = pos.reshape(b, 1, 1, 1)
    valid = kpos <= pos
    if kind == "local":
        valid &= kpos > pos - window
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p.astype(v.dtype), v)
    return o.reshape(b, h, 1, v.shape[-1]).astype(q.dtype)


def decode_attention_paged(q, kv_pool, page_table, pos, *,
                           window: int = 0,
                           scale: Optional[float] = None,
                           grid_mode: str = "compact", backend=None,
                           mesh=None, shard_axis: Optional[str] = None,
                           verify: bool = False):
    """Paged single-token decode through the block-space Pallas kernel.

    q: (B,H,1,D) slot queries; kv_pool: (P, 2*Hkv, page_size, D) fused
    page pool; page_table: (B, max_pages) i32; pos: (B,) per-slot
    positions (a scalar broadcasts).  See
    :func:`repro.kernels.flash_attention.paged_flash_attention`.

    ``mesh`` (default: the registered serving mesh) shards the *slot*
    axis: each device decodes its contiguous slot group against its
    page-table rows with the pool replicated -- embarrassingly
    parallel, like the contiguous decode path.  A batch that does not
    tile the mesh axis runs unsharded."""
    b = q.shape[0]
    posv = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    kw = dict(window=window, scale=scale, grid_mode=grid_mode,
              backend=backend, verify=verify)
    if mesh is None:
        mesh = _DECODE_MESH
    axis = shard_axis or _DECODE_AXIS
    if mesh is None or b % int(mesh.shape[axis]):
        return paged_attention_kernel(q, kv_pool, page_table, posv, **kw)

    from jax.sharding import PartitionSpec as P

    def device_fn(qd, pool, ptd, posd):
        return paged_attention_kernel(qd, pool, ptd, posd, **kw)

    return jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(P(axis, None, None, None), P(None, None, None, None),
                  P(axis, None), P(axis)),
        out_specs=P(axis, None, None, None), check_vma=False)(
            q, kv_pool, page_table.astype(jnp.int32), posv)


def paged_attention_kernel(*args, **kwargs):
    """Import indirection for the paged Pallas kernel (as
    :func:`flash_attention_kernel`)."""
    from repro.kernels.flash_attention import paged_flash_attention
    return paged_flash_attention(*args, **kwargs)


def decode_attention_paged_xla(q, kv_pool, page_table, pos, *,
                               window: int = 0,
                               scale: Optional[float] = None):
    """Pure-XLA paged decode: gather the mapped pages back into
    contiguous caches, then run :func:`decode_attention`.  The oracle
    of the paged bit-identity tests and the degradation ladder's
    ``paged-xla`` rung (no Pallas in the loop)."""
    from repro.core.paged import gather_kv
    k, v = gather_kv(kv_pool, page_table)
    kind = "local" if window else "causal"
    return decode_attention(q, k, v, pos, kind=kind, window=window,
                            scale=scale)


# ---------------------------------------------------------------------------
# dispatcher
# ---------------------------------------------------------------------------

def attention(q, k, v, *, kind="causal", window=0, scale=None,
              chunk=1024, schedule="dense", flash_threshold=8192):
    """schedule: "dense" | "triangular", or any GridPlan lowering name
    ("closed_form" | "prefetch_lut" | "bounding" | "compact")."""
    sq, sk = q.shape[2], k.shape[2]
    if sq == 1:
        raise ValueError("use decode_attention for single-token queries")
    if max(sq, sk) <= flash_threshold:
        return simple_attention(q, k, v, kind=kind, window=window,
                                scale=scale)
    return flash_attention_xla(q, k, v, kind=kind, window=window,
                               scale=scale, chunk=chunk, schedule=schedule)
