"""Bring-up check: the system's main path on one TPU, through the entry
points a user calls, checked against the repo's own references.

    python3 chip_smoke.py               # one chip: kernels + serving
    python3 chip_smoke.py --four-chips  # four chips: sharded CA only

Phases (one process, any failure exits non-zero):

1. device -- JAX must find a TPU; there is no CPU path.
2. kernels -- the lambda-map kernels at the paper's sizes with explicit
   schedules: ``sierpinski_write`` / ``sierpinski_sum`` (closed_form,
   bounding, and the table lowerings prefetch_lut / mma) on the embedded
   f32 n=2^14 gasket, compact ``sierpinski_write`` at n=2^16, fused
   ``ca_run`` (parity, fuse=8) at embedded n=2^13 and compact n=2^16,
   each against ``repro.kernels.ref``.  The compact runs take
   closed_form and bounding, whose non-member steps revisit the block
   of the member step before them.
3. serve -- ``PagedServer`` on phi3-mini-3.8b at its published widths
   (bf16 weights from seed 0) with the block-space paged decode kernel,
   then one paged decode step against ``logits_fn``'s full forward, and
   every token the server chose against the forward over its request.

``--four-chips`` runs only the halo-sharded compact ``ca_run`` on a
4-device mesh and compares it bit for bit with the same run on one
device.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

#: bf16 tolerance of the decode-vs-forward logits check: relative L2
#: error ||decode - forward|| / ||forward||.  The two paths round to
#: bf16 at different points (the paged kernel accumulates attention in
#: f32 over bf16 K/V pages; the forward runs XLA attention over the
#: whole sequence), and bf16 keeps 8 significant bits (2^-8 = 0.4%).
#: Through a stack of layers these differences add up: the same check
#: on the CPU at cut widths gives 1.2% for 8 layers and 1.7% for 32.
#: 5% leaves room for the chip's own rounding and still fails a real
#: error (a wrong page, position or mask moves the logits by O(1)).
LOGITS_REL_TOL = 5e-2
#: The served stream is held against the forward too: every greedy
#: token must be the forward's argmax at its position, or lie within
#: STREAM_MARGIN times the single step's max |decode - forward| logit
#: difference of it.  The two paths can only pick different tokens
#: where the forward's top logits are that close (bf16 near-ties); a
#: wrong page, position or slot in the batched server moves a chosen
#: token's logit O(1) below the maximum.
STREAM_MARGIN = 8.0


def log(msg: str) -> None:
    print(msg, flush=True)


class Timer:
    def __init__(self, what: str):
        self.what = what

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            log(f"  {self.what}: ok "
                f"({time.perf_counter() - self.t0:.1f} s incl. compile)")
        return False


def check(ok, what: str) -> None:
    if not bool(ok):
        raise AssertionError(f"chip_smoke: {what}")


# ---------------------------------------------------------------------------
# phase 2: the lambda-map kernels
# ---------------------------------------------------------------------------

def _slot_block_grids(layout):
    """(BX, BY) i32 host grids over the packed slot grid: the embedded
    block held by each slot, -1 where a slot holds none.  Built from
    the layout's host tables, not from the kernels' in-kernel decode."""
    import numpy as np
    scols, srows = layout.grid_shape
    bx = np.full((srows, scols), -1, np.int32)
    by = np.full((srows, scols), -1, np.int32)
    coords = layout.domain.coords_host()
    slots = layout.slots_host()
    bx[slots[:, 1], slots[:, 0]] = coords[:, 0]
    by[slots[:, 1], slots[:, 0]] = coords[:, 1]
    return bx, by


def _compact_write_ref(packed, value, layout, n, block):
    """Expected compact write: every gasket cell of every occupied slot
    takes ``value`` (the ``x & (n-1-y) == 0`` bit test on the cell's
    embedded coordinates), everything else keeps its input."""
    import jax
    import jax.numpy as jnp
    bx, by = (jnp.asarray(g) for g in _slot_block_grids(layout))

    @jax.jit
    def ref(m, bx, by):
        rows, cols = m.shape
        gx = jnp.repeat(jnp.repeat(bx, block, 0), block, 1)
        gy = jnp.repeat(jnp.repeat(by, block, 0), block, 1)
        x = gx * block + jax.lax.broadcasted_iota(jnp.int32, m.shape, 1) \
            % block
        y = gy * block + jax.lax.broadcasted_iota(jnp.int32, m.shape, 0) \
            % block
        member = (gx >= 0) & ((x & (n - 1 - y)) == 0)
        return jnp.where(member, jnp.asarray(value, m.dtype), m)

    return ref(packed, bx, by)


def _window_blocks(layout, n, m, block):
    """Packed slots of the bottom-left m x m sub-gasket of the n-gasket
    (rows [n-m, n), cols [0, m)), which is itself an m-gasket:
    ((wy, wx) window block, (sy, sx) slot) index arrays for its member
    blocks."""
    import numpy as np
    coords = layout.domain.coords_host()
    slots = layout.slots_host()
    lo = (n - m) // block
    inw = (coords[:, 0] < m // block) & (coords[:, 1] >= lo)
    return (coords[inw, 1] - lo, coords[inw, 0],
            slots[inw, 1], slots[inw, 0])


def _ca_window_check(n, m, block, steps, fuse, seed,
                     grid_mode="closed_form", **kw):
    """Fused compact ``ca_run`` at size n, checked on a window.

    The packed state holds random parity bits only inside the
    bottom-left m-sub-gasket, at least ``steps + 2`` cells from the
    edges it shares with the rest of the gasket.  Influence travels one
    cell per step, so after ``steps`` steps every cell outside the
    window is still 0 and the window has evolved exactly like a
    standalone m-gasket: ``steps`` applications of ``ref.ca_step_ref``
    to the m x m window."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    from repro.core.fractal import membership_grid
    from repro.kernels import ops, ref

    layout = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                               n // block))
    wy, wx, sy, sx = (jnp.asarray(a) for a in
                      _window_blocks(layout, n, m, block))
    margin = steps + 2
    key = jax.random.PRNGKey(seed)
    bits = jax.random.bernoulli(key, 0.5, (m, m))
    yy, xx = np.mgrid[0:m, 0:m]
    keep = membership_grid(m) & (yy >= margin) & (xx < m - margin)
    win0 = jnp.where(jnp.asarray(keep), bits, False).astype(jnp.float32)

    @jax.jit
    def to_packed(win):
        tiles = win.reshape(m // block, block, m // block, block)
        tiles = tiles.transpose(0, 2, 1, 3)[wy, wx]
        rows, cols = layout.array_shape(block)
        p = jnp.zeros((rows // block, cols // block, block, block),
                      jnp.float32).at[sy, sx].set(tiles)
        return p.transpose(0, 2, 1, 3).reshape(rows, cols)

    @jax.jit
    def from_packed(p):
        rows, cols = p.shape
        t = p.reshape(rows // block, block, cols // block, block)
        tiles = t.transpose(0, 2, 1, 3)[sy, sx]
        w = jnp.zeros((m // block, m // block, block, block),
                      jnp.float32).at[wy, wx].set(tiles)
        return w.transpose(0, 2, 1, 3).reshape(m, m)

    def ref_run(w):
        # eager: under jit the reference's host-built membership grid
        # would be baked into the program as a constant
        for _ in range(steps):
            w = ref.ca_step_ref(w, "parity")
        return w

    a = to_packed(win0)
    b = jnp.zeros_like(a)
    out = ops.ca_run(a, b, steps, fuse=fuse, rule="parity", block=block,
                     grid_mode=grid_mode, storage="compact", n=n,
                     coarsen=1, **kw)
    got = from_packed(out)
    want = ref_run(win0)
    check(float(jnp.sum(want)) > 0, "CA window evolved to all zeros")
    check(jnp.array_equal(got, want), f"compact ca_run {grid_mode} "
          f"n={n} differs from ca_step_ref on the window")
    check(float(jnp.sum(out)) == float(jnp.sum(got)),
          f"compact ca_run {grid_mode} n={n} wrote outside the window")
    return out


def phase_kernels(*, n_embed: int = 1 << 14, n_ca: int = 1 << 13,
                  n_compact: int = 1 << 16, block: int = 128,
                  ca_steps: int = 20, compact_steps: int = 24,
                  fuse: int = 8, seed: int = 0) -> None:
    """The paper's kernels with explicit schedules, each against
    ``repro.kernels.ref``.  Sizes default to the paper's; tests call
    this at tiny sizes under the interpreter."""
    import jax
    import jax.numpy as jnp

    from repro.core.compact import CompactLayout
    from repro.core.domain import make_fractal_domain
    from repro.core.fractal import membership_grid
    from repro.kernels import ops, ref

    log(f"kernels: embedded n={n_embed}, block {block}")
    key = jax.random.PRNGKey(seed)
    # small integers: every partial sum is exact in f32, so sums of any
    # accumulation order must agree exactly
    m = jax.random.randint(key, (n_embed, n_embed), 0, 4).astype(
        jnp.float32)
    # the references run eagerly: under jit their host-built
    # membership grids would become program constants
    want_w = ref.sierpinski_write_ref(m, 7.0)
    want_s = float(ref.sierpinski_sum_ref(m))
    for gm in ("closed_form", "bounding", "prefetch_lut", "mma"):
        sched = dict(block=block, grid_mode=gm, coarsen=1, num_stages=1)
        with Timer(f"sierpinski_write {gm}"):
            out = ops.sierpinski_write(m, 7.0, **sched)
            check(jnp.array_equal(out, want_w),
                  f"sierpinski_write {gm} differs from the reference")
        del out
        with Timer(f"sierpinski_sum {gm}"):
            got = float(ops.sierpinski_sum(m, **sched))
            check(got == want_s, f"sierpinski_sum {gm}: {got} != {want_s}")
    del m, want_w

    log(f"kernels: compact sierpinski_write n={n_compact}")
    lay = CompactLayout(make_fractal_domain("sierpinski-gasket",
                                            n_compact // block))
    p = jax.random.randint(key, lay.array_shape(block), 0, 4).astype(
        jnp.float32)
    want = _compact_write_ref(p, 7.0, lay, n_compact, block)
    for gm in ("closed_form", "bounding"):
        with Timer(f"compact sierpinski_write {gm} "
                   f"{lay.array_shape(block)}"):
            out = ops.sierpinski_write(p, 7.0, block=block, grid_mode=gm,
                                       storage="compact", n=n_compact,
                                       coarsen=1, num_stages=1)
            check(jnp.array_equal(out, want), f"compact sierpinski_write "
                  f"{gm} differs from the gasket bit test")
        del out
    del p, want

    log(f"kernels: fused ca_run parity fuse={fuse}")
    with Timer(f"embedded ca_run n={n_ca}, {ca_steps} steps"):
        bits = jax.random.bernoulli(key, 0.5, (n_ca, n_ca))
        s0 = jnp.where(jnp.asarray(membership_grid(n_ca)), bits,
                       False).astype(jnp.float32)
        want = s0
        for _ in range(ca_steps):
            want = ref.ca_step_ref(want, "parity")
        got = ops.ca_run(s0, jnp.zeros_like(s0), ca_steps, fuse=fuse,
                         rule="parity", block=block,
                         grid_mode="closed_form", coarsen=1,
                         num_stages=1)
        check(jnp.array_equal(got, want),
              "embedded ca_run differs from ca_step_ref")
    del s0, want, got
    for gm in ("closed_form", "bounding"):
        with Timer(f"compact ca_run {gm} n={n_compact}, "
                   f"{compact_steps} steps"):
            _ca_window_check(n_compact, n_ca, block, compact_steps, fuse,
                             seed, grid_mode=gm, num_stages=1)


# ---------------------------------------------------------------------------
# phase 3: serving at published widths
# ---------------------------------------------------------------------------

def phase_serve(cfg, *, num_slots: int = 4, page_size: int = 16,
                prompt_lens=(256, 1024), n_requests: int = 8,
                max_new: int = 32, seed: int = 0) -> None:
    """``PagedServer`` on ``cfg`` with bf16 weights from ``seed`` and the
    block-space paged decode kernel, then one paged decode step checked
    against ``logits_fn``'s full forward on the same tokens."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.paged import pages_for
    from repro.launch.serve import PagedServeConfig, PagedServer
    from repro.models import model as model_lib
    from repro.runtime.guard import ServerState

    cfg = cfg.replace(param_dtype=cfg.dtype,
                      attn_decode_kernel="blockspace")
    log(f"serve: {cfg.name}: {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads} heads (kv {cfg.n_kv_heads}), "
        f"head_dim {cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.param_dtype} weights")
    with Timer("init weights"):
        params = jax.jit(partial(model_lib.init, cfg=cfg))(
            jax.random.PRNGKey(seed))
        jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    dtypes = {str(x.dtype) for x in jax.tree.leaves(params)}
    log(f"  {n_params} parameters, dtypes {sorted(dtypes)}")
    check(dtypes == {cfg.param_dtype}, f"weights not all {cfg.param_dtype}")

    max_len = max(prompt_lens) + max_new
    per_slot = pages_for(max_len, page_size)
    scfg = PagedServeConfig(max_len=max_len, temperature=0.0, seed=seed,
                            num_slots=num_slots, page_size=page_size,
                            num_pages=1 + num_slots * per_slot)
    rng = np.random.default_rng(seed)
    requests = [rng.integers(0, cfg.vocab_size,
                             (prompt_lens[i % len(prompt_lens)],))
                for i in range(n_requests)]
    with Timer(f"PagedServer: {n_requests} requests "
               f"{list(prompt_lens)} tokens, {max_new} new each, "
               f"{num_slots} slots, {scfg.num_pages} pages of "
               f"{page_size}"):
        server = PagedServer(cfg, params, scfg)
        t0 = time.perf_counter()
        done = server.run(requests, max_new=max_new)
        dt = time.perf_counter() - t0
    log(f"  served {sum(len(v) for v in done.values())} tokens in "
        f"{dt:.2f} s (host clock, compiles included)")
    check(sorted(done) == list(range(n_requests)),
          f"finished {sorted(done)}")
    check(all(len(v) == max_new for v in done.values()),
          "a request stopped short")
    check(not server.ladder.transitions,
          f"ladder stepped down: {server.ladder.transitions}")
    check(server.state == ServerState.HEALTHY, f"state {server.state}")

    # one paged decode step vs the full forward on the same tokens: the
    # first request's prompt and generated tokens, minus the last
    toks = np.concatenate([requests[0], done[0][:-1]]).astype(np.int32)
    ctx, last = toks[:-1], toks[-1:]
    pages = np.arange(1, 1 + pages_for(len(toks), page_size),
                      dtype=np.int32)
    table = np.zeros((num_slots, server.max_pages), np.int32)
    table[0, :len(pages)] = pages
    posv = np.zeros((num_slots,), np.int32)
    posv[0] = len(ctx)
    act = np.zeros((num_slots,), bool)
    act[0] = True
    tok_in = np.zeros((num_slots, 1), np.int32)
    tok_in[0, 0] = last[0]
    pools = server.pools
    del server
    with Timer(f"decode-vs-forward logits at position {len(ctx)}"):
        _, caches = jax.jit(partial(model_lib.prefill, cfg=cfg))(
            params, jnp.asarray(ctx[None]))
        pools = jax.jit(partial(model_lib.scatter_prefill_pages,
                                cfg=cfg))(pools, caches,
                                          jnp.asarray(pages))
        del caches
        dec, _ = jax.jit(partial(model_lib.decode_step_paged, cfg=cfg))(
            params, jnp.asarray(tok_in), pools, jnp.asarray(table),
            jnp.asarray(posv), jnp.asarray(act))
        del pools
        full, _ = jax.jit(partial(model_lib.logits_fn, cfg=cfg))(
            params, jnp.asarray(toks[None]))
        d = np.asarray(dec[0, 0], np.float32)
        f = np.asarray(full[0, -1], np.float32)
        rel = float(np.linalg.norm(d - f) / np.linalg.norm(f))
        log(f"  relative L2 error {rel:.3e} (tolerance "
            f"{LOGITS_REL_TOL}), max |diff| {np.max(np.abs(d - f)):.3e}, "
            f"max |logit| {np.max(np.abs(f)):.3e}, argmax "
            f"{'agrees' if d.argmax() == f.argmax() else 'differs'}")
        check(np.isfinite(d).all(), "non-finite decode logits")
        check(rel <= LOGITS_REL_TOL,
              f"decode logits off the forward by {rel:.3e}")
    _check_stream(cfg, params, requests, done, max_new,
                  STREAM_MARGIN * float(np.max(np.abs(d - f))))


def _check_stream(cfg, params, requests, done, max_new, margin):
    """Each request's greedy tokens, as the server's continuous batching
    chose them (its own page tables, page growth and slot refill),
    against one ``logits_fn`` forward over prompt + generated tokens:
    the chosen token's forward logit must lie within ``margin`` of the
    forward's maximum at its position.

    The maximum and the chosen logit are taken on the host, from one
    copy of the logits.  Inside one jit the chip's compiler may take
    the maximum over the bf16 logits and the chosen value from an f32
    copy it never rounded (excess precision), so no gap would be 0."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as model_lib

    @partial(jax.jit, static_argnames=("start",))
    def window(params, toks, start):
        logits, _ = model_lib.logits_fn(params, toks, cfg=cfg)
        return logits[:, start:start + max_new].astype(jnp.float32)

    def gaps(params, toks, start):
        sl = np.asarray(window(params, jnp.asarray(toks), start=start))
        chosen = np.take_along_axis(
            sl, toks[:, start + 1:start + 1 + max_new, None], axis=-1)[..., 0]
        return sl.max(axis=-1) - chosen

    by_len = {}
    for rid, prompt in enumerate(requests):
        by_len.setdefault(len(prompt), []).append(rid)
    worst, exact, total = 0.0, 0, 0
    with Timer(f"served tokens vs forward ({len(requests)} requests)"):
        for plen, rids in sorted(by_len.items()):
            toks = np.stack([np.concatenate([requests[r], done[r]])
                             for r in rids]).astype(np.int32)
            g = gaps(params, toks, plen - 1)
            check(np.isfinite(g).all(), "non-finite forward logits")
            worst = max(worst, float(g.max()))
            exact += int(np.sum(g == 0))
            total += g.size
        log(f"  {exact}/{total} served tokens are the forward's argmax; "
            f"largest gap {worst:.3e} (margin {margin:.3e})")
        check(worst <= margin,
              f"a served token is {worst:.3e} below the forward's best "
              f"logit (margin {margin:.3e})")


# ---------------------------------------------------------------------------
# --four-chips: the halo-sharded compact CA
# ---------------------------------------------------------------------------

def phase_four_chips(*, n: int = 1 << 16, window: int = 1 << 13,
                     block: int = 128, steps: int = 24, fuse: int = 8,
                     devices: int = 4, seed: int = 0) -> None:
    """Compact ``ca_run`` sharded over ``devices`` chips (orthotope row
    slabs, ``ppermute`` ghost exchange between launches), synchronous
    and with the interior/boundary overlap, each bit for bit against
    the same run on one device -- which is itself checked against
    ``ca_step_ref`` on a window."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) >= devices,
          f"--four-chips needs {devices} devices, found "
          f"{len(jax.devices())}")
    mesh = make_mesh((devices,), ("data",),
                     devices=jax.devices()[:devices])
    log(f"four chips: compact ca_run n={n}, {steps} steps, fuse {fuse}, "
        f"mesh {dict(mesh.shape)}")
    with Timer("one device (checked against ca_step_ref on a window)"):
        one = np.asarray(_ca_window_check(
            n, window, block, steps, fuse, seed, num_stages=1))
    for stages in (1, 2):
        with Timer(f"{devices} devices, num_stages={stages}"):
            out = _ca_window_check(
                n, window, block, steps, fuse, seed, num_stages=stages,
                mesh=mesh)
            check(np.array_equal(np.asarray(out), one),
                  f"sharded ca_run (num_stages={stages}) is not "
                  f"bit-identical to one device")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the halo-sharded CA on 4 devices")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    count = len(jax.devices())
    log(f"device: {dev.device_kind} x{count}, jax {jax.__version__}")

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.core import backend
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache() or os.environ["JAX_COMPILATION_CACHE_DIR"]
    log(f"compile cache: {cache}")
    check(backend.resolve(None) is backend.TPU,
          f"default kernel target is {backend.resolve(None).name}")

    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips()
    else:
        from repro.configs import get_config
        phase_kernels()
        phase_serve(get_config("phi3-mini-3.8b"))
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
