"""Serving throughput: paged continuous batching vs contiguous prealloc.

The paged pool + continuous batching wins on *mixed-length* traffic two
ways the rows make explicit:

  * wall clock -- the contiguous baseline pads every prompt in a wave
    to the wave maximum and decodes the whole wave until its longest
    request finishes; the paged scheduler prefills each request at its
    true length and refills a slot the moment its request completes;
  * memory -- the contiguous server preallocates ``slots x max_len``
    KV up front (internal fragmentation approaches 1 on short
    requests), the pool allocates pages on demand.

Also here: the zig-zag causal shard balance folded into the serving
measurements -- static per-device work imbalance of the contiguous
band partition vs the snake (exact 1.00), plus a wall-clock A/B when
the process actually has multiple devices.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.launch.mesh import make_mesh
from .common import row


def _mixed_requests(vocab: int, n: int, lo: int, hi: int,
                    new_lo: int = 8, new_hi: int = 32, seed: int = 0):
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, n)
    news = rng.integers(new_lo, new_hi + 1, n)
    return ([rng.integers(0, vocab, (int(L),)) for L in lens],
            [int(m) for m in news])


def _contiguous_waves(server, B, requests, max_news):
    """Static batching: waves of ``num_slots`` padded to the wave
    maximum, decoded until the wave's longest request finishes (the
    classic baseline -- short requests ride along to the wave end)."""
    for i in range(0, len(requests), B):
        wave = requests[i:i + B]
        news = max_news[i:i + B]
        lmax = max(len(p) for p in wave)
        prompts = np.stack([np.pad(p, (0, lmax - len(p)), mode="wrap")
                            for p in wave])
        if len(wave) < B:   # ragged tail wave: pad with clones
            prompts = np.pad(prompts, ((0, B - len(wave)), (0, 0)),
                             mode="edge")
        server.generate(prompts, max_new=max(news))


def _paged_drain(server, requests, max_news, rid0: int):
    for j, (prompt, m) in enumerate(zip(requests, max_news)):
        server.submit(rid0 + j, prompt, m)
    while server.pending or any(s is not None for s in server.slots):
        while server._admit_one():
            pass
        server.step()


def run(slot_counts=(2, 4), n_requests: int = 12):
    """Steady-state throughput: both servers are warmed over the full
    request set first (jit traces for every wave / prompt-length shape
    exist), then an identical second pass is timed."""
    from repro.configs import get_config
    from repro.launch.serve import (PagedServeConfig, PagedServer,
                                    ServeConfig, Server)
    from repro.models import init

    print("# serving throughput: paged continuous batching vs "
          "contiguous prealloc (mixed-length)")
    cfg = get_config("quickstart", smoke=True)
    params = init(jax.random.PRNGKey(0), cfg)
    max_len = 64
    requests, max_news = _mixed_requests(
        cfg.vocab_size, n_requests, lo=4, hi=28)
    useful = sum(max_news)
    lens_max = max(len(p) for p in requests)
    assert lens_max + max(max_news) <= max_len

    for B in slot_counts:
        scfg = PagedServeConfig(max_len=max_len, temperature=0.0,
                                num_slots=B, page_size=8,
                                num_pages=2 + B * (max_len // 8),
                                guard=False, validate=False)
        # contiguous static-batching baseline: same requests, same
        # slot count, slots x max_len KV preallocated
        contig = Server(cfg, params, ServeConfig(
            max_len=max_len, temperature=0.0, guard=False,
            validate=False))
        _contiguous_waves(contig, B, requests, max_news)   # warm
        dt_c = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            _contiguous_waves(contig, B, requests, max_news)
            dt_c = min(dt_c, time.perf_counter() - t0)
        live = float(np.mean([len(p) + m for p, m in
                              zip(requests, max_news)]))
        frag_c = 1.0 - live / max_len
        row(f"serve_throughput/contiguous/slots={B}",
            dt_c / useful * 1e6,
            f"tok_per_s={useful / dt_c:.1f},frag={frag_c:.2f}")

        server = PagedServer(cfg, params, scfg)
        _paged_drain(server, requests, max_news, rid0=0)   # warm
        dt_p = float("inf")
        for r in range(1, 4):
            t0 = time.perf_counter()
            _paged_drain(server, requests, max_news,
                         rid0=r * len(requests))
            dt_p = min(dt_p, time.perf_counter() - t0)
        frag = [s["fragmentation"] for s in server.stats_history] or [0]
        row(f"serve_throughput/paged/slots={B}/ps=8",
            dt_p / useful * 1e6,
            f"tok_per_s={useful / dt_p:.1f},"
            f"frag={float(np.mean(frag)):.2f},"
            f"speedup_vs_contiguous={dt_c / dt_p:.2f}")


def run_page_sizes(page_sizes=(4, 8, 16), n_requests: int = 6):
    """Fragmentation/throughput trade of the page-size knob (the axis
    ``repro.core.tune.autotune_paged`` searches)."""
    from repro.configs import get_config
    from repro.launch.serve import PagedServeConfig, PagedServer
    from repro.models import init

    print("# paged page-size sweep (fragmentation vs throughput)")
    cfg = get_config("quickstart", smoke=True)
    params = init(jax.random.PRNGKey(0), cfg)
    requests, max_news = _mixed_requests(
        cfg.vocab_size, n_requests, lo=4, hi=16, new_lo=4,
        new_hi=24, seed=1)
    useful = sum(max_news)
    for ps in page_sizes:
        scfg = PagedServeConfig(max_len=48, temperature=0.0,
                                num_slots=2, page_size=ps,
                                num_pages=2 + 2 * (48 // ps),
                                guard=False, validate=False)
        server = PagedServer(cfg, params, scfg)
        _paged_drain(server, requests, max_news, rid0=0)   # warm
        t0 = time.perf_counter()
        _paged_drain(server, requests, max_news, rid0=len(requests))
        dt = time.perf_counter() - t0
        frag = [s["fragmentation"] for s in server.stats_history] or [0]
        row(f"serve_paged/page_size={ps}", dt / useful * 1e6,
            f"tok_per_s={useful / dt:.1f},"
            f"frag={float(np.mean(frag)):.2f}")


def run_zigzag_balance(device_counts=(2, 4, 8), nby: int = 32):
    """Causal-triangle work balance of the serving prefill shard: the
    contiguous band partition's per-device imbalance vs the zig-zag
    snake (exactly 1.00 by construction).  Static host math -- the
    wall-clock A/B additionally runs when the process has devices."""
    from repro.core.shard import zigzag_row_order

    print("# zig-zag causal shard balance (prefill sharding)")
    for D in device_counts:
        rbd = nby // D
        contig = [sum(j + 1 for j in range(d * rbd, (d + 1) * rbd))
                  for d in range(D)]
        perm = zigzag_row_order(nby, D)
        zz = [sum(j + 1 for j in perm[d * rbd:(d + 1) * rbd])
              for d in range(D)]
        ideal = sum(contig) / D
        row(f"serve_prefill_balance/contiguous/nby={nby}/D={D}",
            0.0, f"imbalance={max(contig) / ideal:.2f}")
        row(f"serve_prefill_balance/zigzag/nby={nby}/D={D}",
            0.0, f"imbalance={max(zz) / ideal:.2f}")

    D = jax.device_count()
    if D < 2 or nby % (2 * D):
        return
    import jax.numpy as jnp

    from repro.kernels import ops
    from .common import time_fn
    rng = np.random.default_rng(0)
    s, d = nby * 16, 16
    q = jnp.asarray(rng.normal(size=(1, 2, s, d)), jnp.float32)
    mesh = make_mesh((D,), ("data",))
    for bal in ("contiguous", "zigzag"):
        t = time_fn(
            lambda: ops.flash_attention(q, q, q, kind="causal",
                                        block_q=16, block_k=16,
                                        mesh=mesh, shard_balance=bal),
            warmup=1, iters=5)
        row(f"serve_prefill_shard/{bal}/s={s}/D={D}", t, "")
