"""Block-space attention: the paper's compact-vs-bounding-box comparison
applied to causal attention (DESIGN.md SS3).

Measures (a) compiled HLO FLOPs of the dense (bounding-box) vs
triangular (compact) schedules -- the Theorem-2 work ratio in the LM
setting -- and (b) CPU wall clock at a small config.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import LOWERINGS
from repro.kernels import ops
from repro.launch.hlo_analysis import analyze
from repro.models.attention import flash_attention_xla
from .common import row, time_fn


def hlo_flops(schedule, b, h, s, d, chunk):
    def f(q, k, v):
        return flash_attention_xla(q, k, v, kind="causal", chunk=chunk,
                                   schedule=schedule)
    spec = jax.ShapeDtypeStruct((b, h, s, d), jnp.float32)
    compiled = jax.jit(f).lower(spec, spec, spec).compile()
    return analyze(compiled.as_text()).flops


def run_kernel_lowerings(iters: int = 5):
    """GridPlan lowering A/B on the Pallas flash kernel, per attention
    block domain (triangular / band / bounding-box) and block size."""
    print("# Pallas flash kernel: GridPlan lowering A/B per domain")
    rng = np.random.default_rng(0)
    for kind, kw, s, bq in (("causal", {}, 256, 64),
                            ("causal", {}, 256, 128),
                            ("local", {"window": 128}, 256, 64),
                            ("full", {}, 256, 64)):
        q = jnp.asarray(rng.normal(size=(1, 2, s, 32)), jnp.float32)
        t_closed = None
        for low in LOWERINGS:
            fn = functools.partial(ops.flash_attention, kind=kind,
                                   block_q=bq, block_k=bq,
                                   grid_mode=low, **kw)
            t = time_fn(fn, q, q, q, warmup=2, iters=iters)
            if t_closed is None:
                t_closed = t
            row(f"gridplan_flash/{kind}/s={s}/bq={bq}/{low}", t,
                f"speedup_vs_closed_form={t_closed / t:.2f}")


def run_backend_ab(iters: int = 5):
    """Flash-kernel A/B on the default emission target: compact vs
    bounding lowering."""
    from repro.core import backend as backend_lib
    print("# Pallas flash kernel: backend x lowering A/B (causal)")
    rng = np.random.default_rng(0)
    s, bq = 256, 64
    q = jnp.asarray(rng.normal(size=(1, 2, s, 32)), jnp.float32)
    for tname in (backend_lib.resolve(None).name,):
        times = {}
        for low in ("closed_form", "bounding"):
            fn = functools.partial(ops.flash_attention, kind="causal",
                                   block_q=bq, block_k=bq,
                                   grid_mode=low, backend=tname)
            times[low] = time_fn(fn, q, q, q, warmup=2, iters=iters)
        row(f"backend_flash/{tname}/s={s}/bq={bq}/closed_form",
            times["closed_form"],
            f"speedup_vs_bounding="
            f"{times['bounding'] / times['closed_form']:.2f}")
        row(f"backend_flash/{tname}/s={s}/bq={bq}/bounding",
            times["bounding"], "")


def run():
    run_kernel_lowerings()
    run_backend_ab()
    print("# causal flash attention: dense (BB) vs triangular (compact)")
    b, h, d = 1, 4, 64
    for s, chunk in ((2048, 256), (4096, 512), (8192, 1024)):
        fd = hlo_flops("dense", b, h, s, d, chunk)
        ft = hlo_flops("triangular", b, h, s, d, chunk)
        row(f"attn_flops_dense/s={s}", 0.0, f"hlo_flops={fd:.3e}")
        row(f"attn_flops_tri/s={s}", 0.0,
            f"hlo_flops={ft:.3e};work_ratio={fd / ft:.3f}")

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 4, 2048, 64)), jnp.float32)
    fn_d = jax.jit(functools.partial(flash_attention_xla, kind="causal",
                                     chunk=256, schedule="dense"))
    fn_t = jax.jit(functools.partial(flash_attention_xla, kind="causal",
                                     chunk=256, schedule="triangular"))
    td = time_fn(fn_d, q, q, q, iters=10)
    tt = time_fn(fn_t, q, q, q, iters=10)
    row("attn_wall_dense/s=2048", td, "")
    row("attn_wall_tri/s=2048", tt, f"speedup={td / tt:.2f}")


if __name__ == "__main__":
    run()
