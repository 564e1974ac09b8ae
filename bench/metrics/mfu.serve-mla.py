"""Model FLOPs of the latent-attention serving cell's traced window, per
second of the window, over the chip's bf16 peak: every decoded token in
the absorbed form at its live context, the routes its held experts
computed (the program's ``moe_routes_held`` of each step), and the
prompt tokens of the admissions in the window (without their routed
experts, which a prefill does not report)."""
from bench import counts_mla

UNIT = "%"


def read(ctx):
    steps, dm = ctx.work.get("mla_contexts"), ctx.work.get("mla_dims")
    if not steps or not dm or ctx.trace.window_s <= 0:
        return None
    flops = sum(counts_mla.decode_flops_per_token(context=c, dm=dm)
                for contexts in steps for c in contexts)
    flops += sum(counts_mla.routed_flops(r, dm)
                 for r in ctx.work.get("moe_routes_held", ()))
    flops += sum(counts_mla.prefill_flops(tokens=t, dm=dm)
                 for t in ctx.work.get("mla_prefill_tokens", ()))
    return 100.0 * flops / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]
