"""Model FLOPs of every token the serving cell processed in the traced
window (generated tokens at their live context, prompt tokens of the
admissions in it), per second of the window, over the chip's bf16
peak."""
from bench import counts

UNIT = "%"


def read(ctx):
    steps, dm = ctx.work.get("decode_contexts"), ctx.work.get("dims")
    if not steps or not dm or ctx.trace.window_s <= 0:
        return None
    flops = sum(counts.model_flops_per_token(context=c, **dm)
                for contexts in steps for c in contexts)
    flops += sum(counts.prefill_flops(tokens=t, **dm)
                 for t in ctx.work.get("prefill_tokens", ()))
    return 100.0 * flops / ctx.trace.window_s / ctx.peaks["bf16_flops_per_s"]
