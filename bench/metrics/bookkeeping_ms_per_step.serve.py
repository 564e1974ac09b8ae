"""The serving host path around the guarded call, per ``serve.step``:
each step of the traced window less its ``guard.call`` spans (page
growth, input uploads, the logits' copy, sampling, table checks and
pool statistics), mean over the steps."""
from bench.metrics._serve_steps import inside_ms, mean, steps

UNIT = "ms"


def read(ctx):
    spans = steps(ctx)
    guarded = inside_ms(ctx, spans, "guard.call")
    return mean([1e-6 * (b - a) - g for (a, b), g in zip(spans, guarded)])
