"""The guard's validators per ``serve.step``: the ``guard.validate``
spans inside each step of the traced window, mean over the steps."""
from bench.metrics._serve_steps import inside_ms, mean, steps

UNIT = "ms"


def read(ctx):
    spans = steps(ctx)
    return mean(inside_ms(ctx, spans, "guard.validate"))
