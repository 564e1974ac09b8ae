"""The guarded decode call per ``serve.step``: the ``guard.run`` spans
inside each step of the traced window (dispatch and the host's wait on
the device), mean over the steps."""
from bench.metrics._serve_steps import inside_ms, mean, steps

UNIT = "ms"


def read(ctx):
    spans = steps(ctx)
    return mean(inside_ms(ctx, spans, "guard.run"))
